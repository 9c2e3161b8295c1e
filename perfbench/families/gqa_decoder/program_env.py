"""The env that makes the program build a configuration of this family:
``config_from_env``'s names for a pre-norm decoder with grouped-query
attention and a dense or mixture SwiGLU feed-forward.  Three sizes are
templated into ``svc_serve.yml``; the others reach the task through the
``TASKCFG_ALL_`` prefix.
"""

from __future__ import annotations


def program_env(model: dict, config_path: str) -> dict:
    """``config_path`` is not used: this program is sized by env names,
    not by a file."""
    if model["head_dim"] * model["num_attention_heads"] != model["hidden_size"]:
        raise ValueError(
            "the program derives head_dim as hidden_size / heads; "
            "this configuration states another"
        )
    templated = {
        "VOCAB": model["vocab_size"],
        "D_MODEL": model["hidden_size"],
        "N_LAYERS": model["num_hidden_layers"],
    }
    routed = {
        "N_HEADS": model["num_attention_heads"],
        "N_KV_HEADS": model["num_key_value_heads"],
        "D_FF": model["intermediate_size"],
        "N_EXPERTS": model.get("num_local_experts", 0),
    }
    env = {k: str(v) for k, v in templated.items()}
    env.update({f"TASKCFG_ALL_{k}": str(v) for k, v in routed.items()})
    return env
