"""The env that makes the program build a configuration of this family:
the configuration's FILE, named under ``MODEL_CONFIG`` (the key
``frameworks/jax/svc_serve.yml`` templates into the task's env; the
program's ``config_from_env`` reads the file's published key names,
``head_dim``, ``layer_types`` with ``sliding_attention`` beside
``full_attention``, ``sliding_window``, ``num_shared_experts``,
``score_func``, ``route_norm``, ``route_scale`` and ``mup_enabled``
among them, and lets the file win over the size names).  The size names
are sent too, equal to the file, so that what the YAML templates never
contradicts it and ``/stats``' ``model`` can be checked name by name.

What the published ``config.json`` has no key for, the file states
under the program's own names (``qk_norm``, ``attention_gate``,
``sandwich_norm``, ``nope_on_full_attention``, ``use_expert_bias``,
``route_norm_eps``) and its ``assumed`` says from where; the family's
reference computes those equations whatever the file says, so a file
that switches one off is refused here.

A program from before window layers refuses the file at once
(``head_dim`` x heads is not ``hidden_size`` there, and
``sliding_attention`` names no operator): the task exits during deploy.
"""

from __future__ import annotations

import os

# the equations of the family's reference that a file states as switches
ASSUMED = {
    "qk_norm": True, "attention_gate": True, "sandwich_norm": True,
    "nope_on_full_attention": True, "use_expert_bias": True,
    "route_norm_eps": 1e-20, "mup_enabled": True, "route_norm": True,
    "score_func": "sigmoid", "tie_word_embeddings": False,
    "num_shared_experts": 1,
}


def program_env(model: dict, config_path: str) -> dict:
    if model.get("model_type") != "afmoe":
        raise ValueError(
            "family afmoe builds model_type \"afmoe\" alone, this "
            f"configuration states {model.get('model_type')!r}"
        )
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(model['layer_types'])} layers, "
            f"num_hidden_layers is {model['num_hidden_layers']}"
        )
    for key, want in ASSUMED.items():
        if model.get(key) != want:
            raise ValueError(
                f"the family's reference computes {key} = {want!r}, this "
                f"configuration states {model.get(key)!r}"
            )
    templated = {
        "VOCAB": model["vocab_size"],
        "D_MODEL": model["hidden_size"],
        "N_LAYERS": model["num_hidden_layers"],
        "MODEL_CONFIG": os.path.abspath(config_path),
    }
    routed = {
        "N_HEADS": model["num_attention_heads"],
        "N_KV_HEADS": model["num_key_value_heads"],
        "D_FF": model["intermediate_size"],
        "N_EXPERTS": model["num_experts"],
    }
    env = {k: str(v) for k, v in templated.items()}
    env.update({f"TASKCFG_ALL_{k}": str(v) for k, v in routed.items()})
    return env
