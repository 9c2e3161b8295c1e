"""The env that makes the program build a configuration of this family:
the configuration's FILE, named under ``MODEL_CONFIG`` (the key
``frameworks/jax/svc_serve.yml`` templates into the task's env; the
program's ``config_from_env`` reads the file's published key names,
``layer_types`` and the nested ``rope_parameters.rope_theta`` among
them, and lets the file win over the size names).  The size names are
sent too, equal to the file, so that what the YAML templates never
contradicts it and ``/stats``' ``model`` can be checked name by name.

A program from before the layer pattern ignores ``layer_types`` and
builds a grouped-query mixture decoder of these widths; the worker
entry's comparison of parameter trees then stops it before anything is
built (no ``layers/conv/...``, no ``expert_bias``), so the cell fails at
once there.
"""

from __future__ import annotations

import os


def program_env(model: dict, config_path: str) -> dict:
    if model.get("model_type") != "lfm2_moe":
        raise ValueError(
            "family lfm2_moe builds model_type \"lfm2_moe\" alone, this "
            f"configuration states {model.get('model_type')!r}"
        )
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(model['layer_types'])} layers, "
            f"num_hidden_layers is {model['num_hidden_layers']}"
        )
    if model.get("conv_bias"):
        raise ValueError("the program's conv operator has no bias")
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
