"""The env that makes the program build a configuration of this family:
the configuration's FILE, named under ``MODEL_CONFIG`` (the key
``frameworks/jax/svc_serve.yml`` templates into the task's env; the
program's ``config_from_env`` reads the file's published key names and
lets it win over the size names).  The eight size names are sent too,
equal to the file, so that what the YAML templates never contradicts it
and ``/stats``' ``model`` can be checked name by name.

A program from before ``MODEL_CONFIG`` ignores the name and builds a
grouped-query decoder of these widths; the worker entry's comparison of
parameter trees then stops it before anything is built (no ``lm_head``,
``eva_phi``, ``eva_mu``), so the cell fails at once there.
"""

from __future__ import annotations

import os


def program_env(model: dict, config_path: str) -> dict:
    if model.get("attention_class") != "eva":
        raise ValueError(
            "family eva_decoder builds attention_class \"eva\" alone, "
            f"this configuration states {model.get('attention_class')!r}"
        )
    if model["window_size"] % (model["chunk_size"] ** 2):
        raise ValueError(
            "the program keeps one chunk a cache page and whole pages of "
            "summaries a window: window_size must be a multiple of "
            "chunk_size squared"
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
        "N_EXPERTS": 0,
    }
    env = {k: str(v) for k, v in templated.items()}
    env.update({f"TASKCFG_ALL_{k}": str(v) for k, v in routed.items()})
    return env
