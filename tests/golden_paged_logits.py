"""Logits of the two serving programs for tiny twins of the three
configurations the benchmark had before window layers (a grouped-query
mixture scanned as one kind, EVA's window and summaries, a conv pattern
walked by periods): what tests/test_paged_logits_golden.py holds the
programs to.  Run as a script it prints them as JSON; PR 44 recorded
tests/golden_paged_logits.json with it from the PARENT commit's tree
(``PYTHONPATH=<parent checkout> python tests/golden_paged_logits.py``)."""

import json
import sys

import numpy as np

PAGE, CHUNK, SLOTS, PROMPT, STEPS = 4, 8, 2, 19, 3


def twins():
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    common = dict(
        vocab=128, d_model=64, n_heads=4, d_ff=96, dtype=jnp.float32,
        remat=False,
    )
    return {
        "mixtral": TransformerConfig(
            n_layers=2, n_kv_heads=2, n_experts=4, moe_top_k=2, **common
        ),
        "evabyte": TransformerConfig(
            n_layers=2, n_kv_heads=4, attention="eva", window_size=16,
            chunk_size=4, rms_norm_eps=1e-5, tie_embeddings=False, **common
        ),
        "lfm2": TransformerConfig(
            n_layers=9, n_kv_heads=2, n_experts=8, moe_top_k=2, moe_d_ff=48,
            layer_types=("conv",) + ("attention", "conv", "conv", "conv") * 2,
            n_dense_layers=1, moe_score="sigmoid", moe_expert_bias=True,
            qk_norm=True, rms_norm_eps=1e-5, rope_theta=1e6, **common
        ),
    }


def logits_of(config):
    """Every logit the two programs return for one prompt of 19 tokens
    prefilled in chunks of 8 and decoded 3 steps, in a pool of 2 rows
    whose other row is idle: ``[3 chunks + 3 steps, vocab]``."""
    import jax

    from dcos_commons_tpu.models import init_params
    from dcos_commons_tpu.models.decode import (
        init_paged_kv_cache,
        paged_decode_step,
        paged_prefill_chunk,
    )
    from dcos_commons_tpu.serve.paging import RowLayout

    params = init_params(config, jax.random.key(1))
    eva = config.attention == "eva"
    layout = RowLayout(
        PAGE, config.window_size if eva else 0,
        config.chunk_size if eva else 0,
    )
    entries = layout.table_len(64)
    cache = init_paged_kv_cache(config, 2 * entries + 1, PAGE, slots=SLOTS)
    table = np.arange(1, entries + 1, dtype=np.int32)
    prompt = np.random.default_rng(7).integers(0, 128, PROMPT)
    out = []
    for start in range(0, PROMPT, CHUNK):
        true_len = min(CHUNK, PROMPT - start)
        tokens = np.zeros((1, CHUNK), np.int32)
        tokens[0, :true_len] = prompt[start:start + true_len]
        logits, cache, *_ = paged_prefill_chunk(
            config, params, cache, tokens, table, start, true_len, 1
        )
        out.append(np.asarray(logits[0]))
    token = int(np.argmax(out[-1]))
    tables = np.zeros((SLOTS, entries), np.int32)
    tables[1] = table
    for step in range(STEPS):
        logits, cache, *_ = paged_decode_step(
            config, params, cache, np.array([0, token], np.int32),
            np.array([0, PROMPT + step], np.int32), tables,
        )
        out.append(np.asarray(logits[1]))
        token = int(np.argmax(out[-1]))
    return np.stack(out)


if __name__ == "__main__":
    json.dump(
        {name: logits_of(config).tolist() for name, config in twins().items()},
        sys.stdout,
    )
