"""What one decode tick and one prefill chunk of an LFM2 mixture decoder
NEED: operations and bytes from the configuration's shapes and the
call's live rows and tokens, not from what the program happens to read.
The weights outside the experts stream once a call; keys and values of
the live tokens once an ATTENTION layer (the conv layers keep none); a
row's conv state in and out once a conv layer; the rows' activations
once a layer.

Of each expert layer a call reads the experts its tokens are expected
to choose under even routing (``experts_touched``: 63 of 64 for a chunk
of 64 tokens choosing 4, 30 for a decode tick of 10 rows, 44 at 18):
the bytes of a tick move with its rows.  So ``decode_tick`` is only as
good as the rows it is handed.  ``decode_step_roofline.chat`` hands it a
gauge's mean over the minute in which the profile is written, during
which rows pile up behind a slowed worker (16 where the traced steps had
9), and sets that beside the median step of the 4 traced seconds: it
read 76.8, 79.7, 94.5 and 117.4% of one program in four traced runs
(PERF.md section 6, PR 33), so ``lfm2-24b.chat`` is NOT among that
metric's cells until its reader takes the rows of the traced calls
themselves (``loop.decode_rows_sum``; PERF.md section 7o).  In that cell
``moe_experts_touched_per_layer.chat`` and, for the kernel,
``moe_grouped_matmul_roofline.chat`` read the calls' own counts.

``moe_grouped_matmul`` is ONE expert layer's three grouped products
(the ``tpu_custom_call`` named ``gmm``, dcos_commons_tpu/ops/
grouped_matmul.py), for the assignments and the expert groups a call
really had (the engine counts both: ``loop.moe_assignments_sum``,
``loop.moe_groups_touched_sum``).
"""

from __future__ import annotations

BYTES = 2  # bf16, weights, cache and conv state


def _counts(m: dict):
    types = m["layer_types"]
    n_attention = sum(t == "full_attention" for t in types)
    n_conv = sum(t == "conv" for t in types)
    n_dense = m["num_dense_layers"]
    return n_attention, n_conv, n_dense, m["num_hidden_layers"] - n_dense


def _attention_params(m: dict) -> int:
    d, h, kv = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    hd = d // h
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def _conv_params(m: dict) -> int:
    d = m["hidden_size"]
    return 3 * d * d + d * d + d * m["conv_L_cache"]


def _dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def experts_touched(m: dict, tokens: float) -> float:
    """Expected distinct experts of one layer that ``tokens`` tokens
    choose, each taking k of E uniformly."""
    e, k = m["num_experts"], m["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def call_needs(m: dict, new_tokens: float, kv_tokens: float,
               attended: float, rows: float, experts: float) -> dict:
    """One forward call that computes ``new_tokens`` positions of
    ``rows`` rows, reads ``experts`` experts of each expert layer, reads
    and writes ``kv_tokens`` positions of keys and values in each
    attention layer, and scores ``attended`` (query, key) pairs in
    each.  Logits are counted for every new position; a prefill chunk
    emits one row, which overstates its operations by the head's share."""
    d, v = m["hidden_size"], m["vocab_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // h
    e, k = m["num_experts"], m["num_experts_per_tok"]
    n_attention, n_conv, n_dense, n_moe = _counts(m)
    outside = (n_attention * _attention_params(m) + n_conv * _conv_params(m)
               + n_dense * _dense_params(m) + n_moe * (d * e + e) + v * d)
    expert_bytes = n_moe * experts * expert_params(m) * BYTES
    weight_bytes = outside * BYTES + expert_bytes
    kv_bytes = n_attention * kv_tokens * 2 * kv * hd * BYTES
    # a row's last conv_L_cache - 1 gated inputs, read and written
    state_bytes = n_conv * rows * (m["conv_L_cache"] - 1) * d * BYTES * 2
    # each layer reads and writes the residual stream of its tokens
    act_bytes = m["num_hidden_layers"] * new_tokens * d * BYTES * 2
    flops = 2 * new_tokens * (
        n_attention * _attention_params(m) + n_conv * _conv_params(m)
        + n_dense * _dense_params(m)
        + n_moe * (d * e + k * expert_params(m)) + v * d
    ) + n_attention * 4 * attended * h * hd
    return {
        "bytes": weight_bytes + kv_bytes + state_bytes + act_bytes,
        "flops": flops, "weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
        "expert_bytes": expert_bytes,
    }


def decode_tick(m: dict, live_rows: float, live_tokens: float) -> dict:
    """Every live row adds one token, attends to its own context and
    reads the experts it chooses."""
    return call_needs(
        m, live_rows, live_tokens + live_rows, live_tokens, live_rows,
        experts_touched(m, live_rows),
    )


def prefill_chunk(m: dict, chunk_tokens: float, context_tokens: float) -> dict:
    """One request's chunk behind ``context_tokens`` cached positions."""
    return call_needs(
        m, chunk_tokens, context_tokens + chunk_tokens,
        chunk_tokens * (context_tokens + chunk_tokens / 2.0), 1.0,
        experts_touched(m, chunk_tokens),
    )


def moe_grouped_matmul(m: dict, assignments: float,
                       experts_touched: float) -> dict:
    """ONE expert layer's three grouped products over ``assignments``
    sorted rows in ``experts_touched`` groups: each touched expert's
    three matrices once, every row in and out of each product,
    2 x 3 x hidden x moe_intermediate FLOPs an assignment."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return {
        "bytes": (experts_touched * expert_params(m)
                  + assignments * (3 * d + 3 * f)) * BYTES,
        "flops": 2 * assignments * expert_params(m),
    }
