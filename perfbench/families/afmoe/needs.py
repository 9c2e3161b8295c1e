"""What one decode tick and one prefill chunk of an AFMoE decoder NEED:
operations and bytes from the configuration's shapes and the call's live
rows and tokens, not from what the program happens to read.  The weights
outside the routed experts stream once a call (the attention's five
matrices a layer, the dense layers' FFN, the routers, the one shared
expert of each expert layer, the untied head); keys and values once a
layer, by the layer's KIND: a full layer reads every position of the
live rows, a window layer at most ``sliding_window`` a row
(``window_reads``: the caller hands the live rows and their summed
context, and a row's share of it is taken as the mean, so the window
layers' reads are ``min(mean context, sliding_window)`` a row; exact
where every row is past its first window, as in a cell whose shortest
prompt is two windows long); the rows' activations once a layer.

Of each expert layer a call reads the routed experts its tokens are
expected to choose under even routing (``experts_touched``: 35 of 128
for a decode tick of 5 rows choosing 8, 128 for a chunk of 512): the
bytes of a tick move with its rows.  ``decode_tick`` can therefore state
a step from the rows, the positions and the groups touched, but is only
as good as the rows it is handed: ``decode_step_roofline.chat`` hands it
a gauge's mean over the minute in which the profile is written
(lfm2_moe's ``needs.py`` says what that did there), so
``trinity-mini.longdoc`` is NOT among that metric's cells;
``moe_experts_touched_per_layer.chat`` reads the calls' own counts.

``moe_grouped_matmul`` is ONE expert layer's three grouped products
(the ``tpu_custom_call`` named ``gmm``), for the assignments and the
expert groups a call really had; the shared expert is a plain product
outside it.  ``window_decode_attention`` is ONE window layer's decode
attention (the ``tpu_custom_call`` named
``paged_decode_attention_window``, dcos_commons_tpu/ops/paged_decode.py)
for the rows of a step and the ring entries they read between them.
"""

from __future__ import annotations

BYTES = 2  # bf16, weights and both caches


def _counts(m: dict):
    types = m["layer_types"]
    n_full = sum(t == "full_attention" for t in types)
    n_window = sum(t == "sliding_attention" for t in types)
    n_dense = m["num_dense_layers"]
    return n_full, n_window, n_dense, m["num_hidden_layers"] - n_dense


def _attention_params(m: dict) -> int:
    """Wq, Wg and Wo at heads x head_dim, Wk and Wv at KV heads."""
    d, h, kv, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return 3 * d * h * hd + 2 * d * kv * hd


def _dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def experts_touched(m: dict, tokens: float) -> float:
    """Expected distinct routed experts of one layer that ``tokens``
    tokens choose, each taking k of E uniformly."""
    e, k = m["num_experts"], m["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def window_reads(m: dict, rows: float, context_tokens: float) -> float:
    """Ring entries ``rows`` rows of ``context_tokens`` positions between
    them read in ONE window layer."""
    if not rows:
        return 0.0
    return rows * min(context_tokens / rows, m["sliding_window"])


def call_needs(m: dict, new_tokens: float, full_tokens: float,
               window_tokens: float, attended_full: float,
               attended_window: float, experts: float) -> dict:
    """One forward call that computes ``new_tokens`` positions, reads
    ``experts`` routed experts of each expert layer, reads and writes
    ``full_tokens`` positions of keys and values in each full layer and
    ``window_tokens`` in each window layer, and scores
    ``attended_full`` / ``attended_window`` (query, key) pairs in each.
    Logits are counted for every new position; a prefill chunk emits one
    row, which overstates its operations by the head's share."""
    d, v = m["hidden_size"], m["vocab_size"]
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    e, k = m["num_experts"], m["num_experts_per_tok"]
    shared = m["num_shared_experts"]
    n_full, n_window, n_dense, n_moe = _counts(m)
    n_attention = n_full + n_window
    # embedding rows are gathered, the head is read whole
    outside = (n_attention * _attention_params(m)
               + n_dense * _dense_params(m)
               + n_moe * (d * e + e + shared * expert_params(m)) + v * d)
    expert_bytes = n_moe * experts * expert_params(m) * BYTES
    weight_bytes = outside * BYTES + expert_bytes
    kv_bytes = (n_full * full_tokens + n_window * window_tokens) * (
        2 * kv * hd * BYTES
    )
    # each layer reads and writes the residual stream of its tokens
    act_bytes = m["num_hidden_layers"] * new_tokens * d * BYTES * 2
    flops = 2 * new_tokens * (
        n_attention * _attention_params(m) + n_dense * _dense_params(m)
        + n_moe * (d * e + (k + shared) * expert_params(m)) + v * d
    ) + 4 * h * hd * (n_full * attended_full + n_window * attended_window)
    return {
        "bytes": weight_bytes + kv_bytes + act_bytes, "flops": flops,
        "weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
        "expert_bytes": expert_bytes,
    }


def decode_tick(m: dict, live_rows: float, live_tokens: float) -> dict:
    """Every live row adds one token, attends to its whole context in a
    full layer and to its last window in a window layer, and reads the
    experts it chooses."""
    window = window_reads(m, live_rows, live_tokens)
    return call_needs(
        m, live_rows, live_tokens + live_rows, window + live_rows,
        live_tokens, window, experts_touched(m, live_rows),
    )


def prefill_chunk(m: dict, chunk_tokens: float, context_tokens: float) -> dict:
    """One request's chunk behind ``context_tokens`` cached positions:
    a full layer's queries see all of them and the chunk's causal half,
    a window layer's at most ``sliding_window`` each."""
    seen = min(context_tokens + chunk_tokens / 2.0, m["sliding_window"])
    return call_needs(
        m, chunk_tokens, context_tokens + chunk_tokens,
        min(context_tokens, m["sliding_window"]) + chunk_tokens,
        chunk_tokens * (context_tokens + chunk_tokens / 2.0),
        chunk_tokens * seen, experts_touched(m, chunk_tokens),
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


def window_decode_attention(m: dict, rows: float, entries: float) -> dict:
    """ONE window layer's decode attention over ``rows`` rows that read
    ``entries`` ring entries between them (each at most
    ``sliding_window``): every entry's key and value once, a query and
    an output a row; a product and a weighted sum an entry a head."""
    h, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    return {
        "bytes": (entries * 2 * kv * hd + rows * 2 * h * hd) * BYTES,
        "flops": 4 * entries * h * hd,
    }
