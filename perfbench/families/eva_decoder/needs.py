"""What one decode tick and one prefill chunk of an EVA decoder NEED:
operations and bytes from the configuration's shapes and the call's live
rows and cache ENTRIES, not from what the program happens to read.
Weights stream once a call (of the output head, the next-byte head's
columns alone: the others are not read by plain decoding), every live
entry's key and value once a layer, the rows' activations once a layer.

An entry is one position's exact key and value, or one chunk's summary:
the same bytes (2 x kv heads x head_dim x 2).  A query at position
``p`` reads ``entries_seen(p)``: its own window's positions before it
and one summary a chunk of every window that is past.  Nothing is
routed.
"""

from __future__ import annotations

BYTES = 2  # bf16, weights and cache


def _sizes(m: dict):
    d, h, kv = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    return d, h, kv, d // h


def _layer_params(m: dict) -> int:
    d, h, kv, hd = _sizes(m)
    attention = d * h * hd + 2 * d * kv * hd + h * hd * d
    return attention + 3 * d * m["intermediate_size"] + 2 * kv * hd


def entry_bytes(m: dict) -> int:
    """One cache entry of one layer."""
    _d, _h, kv, hd = _sizes(m)
    return 2 * kv * hd * BYTES


def entries_seen(m: dict, position: float) -> float:
    """Entries a query at ``position`` reads beside itself."""
    window, chunk = m["window_size"], m["chunk_size"]
    return position % window + (position // window) * (window // chunk)


def call_needs(m: dict, new_tokens: float, read_entries: float,
               written_entries: float, attended: float) -> dict:
    """One forward call that computes ``new_tokens`` positions, reads
    ``read_entries`` and writes ``written_entries`` cache entries in
    each layer, and scores ``attended`` (query, entry) pairs in each
    layer.  Logits are counted for every new position; a prefill chunk
    emits one row, which overstates its operations by the head's
    share."""
    n, v = m["num_hidden_layers"], m["vocab_size"]
    d, h, _kv, hd = _sizes(m)
    weight_bytes = (n * _layer_params(m) + d * v + new_tokens * d) * BYTES
    kv_bytes = n * (read_entries + written_entries) * entry_bytes(m)
    # each layer reads and writes the residual stream of its tokens
    act_bytes = n * new_tokens * d * BYTES * 2
    # pooling a chunk: phi . k, then the weighted sums of k and of v
    layer_flops = (2 * new_tokens * _layer_params(m)
                   + 4 * attended * h * hd + 6 * new_tokens * h * hd)
    return {
        "bytes": weight_bytes + kv_bytes + act_bytes,
        "flops": n * layer_flops + 2 * new_tokens * v * d,
        "weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
    }


def decode_tick(m: dict, live_rows: float, live_entries: float) -> dict:
    """Every live row adds one position and attends to its entries
    (``kv_live_tokens`` of ``/stats`` counts entries)."""
    return call_needs(m, live_rows, live_entries, live_rows, live_entries)


def prefill_chunk(m: dict, chunk_tokens: float, context_tokens: float) -> dict:
    """One request's chunk behind ``context_tokens`` positions: it reads
    what a query at ``context_tokens`` sees, writes its own positions
    and one summary a chunk of them."""
    seen = entries_seen(m, context_tokens)
    return call_needs(
        m, chunk_tokens, seen,
        chunk_tokens + chunk_tokens / m["chunk_size"],
        chunk_tokens * (seen + chunk_tokens / 2.0),
    )


def eva_decode_attention(m: dict, live_rows: float, live_entries: float) -> dict:
    """ONE layer's call of the decode kernel (the ``tpu_custom_call``
    named ``eva_decode_attention``, dcos_commons_tpu/ops/eva_decode.py):
    every live entry's key and value once, and the entry each row has
    just written; a product and a weighted sum an entry a head."""
    _d, h, _kv, hd = _sizes(m)
    read = live_entries + live_rows
    return {
        "bytes": read * entry_bytes(m) + 2 * live_rows * h * hd * BYTES,
        "flops": 4 * read * h * hd,
    }
