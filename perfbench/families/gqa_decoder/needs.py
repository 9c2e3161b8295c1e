"""What one decode tick and one prefill chunk of this family NEED:
operations and bytes from the configuration's shapes and the call's live
rows and tokens, not from what the program happens to read.  Weights stream
once a call, the live tokens' keys and values once a layer, the rows'
activations once a layer; a mixture reads the experts its tokens are
expected to choose under even routing, and computes the chosen ones.
"""

from __future__ import annotations

BYTES = 2  # bf16, weights and cache


def _attention_params(m: dict) -> int:
    d, h, kv, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def _ffn_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def experts_touched(m: dict, tokens: float) -> float:
    """Expected distinct experts that `tokens` tokens choose, each
    taking k of E uniformly."""
    e = m.get("num_local_experts", 0)
    if not e:
        return 1.0
    k = m["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def call_needs(m: dict, new_tokens: float, kv_tokens: float,
               attended: float) -> dict:
    """One forward call that computes `new_tokens` positions, reads
    and writes `kv_tokens` positions of keys and values in each layer,
    and scores `attended` (query, key) pairs in each layer.  Logits
    are counted for every new position; a prefill chunk emits one row,
    which overstates its operations by the head's share."""
    n, d, v = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    kv, hd, h = m["num_key_value_heads"], m["head_dim"], m["num_attention_heads"]
    e = m.get("num_local_experts", 0)
    k = m["num_experts_per_tok"] if e else 1
    router = d * e
    layer_read = (_attention_params(m) + router
                  + experts_touched(m, new_tokens) * _ffn_params(m))
    weight_bytes = (n * layer_read + v * d) * BYTES
    kv_bytes = n * kv_tokens * 2 * kv * hd * BYTES
    # each layer reads and writes the residual stream of its tokens
    act_bytes = n * new_tokens * d * BYTES * 2
    layer_flops = 2 * new_tokens * (
        _attention_params(m) + router + k * _ffn_params(m)
    ) + 4 * attended * h * hd
    flops = n * layer_flops + 2 * new_tokens * v * d
    return {
        "bytes": weight_bytes + kv_bytes + act_bytes, "flops": flops,
        "weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
    }


def decode_tick(m: dict, live_rows: float, live_tokens: float) -> dict:
    """Every live row adds one token and attends to its own context."""
    return call_needs(m, live_rows, live_tokens + live_rows, live_tokens)


def prefill_chunk(m: dict, chunk_tokens: float, context_tokens: float) -> dict:
    """One request's chunk behind `context_tokens` cached positions."""
    return call_needs(
        m, chunk_tokens, context_tokens + chunk_tokens,
        chunk_tokens * (context_tokens + chunk_tokens / 2.0),
    )
