"""The plain reference: a dense or top-2-of-E mixture decoder forward
pass in ``jax.numpy``, float32, matmuls at ``highest`` precision.  No
cache, no kernels, no batching, and nothing imported from the program.

Written from the published descriptions (Mistral 7B, arXiv:2310.06825;
Mixtral of Experts, arXiv:2401.04088): pre-norm residual blocks,
RMSNorm, rotary positions on the two halves of each head, grouped-query
causal attention, SwiGLU feed-forward; the mixture routes each token to
the ``num_experts_per_tok`` experts with the largest router logits and
weighs them by a softmax over those logits (equal to a softmax over all
experts renormalised over the chosen ones).  Departures are the
program's, listed in each configuration's ``program_fixed``: the output
head is the embedding, ``rope_theta`` and ``rms_norm_eps`` are the
values the program hard-codes.

Layers run one at a time and attention one group of heads at a time,
so the reference fits beside the bf16 weights it widens.

``lower="int8"`` is the control, never run by the benchmark itself: the
same forward pass with every layer's matmul weights rounded to int8
(symmetric, one scale an output channel), the nearest precision below
the bf16 the configurations state.  `tools/control.py` reads what it
puts first at each position.
"""

from __future__ import annotations

import functools


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [s, heads, hd]; position i rotates pair (j, j + hd/2) by
    i * theta^(-2j/hd)."""
    import jax.numpy as jnp

    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], -1
    )


def _attention(model, w, x):
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, kv, hd = (model["num_attention_heads"],
                 model["num_key_value_heads"], model["head_dim"])
    q = _rope((x @ w("wq")).reshape(s, h, hd), model["rope_theta"])
    k = _rope((x @ w("wk")).reshape(s, kv, hd), model["rope_theta"])
    v = (x @ w("wv")).reshape(s, kv, hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    per = h // kv
    outs = []
    for g in range(kv):  # query heads g*per .. g*per+per-1 share kv head g
        qg = q[:, g * per:(g + 1) * per]
        score = jnp.einsum("qhd,kd->hqk", qg, k[:, g]) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,kd->qhd", prob, v[:, g]))
    return jnp.concatenate(outs, 1).reshape(s, h * hd) @ w("wo")


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _mixture(model, w, x):
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    top, chosen = jax.lax.top_k(x @ w("router"), k + 1)
    # by how much the last expert chosen leads the first one left out:
    # where this is small, rounding anywhere upstream changes the choice
    margin = top[:, k - 1] - top[:, k]
    top, chosen = top[:, :k], chosen[:, :k]
    weight = jax.nn.softmax(top, -1)
    out = jnp.zeros_like(x)
    for e in range(model["num_local_experts"]):
        share = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
        out = out + share[:, None] * _swiglu(
            x, w("w_gate", e), w("w_up", e), w("w_down", e)
        )
    return out, margin


MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _to_int8_and_back(w):
    """Symmetric int8 with one scale for each output channel (the
    contraction axis of ``x @ w`` is -2)."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(w), -2, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


@functools.lru_cache(maxsize=None)
def _layer_fn(model_items, lower=None):
    import jax
    import jax.numpy as jnp

    model = dict(model_items)

    def layer(layers, i, x):
        def w(name, *index):
            # one leaf of layer i, widened where it is used: the
            # float32 copy of a whole layer never exists at once
            leaf = layers[name][(i,) + index].astype(jnp.float32)
            if lower == "int8" and name in MATMUL_LEAVES:
                leaf = _to_int8_and_back(leaf)
            return leaf

        eps = model["rms_norm_eps"]
        x = x + _attention(model, w, _rms(x, w("attn_norm"), eps))
        normed = _rms(x, w("mlp_norm"), eps)
        if model.get("num_local_experts", 0):
            out, margin = _mixture(model, w, normed)
            return x + out, margin
        dense = _swiglu(normed, w("w_gate"), w("w_up"), w("w_down"))
        return x + dense, jnp.full(x.shape[:1], jnp.inf)

    return jax.jit(layer)


def _scalars(model: dict):
    return tuple(sorted(
        (k, v) for k, v in model.items() if isinstance(v, (int, float))
    ))


def logits(model: dict, weights: dict, tokens, rows=None, lower=None,
           margins=False):
    """tokens [s] -> float32 logits [len(rows), vocab] at positions
    ``rows`` (all when None).  ``weights`` may be any float dtype.
    With ``margins``, also each position's narrowest routing margin over
    the layers (infinite for a dense model)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        layer = _layer_fn(_scalars(model), lower)
        narrowest = jnp.full(x.shape[:1], jnp.inf)
        for i in range(model["num_hidden_layers"]):
            x, margin = layer(weights["layers"], jnp.int32(i), x)
            narrowest = jnp.minimum(narrowest, margin)
        if rows is not None:
            x, narrowest = x[jnp.asarray(rows)], narrowest[jnp.asarray(rows)]
        x = _rms(x, weights["final_norm"].astype(jnp.float32),
                 model["rms_norm_eps"])
        out = x @ weights["embed"].astype(jnp.float32).T
        return (out, narrowest) if margins else out
