"""The plain reference of an AFMoE decoder (``model_type: afmoe``,
Arcee's Trinity family): the full forward pass over a whole sequence in
``jax.numpy``, float32, matmuls at ``highest`` precision.  A loop over
layers, a loop over experts; no cache, no ring, no pages, no chunked
prompt, no kernel, and nothing imported from the program.  Attention
goes a block of queries at a time (every key at once), so that 32
thousand positions fit a chip beside the weights.  Two copies of this
file are kept byte for byte:
``dcos_commons_tpu/models/reference/afmoe.py`` (the tests' side) and
``perfbench/families/afmoe/reference.py`` (the benchmark's);
``tests/bench/test_bench_afmoe_family.py`` holds them equal.

The equations (Hugging Face ``AfmoeModel``; sizes from
huggingface.co/arcee-ai/Trinity-Mini config.json).  ``x`` is a row's
residual stream; ``RMS`` is an RMSNorm with ``rms_norm_eps`` and a plain
weight; no bias anywhere.  What the published ``config.json`` has no
key for is marked (+): it is the model's published modelling code as
ISSUE 44's writer knew it, and stands under ``assumed`` in the
benchmark's configuration file.

* ``x0 = embed[token] * sqrt(hidden_size)`` (``mup_enabled``) (+).
* layer ``l``: ``x += RMS_post_attn(attn_l(RMS_in(x)))``, then ``x +=
  RMS_post_mlp(ffn_l(RMS_pre_mlp(x)))``: four norms a layer (+).  After
  the last layer ``RMS_final``, then the untied head ``[hidden, vocab]``.
* ``attn_l(h)``: ``q = h Wq`` (``num_attention_heads`` x ``head_dim``,
  whatever ``hidden_size`` is), ``k = h Wk``, ``v = h Wv``
  (``num_key_value_heads`` x ``head_dim``), ``g = h Wg`` (as wide as
  ``q``) (+); ``q`` and ``k`` RMS-normed over ``head_dim`` with learned
  weights (+).  On a ``sliding_attention`` layer RoPE (``rope_theta``,
  default type, the two halves of a head) and query ``i`` sees the keys
  ``j`` with ``i - sliding_window < j <= i``; on a ``full_attention``
  layer NO position encoding (+) and every ``j <= i``.  Grouped-query
  softmax (scale ``head_dim ** -0.5``); ``out = (softmax(..) v *
  sigmoid(g)) Wo`` (+).
* ``ffn_l``, ``l < num_dense_layers``: ``W2(silu(W1 h) * W3 h)`` at
  ``intermediate_size``.
* ``ffn_l`` otherwise: ``s = sigmoid(h Wr)`` in float32; chosen = the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` (+) (one group:
  no limit by groups of experts); weights ``s[chosen] / (sum s[chosen]
  + 1e-20)`` (``route_norm``) times ``route_scale``; ``y = shared(h) +
  sum_e w_e expert_e(h)``, every expert and the one shared expert a
  SwiGLU of ``moe_intermediate_size``.

Departures from the published model, each said here and under
``assumed`` in the configuration file: ``expert_bias`` is a float32
buffer drawn from the seed at scale 0.01 (the checkpoint's starts at
zero and is moved by training); the router's weights are float32.
Neither changes a shape, a byte or a FLOP.

The weights are the program's checkpoint tree: ``embed``, ``lm_head``,
``final_norm`` and one stack a kind of layer part under ``layers``
(``attention`` for the full layers, ``sliding`` for the window layers,
``dense``, ``moe``), a layer reading the index of its part's stack that
the layers before it leave.

``margins``: by how much the last chosen expert leads the first one
left out in ``s + expert_bias``, narrowest over the expert layers.

``lower="int8"`` is the control, never run by the benchmark itself: the
same forward pass with every layer's matmul weights rounded to int8
(symmetric, one scale an output channel), the nearest precision below
the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

MATMUL_LEAVES = (
    "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down",
    "shared_gate", "shared_up", "shared_down",
)
# the queries one block of attention scores against every key
QUERY_BLOCK = 256
# the columns of the head one product takes
HEAD_BLOCK = 32768


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


def _to_int8_and_back(w):
    """Symmetric int8 with one scale for each output channel (the
    contraction axis of ``x @ w`` is -2)."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(w), -2, keepdims=True) / 127.0, 1e-12)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.lru_cache(maxsize=None)
def _part_fns(model_items, lower=None):
    """The layer parts, each ``(stack, i, x) -> ...`` jitted with ``i``
    (the part's index in its stack) traced: one compile a part."""
    import jax
    import jax.numpy as jnp

    model = dict(model_items)
    eps = model["rms_norm_eps"]

    def leaves(stack, i):
        def w(name, *index):
            # one leaf of one layer, widened where it is used: the
            # float32 copy of a whole layer never exists at once
            leaf = stack[name][(i,) + index].astype(jnp.float32)
            if lower == "int8" and name in MATMUL_LEAVES:
                leaf = _to_int8_and_back(leaf)
            return leaf
        return w

    def attention(window, stack, i, x):
        """``window`` 0: a full layer (no position encoding, the whole
        history); else a sliding layer (RoPE, the last ``window``)."""
        w = leaves(stack, i)
        s = x.shape[0]
        h, kv = model["num_attention_heads"], model["num_key_value_heads"]
        hd = model["head_dim"]
        n = _rms(x, w("attn_norm"), eps)
        q = _rms((n @ w("wq")).reshape(s, h, hd), w("q_norm"), eps)
        k = _rms((n @ w("wk")).reshape(s, kv, hd), w("k_norm"), eps)
        if window:
            q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
        v = (n @ w("wv")).reshape(s, kv, hd)
        gate = jax.nn.sigmoid(n @ w("wg"))
        per = h // kv
        block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
        keys_at = jnp.arange(s)[None, :]

        def one_block(first):
            at = first + jnp.arange(block)[:, None]
            seen = keys_at <= at
            if window:
                seen &= keys_at > at - window
            qb = jax.lax.dynamic_slice_in_dim(q, first, block)
            outs = []
            for g in range(kv):  # query heads g*per .. share kv head g
                qg = qb[:, g * per:(g + 1) * per]
                score = jnp.einsum("qhd,kd->hqk", qg, k[:, g]) * hd ** -0.5
                prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1)
                outs.append(jnp.einsum("hqk,kd->qhd", prob, v[:, g]))
            return jnp.concatenate(outs, 1).reshape(block, h * hd)

        out = jax.lax.map(
            one_block, jnp.arange(0, s, block, dtype=jnp.int32)
        ).reshape(s, h * hd)
        return x + _rms((out * gate) @ w("wo"), w("attn_post_norm"), eps)

    def dense(stack, i, x):
        w = leaves(stack, i)
        n = _rms(x, w("mlp_norm"), eps)
        y = _swiglu(n, w("w_gate"), w("w_up"), w("w_down"))
        return x + _rms(y, w("mlp_post_norm"), eps)

    def mixture(stack, i, x):
        w = leaves(stack, i)
        k = model["num_experts_per_tok"]
        n = _rms(x, w("mlp_norm"), eps)
        score = jax.nn.sigmoid(n @ w("router"))
        top, chosen = jax.lax.top_k(score + w("expert_bias"), k + 1)
        # by how much the last expert chosen leads the first one left
        # out: where this is small, rounding upstream changes the choice
        margin = top[:, k - 1] - top[:, k]
        chosen = chosen[:, :k]
        weight = jnp.take_along_axis(score, chosen, -1)
        if model.get("route_norm"):
            weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
        weight = weight * model.get("route_scale", 1.0)

        def one_expert(e, out):
            share = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
            return out + share[:, None] * _swiglu(
                n, w("w_gate", e), w("w_up", e), w("w_down", e)
            )

        out = _swiglu(n, w("shared_gate"), w("shared_up"), w("shared_down"))
        out = jax.lax.fori_loop(0, model["num_experts"], one_expert, out)
        return x + _rms(out, w("mlp_post_norm"), eps), margin

    return {
        "full_attention": jax.jit(functools.partial(attention, 0)),
        "sliding_attention": jax.jit(
            functools.partial(attention, model["sliding_window"])
        ),
        "dense": jax.jit(dense), "moe": jax.jit(mixture),
    }


def _scalars(model: dict):
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, bool))
    ))


def logits(model: dict, weights: dict, tokens, rows=None, lower=None,
           margins=False):
    """tokens [s] -> float32 logits [len(rows), vocab] at positions
    ``rows`` (all when None).  ``weights`` is the program's checkpoint
    tree in any float dtype.  With ``margins``, also each position's
    narrowest routing margin over the expert layers."""
    import jax
    import jax.numpy as jnp

    if model.get("model_type") != "afmoe":
        raise ValueError("this reference computes model_type afmoe alone")
    if model.get("num_shared_experts") != 1:
        raise ValueError("this reference computes one shared expert")
    stacks = {"full_attention": "attention", "sliding_attention": "sliding"}
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        if model.get("mup_enabled"):
            x = x * model["hidden_size"] ** 0.5
        parts = _part_fns(_scalars(model), lower)
        narrowest = jnp.full(x.shape[:1], jnp.inf)
        seen = {}
        for l, operator in enumerate(model["layer_types"]):
            ffn = "dense" if l < model["num_dense_layers"] else "moe"
            stack = stacks[operator]
            x = parts[operator](
                weights["layers"][stack], jnp.int32(seen.get(stack, 0)), x
            )
            seen[stack] = seen.get(stack, 0) + 1
            fed = parts[ffn](
                weights["layers"][ffn], jnp.int32(seen.get(ffn, 0)), x
            )
            seen[ffn] = seen.get(ffn, 0) + 1
            if ffn == "moe":
                x, margin = fed
                narrowest = jnp.minimum(narrowest, margin)
            else:
                x = fed
        if rows is not None:
            x, narrowest = x[jnp.asarray(rows)], narrowest[jnp.asarray(rows)]
        x = _rms(x, weights["final_norm"].astype(jnp.float32),
                 model["rms_norm_eps"])
        # the head a block of the vocabulary at a time: its float32
        # copy never exists whole beside the weights
        head = weights["lm_head"]
        out = jnp.concatenate([
            x @ head[:, first:first + HEAD_BLOCK].astype(jnp.float32)
            for first in range(0, head.shape[1], HEAD_BLOCK)
        ], -1)
        return (out, narrowest) if margins else out
