"""The plain reference of an LFM2 mixture decoder (``model_type:
lfm2_moe``): the full forward pass over a whole sequence in
``jax.numpy``, float32, matmuls at ``highest`` precision.  A loop over
layers, a loop over experts; no cache, no conv state, no pages, no
chunked prompt, no kernel, and nothing imported from the program.  Two
copies of this file are kept byte for byte:
``dcos_commons_tpu/models/reference/lfm2_moe.py`` (the tests' side) and
``perfbench/families/lfm2_moe/reference.py`` (the benchmark's);
``tests/bench/test_bench_lfm2_family.py`` holds them equal.

The equations (Hugging Face ``Lfm2MoeModel``; sizes from
huggingface.co/LiquidAI/LFM2-24B-A2B config.json).  ``x`` is a row's
residual stream; every norm is an RMSNorm with ``norm_eps`` and a plain
weight; no bias anywhere:

* layer ``l``: ``x += operator_l(norm_op(x))``, then ``x +=
  ffn_l(norm_ffn(x))``; after the last layer one more RMSNorm (the
  model's ``embedding_norm``), then the head.
* ``layer_types[l] == "conv"``, ``L = conv_L_cache``: ``[B, C, X] =
  split3(h W_in)``, ``u_t = B_t * X_t``, ``v_t = sum_j w[:, j] *
  u_{t - (L-1) + j}`` (depthwise, causal, ``u`` zero before the row's
  start; ``w [hidden, L]``), ``y_t = (C_t * v_t) W_out``.
* ``"full_attention"``: ``q, k, v = h Wq, h Wk, h Wv``; ``q`` and ``k``
  each RMS-normed over ``head_dim`` with a learned weight
  (``q_layernorm``, ``k_layernorm``), then RoPE (``rope_theta``,
  default type, the two halves of a head); grouped-query causal softmax
  attention over the whole history; ``Wo``.
* FFN of a layer below ``num_dense_layers``: ``W2(silu(W1 h) * W3 h)``
  at ``intermediate_size``.  Of any other layer: ``s = sigmoid(h Wg)``
  (router float32); chosen = the ``num_experts_per_tok`` largest of
  ``s + expert_bias``; weights ``s[chosen] / (sum + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; each expert a
  SwiGLU of ``moe_intermediate_size``; no shared expert.

Departures from the published model, each said here and under
``assumed`` in the benchmark's configuration file: the head is the
embedding (``tie_word_embeddings``: the catalog's config does not give
it); ``expert_bias`` is a float32 buffer drawn from the seed at scale
0.01 (the checkpoint's is trained); ``head_dim`` is ``hidden_size /
num_attention_heads``.  None changes a shape, a byte or a FLOP.

The weights are the program's checkpoint tree: ``embed``,
``final_norm`` and one stack a kind of layer part under ``layers``
(``attention``, ``conv``, ``dense``, ``moe``), a layer reading the
index of its part's stack that the layers before it leave.

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
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "conv_in",
    "conv_out",
)


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
    """The four layer parts, each ``(stack, i, x) -> ...`` jitted with
    ``i`` (the part's index in its stack) traced: one compile a part."""
    import jax
    import jax.numpy as jnp

    model = dict(model_items)
    eps = model["norm_eps"]

    def leaves(stack, i):
        def w(name, *index):
            # one leaf of one layer, widened where it is used: the
            # float32 copy of a whole layer never exists at once
            leaf = stack[name][(i,) + index].astype(jnp.float32)
            if lower == "int8" and name in MATMUL_LEAVES:
                leaf = _to_int8_and_back(leaf)
            return leaf
        return w

    def conv(stack, i, x):
        w = leaves(stack, i)
        taps = model["conv_L_cache"]
        s = x.shape[0]
        b_gate, c_gate, x_gate = jnp.split(
            _rms(x, w("conv_norm"), eps) @ w("conv_in"), 3, axis=-1
        )
        u = jnp.pad(b_gate * x_gate, ((taps - 1, 0), (0, 0)))
        kernel = w("conv_w")                               # [hidden, taps]
        v = sum(kernel[:, j] * u[j:j + s] for j in range(taps))
        return x + (c_gate * v) @ w("conv_out")

    def attention(stack, i, x):
        w = leaves(stack, i)
        s = x.shape[0]
        h, kv = model["num_attention_heads"], model["num_key_value_heads"]
        hd = model["hidden_size"] // h
        n = _rms(x, w("attn_norm"), eps)
        q = _rms((n @ w("wq")).reshape(s, h, hd), w("q_norm"), eps)
        k = _rms((n @ w("wk")).reshape(s, kv, hd), w("k_norm"), eps)
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
        v = (n @ w("wv")).reshape(s, kv, hd)
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        per = h // kv
        outs = []
        for g in range(kv):  # query heads g*per .. share kv head g
            qg = q[:, g * per:(g + 1) * per]
            score = jnp.einsum("qhd,kd->hqk", qg, k[:, g]) * hd ** -0.5
            prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), -1)
            outs.append(jnp.einsum("hqk,kd->qhd", prob, v[:, g]))
        return x + jnp.concatenate(outs, 1).reshape(s, h * hd) @ w("wo")

    def dense(stack, i, x):
        w = leaves(stack, i)
        n = _rms(x, w("mlp_norm"), eps)
        return x + _swiglu(n, w("w_gate"), w("w_up"), w("w_down"))

    def mixture(stack, i, x):
        w = leaves(stack, i)
        k = model["num_experts_per_tok"]
        n = _rms(x, w("mlp_norm"), eps)
        score = jax.nn.sigmoid(n @ w("router"))
        select = score + (
            w("expert_bias") if model.get("use_expert_bias") else 0.0
        )
        top, chosen = jax.lax.top_k(select, k + 1)
        # by how much the last expert chosen leads the first one left
        # out: where this is small, rounding upstream changes the choice
        margin = top[:, k - 1] - top[:, k]
        chosen = chosen[:, :k]
        weight = jnp.take_along_axis(score, chosen, -1)
        if model.get("norm_topk_prob"):
            weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
        weight = weight * model.get("routed_scaling_factor", 1.0)
        out = jnp.zeros_like(x)
        for e in range(model["num_experts"]):
            share = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
            out = out + share[:, None] * _swiglu(
                n, w("w_gate", e), w("w_up", e), w("w_down", e)
            )
        return x + out, margin

    return {
        "conv": jax.jit(conv), "full_attention": jax.jit(attention),
        "dense": jax.jit(dense), "moe": jax.jit(mixture),
    }


def _scalars(model: dict):
    items = {
        k: v for k, v in model.items() if isinstance(v, (int, float, bool))
    }
    items["rope_theta"] = float(model["rope_parameters"]["rope_theta"])
    return tuple(sorted(items.items()))


def logits(model: dict, weights: dict, tokens, rows=None, lower=None,
           margins=False):
    """tokens [s] -> float32 logits [len(rows), vocab] at positions
    ``rows`` (all when None).  ``weights`` is the program's checkpoint
    tree in any float dtype.  With ``margins``, also each position's
    narrowest routing margin over the expert layers."""
    import jax
    import jax.numpy as jnp

    if model.get("model_type") != "lfm2_moe":
        raise ValueError("this reference computes model_type lfm2_moe alone")
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        parts = _part_fns(_scalars(model), lower)
        narrowest = jnp.full(x.shape[:1], jnp.inf)
        seen = {}
        for l, operator in enumerate(model["layer_types"]):
            ffn = "dense" if l < model["num_dense_layers"] else "moe"
            stack = "attention" if operator == "full_attention" else operator
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
                 model["norm_eps"])
        out = x @ weights["embed"].astype(jnp.float32).T
        return (out, narrowest) if margins else out
