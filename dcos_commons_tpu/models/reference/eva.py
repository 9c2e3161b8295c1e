"""The plain reference of an EVA decoder (EvaByte): the full forward
pass over a whole sequence in ``jax.numpy``, float32, matmuls at
``highest`` precision.  No cache, no pages, no chunked prompt, and
nothing imported from the program.  Two copies of this file are kept
byte for byte: ``dcos_commons_tpu/models/reference/eva.py`` (the tests'
side) and ``perfbench/families/eva_decoder/reference.py`` (the
benchmark's); ``tests/bench/test_bench_eva_family.py`` holds them equal.

The equations (EVA: Zheng, Yuan, Wang, Kong, "Efficient Attention via
Control Variates", ICLR 2023, in the deterministic form EvaByte serves;
sizes from huggingface.co/EvaByte/EvaByte config.json).  Per head, with
``s = head_dim ** -0.5`` and q, k after RoPE:

* chunk ``c`` is positions ``C*c .. C*c + C-1`` (``C = chunk_size``).
  Its summary: ``w_j = softmax_j(s * phi . k_j)`` over the chunk,
  ``k~_c = sum_j w_j k_j + mu``, ``v~_c = sum_j w_j v_j``; ``phi`` and
  ``mu`` are learned vectors of ``head_dim`` a head a layer
  (``eva_phi``, ``eva_mu``).
* window of position ``i``: ``W(i) = i // window_size``.  Query ``i``
  scores the exact keys ``j <= i`` with ``W(j) = W(i)`` as
  ``s * q_i . k_j`` and the summary of every chunk that lies in a
  window ``< W(i)`` as ``s * q_i . k~_c``; ONE softmax over both sets;
  output ``sum_j p_j v_j + sum_c p_c v~_c``, then ``wo``.
* block: ``h = x + Attn(norm(x))``, ``x = h + W_down(silu(W_gate n) *
  W_up n)`` with ``n = norm(h)``; ``norm(x) = x / rms(x) * (1 + g)``
  (``norm_add_unit_offset``; without it, ``* g``); logits from the
  first ``vocab_size`` columns of the ``[hidden, num_pred_heads *
  vocab_size]`` output matrix (the other heads speculate further bytes
  and plain decoding does not read them).

Departures from the checkpoint's own code, each said here: the pooling
logit is ``s * phi . k`` with no ``-|k|^2 / 2`` term, head 0 is taken
as the next-byte head, ``phi`` and ``mu`` are drawn at the
checkpoint's ``init_std`` (all three under ``assumed`` in the
benchmark's configuration file; none changes a shape, a byte or a
FLOP).  ``fp32_logits`` is kept: the logits are float32 here and in
the program.  ``fp32_skip_add`` and ``mixedp_attn`` mean nothing in a
float32 reference; the PROGRAM adds its residuals in the dtype it
serves in (bfloat16 on the chip) where the checkpoint's code adds in
float32, and widens q, k, v to float32 before the scores and the
softmax where the checkpoint's code multiplies in bfloat16.

The sequence is processed a window at a time, layer by layer (queries
of one window against that window's keys and every summary, the
feed-forward over one window's rows), so that 32 thousand positions at
the published widths fit beside the bfloat16 weights they widen.

``lower="int8"`` is the control, never run by the benchmark itself: the
same forward pass with every layer's matmul weights rounded to int8
(symmetric, one scale an output channel), the nearest precision below
the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rms(x, g, eps, unit_offset):
    import jax
    import jax.numpy as jnp

    scale = 1.0 + g if unit_offset else g
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, first, theta):
    """x [n, heads, hd] at positions ``first .. first + n - 1``;
    position i rotates pair (j, j + hd/2) by ``i * theta^(-2j/hd)``."""
    import jax.numpy as jnp

    n, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    at = (first + jnp.arange(n)).astype(jnp.float32)
    angle = at[:, None, None] * freq
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


def _sizes(model: dict):
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["hidden_size"] // h
    return h, kv, hd, model["window_size"], model["chunk_size"]


@functools.lru_cache(maxsize=None)
def _layer_fns(model_items, lower=None):
    """(project, attend_and_feed) of one layer, jitted once a model.

    ``project(layers, i, x)``: the whole sequence's roped keys, values
    and chunk summaries of layer ``i``.  ``attend_and_feed(layers, i, x,
    k, v, k_sum, v_sum, w)``: window ``w``'s rows through attention and
    the feed-forward."""
    import jax
    import jax.numpy as jnp

    model = dict(model_items)
    h, kv, hd, win, chunk = _sizes(model)
    eps, offset = model["rms_norm_eps"], bool(model.get("norm_add_unit_offset"))
    theta, scale = model["rope_theta"], hd ** -0.5

    def leaf(layers, i, name):
        # one leaf of layer i, widened where it is used: the float32
        # copy of a whole layer never exists at once
        w = layers[name][i].astype(jnp.float32)
        if lower == "int8" and name in MATMUL_LEAVES:
            w = _to_int8_and_back(w)
        return w

    def project(layers, i, x):
        s = x.shape[0]
        n = _rms(x, leaf(layers, i, "attn_norm"), eps, offset)
        k = _rope((n @ leaf(layers, i, "wk")).reshape(s, kv, hd), 0, theta)
        v = (n @ leaf(layers, i, "wv")).reshape(s, kv, hd)
        kc = k.reshape(s // chunk, chunk, kv, hd)
        vc = v.reshape(s // chunk, chunk, kv, hd)
        pool = jax.nn.softmax(
            jnp.einsum("nckd,kd->nck", kc, leaf(layers, i, "eva_phi")) * scale,
            axis=1,
        )
        k_sum = jnp.einsum("nck,nckd->nkd", pool, kc) + leaf(layers, i, "eva_mu")
        v_sum = jnp.einsum("nck,nckd->nkd", pool, vc)
        return k, v, k_sum, v_sum

    def attend_and_feed(layers, i, x, k, v, k_sum, v_sum, w):
        first = w * win
        xw = jax.lax.dynamic_slice_in_dim(x, first, win)
        kw = jax.lax.dynamic_slice_in_dim(k, first, win)
        vw = jax.lax.dynamic_slice_in_dim(v, first, win)
        n = _rms(xw, leaf(layers, i, "attn_norm"), eps, offset)
        q = _rope((n @ leaf(layers, i, "wq")).reshape(win, h, hd), first, theta)
        causal = jnp.arange(win)[:, None] >= jnp.arange(win)[None, :]
        # a chunk is seen as a summary only from a LATER window
        past = (jnp.arange(k_sum.shape[0]) < w * (win // chunk))[None, :]
        per = h // kv
        outs = []
        for g in range(kv):  # query heads g*per .. share kv head g
            qg = q[:, g * per:(g + 1) * per]
            exact = jnp.einsum("qhd,kd->hqk", qg, kw[:, g]) * scale
            summary = jnp.einsum("qhd,cd->hqc", qg, k_sum[:, g]) * scale
            prob = jax.nn.softmax(jnp.concatenate([
                jnp.where(causal, exact, -jnp.inf),
                jnp.where(past, summary, -jnp.inf),
            ], -1), -1)
            outs.append(
                jnp.einsum("hqk,kd->qhd", prob[..., :win], vw[:, g])
                + jnp.einsum("hqc,cd->qhd", prob[..., win:], v_sum[:, g])
            )
        attn = jnp.concatenate(outs, 1).reshape(win, h * hd)
        hidden = xw + attn @ leaf(layers, i, "wo")
        n = _rms(hidden, leaf(layers, i, "mlp_norm"), eps, offset)
        fed = (jax.nn.silu(n @ leaf(layers, i, "w_gate"))
               * (n @ leaf(layers, i, "w_up"))) @ leaf(layers, i, "w_down")
        return hidden + fed

    return jax.jit(project), jax.jit(attend_and_feed)


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
    steadiness margin: nothing is routed, so it is infinite."""
    import jax
    import jax.numpy as jnp

    if model.get("attention_class") != "eva":
        raise ValueError("this reference computes EVA attention alone")
    win = model["window_size"]
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    windows = -(-s // win)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        # whole windows: the padding lies behind every real position
        x = jnp.pad(x, ((0, windows * win - s), (0, 0)))
        project, attend_and_feed = _layer_fns(_scalars(model), lower)
        for i in range(model["num_hidden_layers"]):
            i = jnp.int32(i)
            k, v, k_sum, v_sum = project(weights["layers"], i, x)
            x = jnp.concatenate([
                attend_and_feed(
                    weights["layers"], i, x, k, v, k_sum, v_sum, jnp.int32(w)
                )
                for w in range(windows)
            ])
        x = x[:s] if rows is None else x[jnp.asarray(rows)]
        x = _rms(x, weights["final_norm"].astype(jnp.float32),
                 model["rms_norm_eps"],
                 bool(model.get("norm_add_unit_offset")))
        if model.get("tie_word_embeddings"):
            out = x @ weights["embed"].astype(jnp.float32).T
        else:
            head = weights["lm_head"][:, :model["vocab_size"]]
            out = x @ head.astype(jnp.float32)
        steady = jnp.full(out.shape[:1], jnp.inf)
        return (out, steady) if margins else out
