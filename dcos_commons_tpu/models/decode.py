"""KV-cache autoregressive inference for the flagship transformer.

The training side (transformer.py) is scan-over-layers with flash
kernels; this is its serving half, built the TPU way: STATIC shapes
throughout (the cache is allocated at ``max_len`` once; XLA never
recompiles as generation advances), ``lax.scan`` over decode steps,
``lax.dynamic_update_slice`` for in-place cache writes, and one fused
masked-softmax attention per step (seq-1 queries gain nothing from the
flash kernel's tiling — the dense einsum against the cache IS the
MXU-friendly form).

Layout: cache k/v are [n_layers, batch, max_len, n_kv_heads, head_dim]
(GQA heads stored unexpanded; expanded per step).  Greedy decoding is
exactly argmax-chaining full forwards — the equivalence tests in
tests/test_decode.py and test_workload.py hold argmax agreement.  For
MoE configs decode routes DROP-FREE (capacity covers every token of
the step); the equivalence therefore holds when the forward side is
also in its drop-free capacity regime — with training-style capacity
pressure, dropped tokens make full forwards differ from any
drop-free server by construction.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dcos_commons_tpu.models.quantize import dequantize_weight as dq
from dcos_commons_tpu.models.transformer import (
    TransformerConfig,
    _mlp,
    _mlp_block,
    _norm,
    _rope,
    head_logits,
    moe_config_of,
)
from dcos_commons_tpu.ops.rmsnorm import rms_norm

Params = Dict[str, Any]
_NEG = -1e30


def init_kv_cache(
    config: TransformerConfig, batch: int, max_len: int,
    kv_dtype: str = "native",
) -> Dict[str, jax.Array]:
    return _kv_leaves(config, kv_dtype, (
        config.n_layers, batch, max_len, config.n_kv_heads,
        config.head_dim,
    ))


def _kv_leaves(config: TransformerConfig, kv_dtype: str, shape):
    """Zeroed keys and values of ``shape`` in the serving dtype, or
    int8 with a float32 scale a vector."""
    if kv_dtype != "int8":
        return {name: jnp.zeros(shape, config.dtype) for name in ("k", "v")}
    scales = shape[:-1] + (1,)
    return {
        "k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(scales, jnp.float32),
        "v_scale": jnp.zeros(scales, jnp.float32),
    }


def whole_lanes(head_dim: int) -> int:
    """``head_dim`` rounded up to whole 128-lane rows."""
    return -(-head_dim // 128) * 128


def arena_lanes(config: TransformerConfig) -> int:
    """The minor dimension of the arena's entries: ``head_dim``, but on
    a TPU whole 128-lane rows (a head of 64 is stored in the lower half
    of a row of 128, the upper half zeros).  The device pads the minor
    dimension to 128 lanes whatever the shape says; said in the shape,
    the arena keeps its row-major layout across calls (left to itself
    the compiler lays ``[.., 8, 64]`` out pages-minor and copies the
    whole arena in and out of every call) and the page walk of
    ops/paged_decode.py can copy a page (Mosaic refuses a slice of 64
    of 128 lanes).  Zeros add nothing to a score or to an output."""
    if jax.default_backend() != "tpu":
        return config.head_dim
    return whole_lanes(config.head_dim)


def init_paged_kv_cache(
    config: TransformerConfig, n_pages: int, page_tokens: int,
    kv_dtype: str = "native", slots: int = 0, lanes: int = 0,
    window_pages: int = 0,
) -> Dict[str, jax.Array]:
    """The paged arena: K/V stored as fixed-size pages instead of
    per-request rows.  Shape [n_attention_layers, n_pages, page_tokens,
    n_kv_heads, head_dim]: only a layer whose operator is attention
    owns pages.  A request's virtual position ``p`` lives at
    ``(table[p // page_tokens], p % page_tokens)`` through its page
    table.  Page 0 is the TRASH page (serve/paging.py): padding and
    inactive-row writes land there, and table entry 0 also means
    "virtual page unallocated" — those positions are always masked.

    What an ENTRY of a page is, is the table entry's to say
    (serve/paging.py RowLayout): a position's K/V, or — in the summary
    region of an ``attention == "eva"`` row — a chunk's pooled K/V;
    both have this one shape, so one arena and one layer scan hold
    both kinds.

    Beside the pages, what a row keeps that no page holds: where the
    pattern has conv layers, ``conv_state [n_conv_layers, slots,
    conv_l_cache - 1, d_model]``, a row's last gated inputs a conv
    layer, by the row's SLOT and however long the row (zeroed inside
    the first chunk's program of whoever is admitted to the slot).

    Same dict keys as ``init_kv_cache`` (int8 adds per-vector scales),
    so ``kv_dtype`` handling and sharding rules carry over: dims are
    (layers, pages, page_tokens, kv_heads, ``lanes``) — kv heads stay
    dim 3, exactly where the gang lays the tp axis; ``lanes`` is
    ``head_dim`` unless the caller says more (``arena_lanes``: the
    pool does on a TPU).

    Where the pattern has WINDOW layers ("sliding") they own an arena
    of their own, ``k_window`` / ``v_window [n_sliding_layers,
    window_pages, ...]``: every slot's ring and a trash page
    (serve/paging.py RowLayout, PagedServeConfig.window_arena_pages),
    the first entries of a row's table; ``k`` / ``v`` are then the
    FULL layers' alone, ``n_pages`` of history each."""
    shape = (
        config.n_layers_of("attention"), n_pages, page_tokens,
        config.n_kv_heads, lanes or config.head_dim,
    )
    n_sliding = config.n_layers_of("sliding")
    if n_sliding and (kv_dtype == "int8" or window_pages < 2):
        raise ValueError(
            "window attention layers keep a ring a slot in an arena of "
            f"their own, in the serving dtype: got KV_DTYPE {kv_dtype!r}, "
            f"{window_pages} window pages (no int8 ring is built: a "
            "ring's scales would need the same second arena)"
        )
    cache = _kv_leaves(config, kv_dtype, shape)
    if n_sliding:
        ring = (n_sliding, window_pages) + shape[2:]
        cache["k_window"] = jnp.zeros(ring, config.dtype)
        cache["v_window"] = jnp.zeros(ring, config.dtype)
    if config.n_layers_of("conv"):
        if slots < 1:
            raise ValueError(
                "conv layers keep their state by slot: the arena of a "
                "pattern that has them is built for a number of slots"
            )
        cache["conv_state"] = jnp.zeros(
            (config.n_layers_of("conv"), slots, config.conv_l_cache - 1,
             config.d_model), config.dtype,
        )
    return cache


def _gqa_only(config: TransformerConfig, what: str) -> None:
    if config.attention != "gqa" or not config.one_kind:
        raise NotImplementedError(
            f"{what}: the dense cache keeps every token of a row and "
            f"scans one kind of layer; attention {config.attention!r} "
            f"in the pattern {sorted(set(config.layer_kinds))} is "
            "served by the paged arena alone"
        )


def _last_logits(config: TransformerConfig, params: Params, x: jax.Array):
    """Normed hidden states [b, d] -> float32 logits [b, vocab]."""
    if not config.tie_embeddings:
        return head_logits(config, params, x)
    return jnp.einsum(
        "bd,vd->bv", x.astype(jnp.float32),
        params["embed"].astype(jnp.float32),
    )


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-vector symmetric int8: each [head_dim] slice gets its own
    max-abs scale.  Decode is HBM-bound on streaming the cache, so
    halving its bytes roughly doubles the throughput roofline; the
    f32 scale adds 4/(head_dim) overhead (~3% at hd=128)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _kv_entries(k_new, v_new, quantized: bool):
    """New keys and values keyed like the cache that takes them: int8
    with their per-vector scales where it is quantized."""
    if not quantized:
        return {"k": k_new, "v": v_new}
    kq, ks_new = _quantize_kv(k_new)
    vq, vs_new = _quantize_kv(v_new)
    return {"k": kq, "v": vq, "k_scale": ks_new, "v_scale": vs_new}


def _masked_attention(config, q, keys, values, valid, scales=()):
    """A step's queries ``q [b, 1, h, hd]`` against each row's ``keys``
    and ``values [b, L, kv, hd]`` where ``valid [b | 1, 1, L]``: one
    masked softmax in float32, a grouped contraction against the
    UNEXPANDED heads (a jnp.repeat to full heads would multiply the
    bytes streamed a step by h/kv in an HBM-bound loop).  ``scales``:
    an int8 cache's per-vector ``[b, L, kv]``, K's folded into the
    scores and V's into the probabilities: the dequantize costs one
    multiply, never a second pass over the cache bytes."""
    b, _length, kv, hd = keys.shape
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, kv, -1, hd)
    scores = jnp.einsum("bkrd,blkd->bkrl", qg, keys.astype(jnp.float32))
    if scales:
        scores = scores * scales[0].transpose(0, 2, 1)[:, :, None, :]
    scores = jnp.where(valid[:, :, None, :], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    if scales:
        probs = probs * scales[1].transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum(
        "bkrl,blkd->bkrd", probs, values.astype(jnp.float32)
    ).astype(config.dtype)


def _project_kv(config, layer, normed, positions, rope=True):
    """normed [b, s, d] -> roped q, k, v in [b, s, heads, hd]
    (``rope`` False: a layer with no position encoding rotates
    nothing).

    Weights may be weight-only int8 (models/quantize.py); the dequant
    fuses into each projection matmul."""
    b, s, _ = normed.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    q = (normed @ dq(layer["wq"], normed.dtype)).reshape(b, s, h, hd)
    k = (normed @ dq(layer["wk"], normed.dtype)).reshape(b, s, kv, hd)
    v = (normed @ dq(layer["wv"], normed.dtype)).reshape(b, s, kv, hd)
    if config.qk_norm:
        # a head at a time, over head_dim, before the rotation: the
        # cache holds the normed, rotated keys
        q = rms_norm(q, layer["q_norm"], eps=config.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], eps=config.rms_norm_eps)
    if rope:
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
    return q, k, v


def _embed(config: TransformerConfig, params: Params, tokens: jax.Array):
    """The residual stream's start: the tokens' rows of the embedding,
    times sqrt(d_model) where the configuration scales them."""
    x = params["embed"][tokens].astype(config.dtype)
    if config.embed_scale:
        x = x * jnp.asarray(config.d_model ** 0.5, config.dtype)
    return x


def _attention_residual(config: TransformerConfig, layer, x, normed, attn):
    """``x`` plus the attention block's output: ``attn [.., h * hd]``
    (gated by ``sigmoid(normed Wg)`` where the configuration has an
    output gate) through ``wo`` (and the block's second norm, where
    norms stand on both sides)."""
    if config.attention_gate:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(
                (normed @ dq(layer["wg"], x.dtype)).astype(jnp.float32)
            )
            attn = (attn.astype(jnp.float32) * gate).astype(x.dtype)
    out = attn @ dq(layer["wo"], x.dtype)
    if config.sandwich_norm:
        out = _norm(config, out, layer["attn_post_norm"])
    return x + out


def prefill(
    config: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    max_len: int,
    true_len: Optional[jax.Array] = None,
    kv_dtype: str = "native",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run the prompt through the trunk, capturing per-layer K/V.

    tokens [b, s] (s <= max_len) -> (logits of the LAST REAL position
    [b, vocab] in f32, cache filled for positions [0, s)).

    ``true_len`` (TRACED, <= s; a scalar for a shared length or a
    [b] vector for PER-ROW lengths) supports RIGHT-padded prompts
    with one compile for every length: causal attention means
    positions < true_len never see the padding, the logits are read
    at true_len - 1 per row, and decode overwrites/masks the pad
    slots — so a server can pad MIXED-length requests to a static
    width without changing any real token's computation.
    """
    b, s = tokens.shape
    if s > max_len:
        raise ValueError(f"prompt {s} exceeds cache max_len {max_len}")
    _gqa_only(config, "prefill")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["embed"][tokens].astype(config.dtype)
    h, kv = config.n_heads, config.n_kv_heads

    def layer_fn(x, layer):
        from dcos_commons_tpu.ops.attention import flash_attention

        normed = _norm(config, x, layer["attn_norm"])
        q, k, v = _project_kv(config, layer, normed, positions)
        k_full, v_full = k, v
        if kv != h:
            reps = h // kv
            k_full = jnp.repeat(k, reps, axis=2)
            v_full = jnp.repeat(v, reps, axis=2)
        attn = flash_attention(
            *(t.transpose(0, 2, 1, 3) for t in (q, k_full, v_full)),
            causal=True,
            block_q=config.attn_block_q, block_k=config.attn_block_k,
        )
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = x + attn @ dq(layer["wo"], x.dtype)
        # drop-free MoE routing: serving must not drop prompt tokens
        # (capacity pressure is a training behavior), and the decode
        # steps that continue this cache are drop-free too
        x, _counts = _serve_ffn(config, layer, x)
        # pad the captured K/V out to the static cache length (scales
        # share the pad spec: same axes, trailing dim 1)
        pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]
        return x, {
            name: jnp.pad(new, pad)
            for name, new in _kv_entries(k, v, kv_dtype == "int8").items()
        }

    x, cache = lax.scan(layer_fn, x, params["layers"])
    x = _norm(config, x, params["final_norm"])
    last = (
        jnp.asarray(true_len, jnp.int32) - 1 if true_len is not None
        else jnp.int32(s - 1)
    )
    if last.ndim == 0:
        x_last = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    else:
        # per-row last REAL position (mixed-length right-padded batch)
        x_last = jnp.take_along_axis(
            x, last[:, None, None], axis=1
        )[:, 0]
    return _last_logits(config, params, x_last), cache


def sample_token(
    logits: jax.Array, temperature: jax.Array, key: jax.Array
) -> jax.Array:
    """Greedy when ``temperature`` == 0, else softmax sampling — both
    operands TRACED so one compile covers every request.  Works on a
    single row [vocab] or a batch [b, vocab] (one shared key)."""
    temp = jnp.asarray(temperature, jnp.float32)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temp, 1e-6), axis=-1
    )
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temp > 0.0, sampled, greedy).astype(jnp.int32)


def decode_step(
    config: TransformerConfig,
    params: Params,
    cache: Dict[str, jax.Array],
    token: jax.Array,
    pos: jax.Array,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One autoregressive step: token [b] at position ``pos`` (int32
    scalar shared by the batch, or a [b] vector for per-row positions
    in a mixed-length batch) -> (logits [b, vocab] f32, updated
    cache).

    The scalar path writes the cache with ONE dynamic_update_slice
    (the HBM-cheapest form); the per-row path scatters b slots via
    ``.at[arange(b), pos]`` — still b slots of bytes, not a full-cache
    rewrite."""
    b = token.shape[0]
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    max_len = cache["k"].shape[2]
    _gqa_only(config, "decode_step")
    x = params["embed"][token][:, None, :].astype(config.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    if per_row:
        positions = pos[:, None]
        valid = (
            lax.broadcasted_iota(jnp.int32, (1, 1, max_len), 2)
            <= pos[:, None, None]
        )  # [b, 1, max_len]
    else:
        positions = jnp.broadcast_to(pos, (b, 1))
        valid = (
            lax.broadcasted_iota(jnp.int32, (1, 1, max_len), 2) <= pos
        )  # [1, 1, max_len], broadcast over batch and heads

    rows = jnp.arange(b) if per_row else None

    def _cache_write(buf, new):
        """buf [b, L, heads, hd], new [b, 1, heads, hd] at pos."""
        if per_row:
            return buf.at[rows, pos].set(new[:, 0])
        return lax.dynamic_update_slice(buf, new, (0, pos, 0, 0))

    def layer_fn(x, inputs):
        layer, entry = inputs                    # the layer's cache
        normed = _norm(config, x, layer["attn_norm"])
        q, k_new, v_new = _project_kv(config, layer, normed, positions)
        new = _kv_entries(k_new, v_new, "k_scale" in entry)
        entry = {
            name: _cache_write(buf, new[name]) for name, buf in entry.items()
        }
        attn = _masked_attention(
            config, q, entry["k"], entry["v"], valid, [
                entry[name][..., 0] for name in ("k_scale", "v_scale")
                if name in entry
            ],
        )
        x = x + attn.reshape(b, 1, h * hd) @ dq(layer["wo"], x.dtype)
        x, _counts = _serve_ffn(config, layer, x)
        return x, entry

    x, new_cache = lax.scan(layer_fn, x, (params["layers"], cache))
    x = _norm(config, x, params["final_norm"])
    return _last_logits(config, params, x[:, 0]), new_cache


# the leaves of a mixture's stack that hold its experts: never a scanned
# array of a serving program (``_held_experts``)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _held_experts(stack):
    """``stack`` (a mixture's leaves over its layers) split into the
    experts, which stay WHOLE outside any scan and are read in place
    (ops/grouped_matmul.py: a layer's slice in front of the kernel
    would be a copy of the layer's experts), and the rest, which is
    small and scanned or indexed a layer at a time."""
    if "router" not in stack:
        return None, stack
    return (
        {name: stack[name] for name in EXPERT_LEAVES},
        {k: v for k, v in stack.items() if k not in EXPERT_LEAVES},
    )


def _serve_ffn(config: TransformerConfig, layer, x: jax.Array, live=None,
               experts=None, index=0):
    """The FFN of a serving layer under its profile scope, with what
    the mixture counted (int32 ``[2]``: live assignments, expert groups
    touched; None for a dense block).  The dense block is ``mlp``; the
    mixture (``layer`` holds a ``router``) names its own two parts
    (``moe_router`` / ``moe_experts``, models/moe.py ``moe_serve_ffn``).
    ``live [b * s]`` marks the rows that stand for something; the
    others reach no expert.  ``experts`` are the held stacks with
    ``index`` this layer's place in them; without them the layer's own
    leaves are a stack of one."""
    if "router" not in layer:
        with jax.named_scope("mlp"):
            if config.sandwich_norm:
                return x + _norm(
                    config, _mlp(config, layer, x), layer["mlp_post_norm"]
                ), None
            return _mlp_block(config, layer, x), None
    from dcos_commons_tpu.models.moe import moe_serve_ffn

    if experts is None:
        experts = {
            name: jax.tree.map(lambda a: a[None], layer[name])
            for name in EXPERT_LEAVES
        }
    b, s, d = x.shape
    normed = _norm(config, x, layer["mlp_norm"])
    y, counts = moe_serve_ffn(
        moe_config_of(config), layer, experts, index,
        normed.reshape(b * s, d), live,
    )
    y = y.reshape(b, s, d)
    if config.sandwich_norm:
        y = _norm(config, y, layer["mlp_post_norm"])
    return x + y, counts


def _scan_layers_over_arena(layer_fn, x, layers, cache):
    """Run ``layer_fn`` over the stacked ``layers`` with the arena as
    the scan's CARRY: ``layer_fn((x, arena), (layer, base))`` gets the
    arena's arrays with layers and pages merged into one leading axis
    (``[n_layers * n_pages, page_tokens, ...]``, a reshape of the
    stored layout that moves no byte) and ``base``, the layer's first
    row of that axis, so layer ``l`` reaches its page ``p`` at row
    ``base + p`` with ONE scatter into the whole buffer and ONE gather
    out of it.  Returns (x, the cache in its stored layout, what
    ``layer_fn`` returned beside its carry, stacked over the layers).

    The arena must never be a scanned array (an ``xs``/``ys`` of this
    scan): XLA then slices a whole layer out of the stacked buffer,
    updates the copy and writes it into a second stacked buffer, in
    every layer — gigabytes of HBM traffic a call for a few KiB of new
    keys and values.  As a carry the donated buffer is updated in
    place (tests/test_paged_kv.py holds the jaxpr to it)."""
    n_layers, n_pages = cache["k"].shape[:2]
    arena = {
        name: arr.reshape((-1,) + arr.shape[2:])
        for name, arr in cache.items()
    }
    bases = jnp.arange(n_layers, dtype=jnp.int32) * n_pages
    (x, arena), ys = lax.scan(layer_fn, (x, arena), (layers, bases))
    return x, {
        name: arr.reshape(cache[name].shape)
        for name, arr in arena.items()
    }, ys


def layer_plan(kinds) -> Tuple[int, int, int, int]:
    """How a serving program walks the pattern ``kinds`` (one entry a
    layer): ``(lead, period, reps, tail)`` — ``lead`` layers one by
    one, then ``reps`` whole periods of ``period`` layers under ONE
    ``lax.scan`` (the period's layers unrolled in its body), then
    ``tail`` layers one by one.  Of the ways to cut the pattern so, the
    one that compiles the fewest layer bodies (``lead + period +
    tail``; ties to the shorter lead, then the shorter period)."""
    n = len(kinds)
    best = None
    for lead in range(n):
        for period in range(1, n - lead + 1):
            reps = 1
            while kinds[lead + reps * period:lead + (reps + 1) * period] \
                    == kinds[lead:lead + period]:
                reps += 1
            tail = n - lead - reps * period
            cost = (lead + period + tail, lead, period)
            if best is None or cost < best[0]:
                best = (cost, (lead, period, reps, tail))
    return best[1]


class Part(NamedTuple):
    """An attention class's share of ONE layer of a serving program:
    ``attend(arena, layer, base, q, k_new, v_new) -> (attn, arena)``
    writes what ``rows`` rows of the layer's operand keep of their new
    keys and values and attends for them.  ``arena``: the layer kind's
    merged arena (``_scan_layers_over_arena``), ``base`` the layer's
    first page in it; ``q``, ``k_new``, ``v_new``: the rows'
    projections ``[rows, heads, hd]``, or, where the operand is the
    part's alone, as they stand (``[1, rows, ..]`` of a chunk,
    ``[rows, 1, ..]`` of a step: ``_rows``), so nothing is traced to
    lay them out twice; ``attn``: ``[rows, h, hd]`` in whatever shape.

    The part owns the class's cache: its region of the row's table,
    write indexes, masks, the entries' format, the rule that chooses a
    kernel.  Norm, projection and residual are ``_attention_layer``'s,
    so a new class (a latent cache, a selector's keys) is a cache spec
    and two parts, a chunk's and a step's (docs/developer-guide.md).
    ``scope``: the profile scope of the layer's residual where it is
    not the kind's own."""

    rows: int
    attend: Callable
    scope: str = ""


# an attention layer by its kind: the profile scope of its norm,
# projections and residual, and what the cache adds to the names its
# parts know their arena's leaves by (``k``, ``v``, an int8 arena's
# scales)
_KINDS = {
    "attention": dict(scope="attention", suffix=""),
    "sliding": dict(scope="attention_window", suffix="_window"),
}


def _rows(a: jax.Array, unit_axis: Optional[int] = None) -> jax.Array:
    """A part's rows ``[n, heads, hd]`` of a projection that reached it
    flat or as it stood (``Part``): the entry of its ``unit_axis`` or,
    given none, one reshape (each program keeps the form it had)."""
    if a.ndim == 3:
        return a
    if unit_axis is None:
        return a.reshape((-1,) + a.shape[2:])
    return a[0] if unit_axis == 0 else a[:, 0]


def _attention_layer(config, kind, parts, x, positions, arena, layer, base):
    """One attention layer of a serving program, its only spelling:
    norm -> q/k/v (``_project_kv``; a full layer of a pattern without
    position encoding rotates nothing) -> each of ``parts`` on its rows
    of ``x [B, S, d]``, in order -> gate / ``wo`` / second norm ->
    residual (``_attention_residual``).  Norms and projections take ALL
    of ``x`` as one operand, so each weight is read once however many
    parts share the layer: a chunk alone, a step alone, or a chunk
    with a step riding behind it (whose rows' pages are other rows'
    than the chunk's).  Returns (x, arena)."""
    width = config.n_heads * config.head_dim
    scope, alone = _KINDS[kind]["scope"], len(parts) == 1
    with jax.named_scope(scope):
        normed = _norm(config, x, layer["attn_norm"])
        qkv = _project_kv(
            config, layer, normed, positions,
            rope=kind != "attention" or not config.nope_full_attention,
        )
        if not alone:
            qkv = [_rows(a) for a in qkv]
    outs, lo = [], 0
    for part in parts:
        out, arena = part.attend(arena, layer, base, *(
            qkv if alone else [a[lo:lo + part.rows] for a in qkv]
        ))
        outs.append(out if alone else out.reshape(part.rows, width))
        lo += part.rows
    with jax.named_scope(parts[0].scope or scope):
        attn = outs[0] if alone else jnp.concatenate(outs)
        x = _attention_residual(
            config, layer, x, normed, attn.reshape(x.shape[:2] + (width,))
        )
    return x, arena


def _walk_pattern(config, params, cache, x, positions, parts, convolve,
                  live):
    """Run ``x`` through a MIXED layer pattern (``layer_plan``) over
    the cache: an attention layer of either kind is
    ``_attention_layer`` with ``parts[kind]`` over the kind's merged
    arena (``_KINDS``; ``base = a * n_pages`` for the ``a``-th layer of
    the kind: only those own its pages),
    ``convolve(x, conv_state, layer, c) -> (x, conv_state)`` the conv
    operator of the ``c``-th conv layer.  Arenas and conv state are the
    scan's carry, never scanned arrays (``_scan_layers_over_arena``
    says why); a layer's weights are read at its index of its part's
    stack (``init_params``), the experts in place.  Returns (x, the
    cache in its stored layout, the mixtures' counts summed)."""
    kinds = config.layer_kinds
    lead, period, reps, _tail = layer_plan(kinds)
    state = {
        name: arr if name == "conv_state"
        else arr.reshape((-1,) + arr.shape[2:])
        for name, arr in cache.items()
    }
    experts, routing = _held_experts(params["layers"].get("moe", {}))
    stacks = dict(params["layers"], moe=routing)
    # each layer's index in its two parts' stacks; what a period adds
    seen, index = {}, []
    for kind in kinds:
        index.append(tuple(seen.get(part, 0) for part in kind))
        for part in kind:
            seen[part] = seen.get(part, 0) + 1
    stride = {
        part: sum(part in kind for kind in kinds[lead:lead + period])
        for part in seen
    }

    def at(stack, i):
        if isinstance(i, int):
            return jax.tree.map(lambda a: a[i], stack)
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            stack,
        )

    def one(carry, l, trip):
        """Layer ``l``, or the layer ``trip`` periods behind it."""
        x, state, counts = carry
        op, ffn = kinds[l]
        op_i = index[l][0] + trip * stride[op]
        ffn_i = index[l][1] + trip * stride[ffn]
        if op == "conv":
            x, conv = convolve(
                x, state["conv_state"], at(stacks[op], op_i), op_i
            )
            state = dict(state, conv_state=conv)
        else:
            suffix = _KINDS[op]["suffix"]
            x, arena = _attention_layer(
                config, op, parts[op], x, positions, {
                    name: state[name + suffix]
                    for name in ("k", "v", "k_scale", "v_scale")
                    if name + suffix in state
                }, at(stacks[op], op_i), op_i * cache["k" + suffix].shape[1],
            )
            state = dict(
                state, **{name + suffix: arr for name, arr in arena.items()}
            )
        x, c = _serve_ffn(
            config, at(stacks[ffn], ffn_i), x, live, experts, ffn_i
        )
        return x, state, counts if c is None else counts + c

    def whole_period(carry, trip):
        for l in range(lead, lead + period):
            carry = one(carry, l, trip)
        return carry, None

    carry = (x, state, jnp.zeros(2, jnp.int32))
    for l in range(lead):
        carry = one(carry, l, 0)
    carry, _ = lax.scan(
        whole_period, carry, jnp.arange(reps, dtype=jnp.int32)
    )
    for l in range(lead + reps * period, len(kinds)):
        carry = one(carry, l, 0)
    x, state, counts = carry
    return x, {
        name: arr.reshape(cache[name].shape) for name, arr in state.items()
    }, counts


def _serving_trunk(config, params, cache, x, positions, parts, convolve,
                   live):
    """``x [B, S, d]`` at ``positions [B, S]`` through every layer over
    the cache, for both serving programs of every family: ``parts`` by
    layer kind (``{"attention": [..], "sliding": [..]}``, each list
    covering ``x``'s ``B * S`` rows in order: ``Part``), ``convolve``
    the conv operator, ``live [B * S]`` the rows that stand for
    something.  The ONE place that chooses how the layers are driven: a
    scan over the one stack where every layer is of one kind,
    ``_walk_pattern`` over a pattern.  Returns (x, the cache in its
    stored layout, the mixtures' counts summed or None)."""
    if not config.one_kind:
        return _walk_pattern(
            config, params, cache, x, positions, parts, convolve, live
        )
    n_pages = cache["k"].shape[1]
    experts, scanned = _held_experts(params["layers"])

    def layer_fn(carry, inputs):
        layer, base = inputs
        x, arena = _attention_layer(
            config, "attention", parts["attention"], carry[0], positions,
            carry[1], layer, base,
        )
        # a dense block asks for no place among held experts
        x, counts = _serve_ffn(
            config, layer, x, live, experts, base // n_pages if experts else 0
        )
        return (x, arena), counts

    x, new_cache, counts = _scan_layers_over_arena(
        layer_fn, x, scanned, cache
    )
    return x, new_cache, None if counts is None else counts.sum(0)


def _conv_gates(config: TransformerConfig, layer, x):
    """``x [b, s, d]`` -> the conv operator's gated input ``u = B * X``
    and its output gate ``C``, each ``[b, s, d]``."""
    normed = _norm(config, x, layer["conv_norm"])
    b_gate, c_gate, x_gate = jnp.split(
        normed @ dq(layer["conv_in"], x.dtype), 3, axis=-1
    )
    return b_gate * x_gate, c_gate


def _conv_taps(layer, seq, n: int):
    """The depthwise causal convolution: ``seq [..., taps - 1 + n, d]``
    (the state, then ``n`` new gated inputs) -> ``[..., n, d]``, tap
    ``j`` on the input ``taps - 1 - j`` back; summed in float32."""
    w = layer["conv_w"].astype(jnp.float32)                # [d, taps]
    out = sum(
        w[:, j] * lax.slice_in_dim(seq, j, j + n, axis=-2).astype(jnp.float32)
        for j in range(w.shape[1])
    )
    return out.astype(seq.dtype)


def _conv_chunk_operator(config: TransformerConfig, start, true_len, slot):
    """The conv operator of a prefill chunk of the row in ``slot``:
    reads the row's state (zeros where the chunk is the row's first:
    whoever held the slot before is gone), convolves ``[state ; u]``,
    writes back the last ``taps - 1`` of the TRUE positions."""
    keep = config.conv_l_cache - 1

    def convolve(x, conv, layer, index):
        d = x.shape[-1]
        at = (jnp.asarray(index, jnp.int32), slot, jnp.int32(0), jnp.int32(0))
        with jax.named_scope("short_conv"):
            u, c_gate = _conv_gates(config, layer, x)
            state = lax.dynamic_slice(conv, at, (1, 1, keep, d))[0, 0]
            state = jnp.where(start == 0, jnp.zeros_like(state), state)
            seq = jnp.concatenate([state, u[0]], axis=0)
            v = _conv_taps(layer, seq, u.shape[1])
            x = x + (c_gate * v[None]) @ dq(layer["conv_out"], x.dtype)
        with jax.named_scope("conv_state_write"):
            # u_t sits at seq[keep + t]: the inputs behind true_len
            last = lax.dynamic_slice_in_dim(seq, true_len, keep, axis=0)
            conv = lax.dynamic_update_slice(conv, last[None, None], at)
        return x, conv

    return convolve


def _conv_step_operator(config: TransformerConfig, live):
    """The conv operator of a decode step over every slot: one shift
    and one write a layer; a slot that is not ``live`` (idle, or held
    by a row that is still prefilling or frozen) keeps its state."""

    def convolve(x, conv, layer, index):
        with jax.named_scope("short_conv"):
            u, c_gate = _conv_gates(config, layer, x)
            state = lax.dynamic_index_in_dim(
                conv, index, 0, keepdims=False
            )                                              # [b, keep, d]
            seq = jnp.concatenate([state, u], axis=1)      # [b, taps, d]
            v = _conv_taps(layer, seq, 1)
            x = x + (c_gate * v) @ dq(layer["conv_out"], x.dtype)
        with jax.named_scope("conv_state_write"):
            conv = lax.dynamic_update_index_in_dim(
                conv, jnp.where(live[:, None, None], seq[:, 1:], state),
                index, 0,
            )
        return x, conv

    return convolve


def _write_rows(arena, base, pages, offset, k_new, v_new):
    """``arena`` with the rows' new K/V ``[n, kv, hd]`` at entry
    ``offset [n]`` of the layer's ``pages [n]`` (``base`` its first):
    widened with zeros to the arena's lanes (``arena_lanes``), int8
    with their per-vector scales where the arena is quantized."""
    with jax.named_scope("kv_write"):
        short = arena["k"].shape[-1] - k_new.shape[-1]
        if short:
            pad = [(0, 0)] * (k_new.ndim - 1) + [(0, short)]
            k_new, v_new = jnp.pad(k_new, pad), jnp.pad(v_new, pad)
        new = _kv_entries(k_new, v_new, "k_scale" in arena)
        # one sum a leaf: the programs' equations are held to what they
        # were (tests/test_serving_programs.py)
        return {
            name: arr.at[base + pages, offset].set(new[name])
            for name, arr in arena.items()
        }


def _gather_pages(arena, base, pages, b: int, hd: int):
    """The entries of the layer's ``pages`` (``base`` its first; ``b``
    rows' tables or one) in their order: keys and values ``[b, L, kv,
    hd]`` and an int8 arena's two scales ``[b, L, kv]``."""
    kv, lanes = arena["k"].shape[-2:]
    with jax.named_scope("paged_gather"):
        pages = base + pages
        keys, values = (
            arena[name][pages].reshape(b, -1, kv, lanes)[..., :hd]
            for name in ("k", "v")
        )
        scales = [
            arena[name][pages].reshape(b, -1, kv)
            for name in ("k_scale", "v_scale") if name in arena
        ]
    return keys, values, scales


# the most positions of history a prefill chunk scores at once; a
# longer history is attended in blocks of this many (tests shrink it)
CHUNK_ATTENTION_BLOCK = 2048


def _ring_pages(config: TransformerConfig, ring_pages: int) -> int:
    """``ring_pages`` as a serving program was handed it, held to the
    pattern: window layers need their ring, no other model has one."""
    if bool(ring_pages) != bool(config.n_layers_of("sliding")):
        raise ValueError(
            f"{config.n_layers_of('sliding')} window attention layers and "
            f"a ring of {ring_pages} pages a row: a table's first entries "
            "are the window layers' ring where the pattern has such "
            "layers, and only there (serve/paging.py RowLayout)"
        )
    return int(ring_pages)


def _ring_positions(index, last_page, ring: int, page_tokens: int):
    """The position each ring entry ``index`` (its place among the
    ring's ``ring * page_tokens`` entries) holds once the row has
    written as far as virtual page ``last_page``: a ring page holds the
    newest virtual page that maps onto it (before 0: nothing yet).
    Entries of ``last_page`` past the row's end still hold the page a
    whole ring before: the caller masks by the row's length."""
    page = last_page - (last_page - index // page_tokens) % ring
    return page * page_tokens + index % page_tokens


def _attention_block_pages(pages: int, page_tokens: int,
                           most: int = 0) -> int:
    """Pages a block of a region of ``pages`` pages: the most whole
    pages within ``most`` positions (``CHUNK_ATTENTION_BLOCK`` where 0)
    that divide the region, so every block is whole."""
    block = max(1, min(pages, (most or CHUNK_ATTENTION_BLOCK) // page_tokens))
    while pages % block:
        block -= 1
    return block


def _attend_blocks(q, arena, pages, block, n_blocks, key_pos, q_pos, end,
                   window, hd):
    """A chunk's queries ``q [.., c, h, hd]`` (in the serving dtype,
    unscaled) against the entries of ``pages [n]`` (arena rows), read
    ``block`` pages at a time under ONE online softmax
    (``_softmax_block``); only the first ``n_blocks`` blocks (traced)
    are gathered at all.  ``key_pos(index [L]) -> [L]`` says which
    position each entry of the region holds; the query at ``q_pos[i]``
    sees the keys at positions ``j`` with ``q_pos[i] - window < j <=
    q_pos[i]`` and ``0 <= j < end``.  Returns ``[1, c, kv, reps, hd]``
    float32."""
    c, h = q.shape[-3:-1]
    p_tok, kv = arena["k"].shape[1:3]
    reps = h // kv
    qg = q.reshape(1, c, kv, reps, hd)
    n = block * p_tok

    def one(i, state):
        with jax.named_scope("paged_gather"):
            ids = lax.dynamic_slice_in_dim(pages, i * block, block)
            keys = arena["k"][ids].reshape(1, n, kv, -1)[..., :hd]
            values = arena["v"][ids].reshape(1, n, kv, -1)[..., :hd]
        held = key_pos(i * n + jnp.arange(n, dtype=jnp.int32))
        mask = (
            (held[None, :] <= q_pos[:, None])
            & (held[None, :] > q_pos[:, None] - window)
            & (held >= 0)[None, :] & (held < end)[None, :]
        )[None]
        return _softmax_block(state, qg, keys, values, mask, hd ** -0.5)

    _m, norm, acc = lax.fori_loop(
        0, n_blocks, one, _softmax_start(1, c, kv, reps, hd)
    )
    return acc / norm[..., None]


def _full_chunk_part(config, cache, history, abs_pos, offset, live, end):
    """A prefill chunk's part of a full-attention layer (``Part``): the
    chunk's K/V is scattered through ``history [m]``, the full layers'
    entries of the row's table (pad positions, not ``live``, into the
    trash page: never into a page a later chunk attends to), then each
    query at ``abs_pos`` attends to EVERY earlier position through the
    same entries: prior chunks' pages, prefix-cache pages and the
    in-chunk causal prefix ride one path.  ``offset``: each position's
    place in its page; ``end``: the positions written once this chunk
    is.  A history longer than ``CHUNK_ATTENTION_BLOCK`` positions is
    attended a block of pages at a time under one running softmax, as
    far as the row reaches: no ``[chunk, MAX_LEN]`` scores are built
    (an int8 arena's scales have no blocked form: one softmax)."""
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    p_tok, reps = cache["k"].shape[2], h // kv
    c, m = abs_pos.shape[0], history.shape[0]
    length = m * p_tok
    phys = jnp.where(
        live, history[jnp.minimum(abs_pos // p_tok, m - 1)], 0
    )
    block = _attention_block_pages(m, p_tok)
    blocked = block < m and "k_scale" not in cache
    if not blocked:
        # causal across the whole virtual sequence: key position <=
        # query position; unallocated pages sit past every valid query
        # and mask out
        valid = (
            lax.broadcasted_iota(jnp.int32, (c, length), 1)
            <= abs_pos[:, None]
        )                                        # [c, L]

    def attend(arena, layer, base, q, k_new, v_new):
        arena = _write_rows(
            arena, base, phys, offset, _rows(k_new, 0), _rows(v_new, 0)
        )
        if blocked:
            with jax.named_scope("attention_full"):
                attn = _attend_blocks(
                    q, arena, base + history, block,
                    -(-end // (block * p_tok)),
                    lambda index: index, abs_pos, end, length, hd,
                ).astype(config.dtype)
            return attn, arena
        k_all, v_all, scales = _gather_pages(arena, base, history, 1, hd)
        with jax.named_scope("attention"):
            qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(
                1, c, kv, reps, hd
            )
            scores = jnp.einsum(
                "bqkrd,blkd->bqkrl", qg, k_all.astype(jnp.float32)
            )
            if scales:
                scores *= scales[0].transpose(0, 2, 1)[:, None, :, None, :]
            scores = jnp.where(valid[None, :, None, None, :], scores, _NEG)
            probs = jax.nn.softmax(scores, axis=-1)
            if scales:
                probs *= scales[1].transpose(0, 2, 1)[:, None, :, None, :]
            attn = jnp.einsum(
                "bqkrl,blkd->bqkrd", probs, v_all.astype(jnp.float32)
            ).astype(config.dtype)
        return attn, arena

    return Part(c, attend, "attention_full" if blocked else "")


def _window_chunk_part(config, cache, ring_table, abs_pos, offset, live,
                       end):
    """A prefill chunk's part of a window layer, as ``_full_chunk_part``
    a full layer's: K/V into the row's ring ``ring_table [ring]`` in
    the window layers' arena, attention over the ring alone, the last
    ``sliding_window`` positions.  Keys are stored rotated, so the
    ring's entries need no order: what an entry holds follows from its
    place and the row's length (``_ring_positions``)."""
    hd, p_tok = config.head_dim, cache["k_window"].shape[2]
    c, ring = abs_pos.shape[0], ring_table.shape[0]
    ring_phys = jnp.where(live, ring_table[(abs_pos // p_tok) % ring], 0)
    ring_block = _attention_block_pages(ring, p_tok, c)
    # what the ring holds once this chunk's true positions are written
    ring_pos = functools.partial(
        _ring_positions, last_page=(end - 1) // p_tok, ring=ring,
        page_tokens=p_tok,
    )

    def attend(arena, layer, base, q, k_new, v_new):
        arena = _write_rows(
            arena, base, ring_phys, offset, _rows(k_new, 0), _rows(v_new, 0)
        )
        with jax.named_scope("attention_window"):
            # a ring that has not wrapped holds its first entries
            attn = _attend_blocks(
                q, arena, base + ring_table, ring_block,
                jnp.minimum(
                    -(-end // (ring_block * p_tok)), ring // ring_block
                ),
                ring_pos, abs_pos, end, config.sliding_window, hd,
            ).astype(config.dtype)
        return attn, arena

    return Part(c, attend)


def _full_step_part(config, cache, history, pos, rows, offset, live):
    """A decode step's part of a full-attention layer (``Part``): row
    ``s`` (``rows`` counts them) writes its new K/V at ``(history[s,
    pos // P], offset)`` (a row that is not ``live``, its table all
    zeros, into the trash page) and attends to its whole history: the
    page walk of ops/paged_decode.py over the row's live pages in
    place, or (``decode_attention_kernel``) every row's pages gathered
    into virtual order and one masked softmax, element for element
    ``decode_step``'s with ``max_len = M * P``."""
    from dcos_commons_tpu.ops.paged_decode import paged_decode_attention

    hd, (b, m) = config.head_dim, history.shape
    p_tok, lanes = cache["k"].shape[2], cache["k"].shape[-1]
    phys = history[rows, jnp.minimum(pos // p_tok, m - 1)]      # [b]
    kernel = decode_attention_kernel(config, cache)
    if not kernel:
        valid = (
            lax.broadcasted_iota(jnp.int32, (1, 1, m * p_tok), 2)
            <= pos[:, None, None]
        )                                        # [b, 1, L]

    def attend(arena, layer, base, q, k_new, v_new):
        arena = _write_rows(
            arena, base, phys, offset, _rows(k_new, 1), _rows(v_new, 1)
        )
        if kernel:
            # the row's new K/V is read back through the page it was
            # just written into
            with jax.named_scope(
                "attention_full" if config.n_layers_of("sliding")
                else "attention"
            ):
                return paged_decode_attention(
                    jnp.pad(_rows(q, 1), ((0, 0), (0, 0), (0, lanes - hd))),
                    arena["k"], arena["v"], base + history, pos, live,
                    scale=hd ** -0.5, interpret=kernel == "interpret",
                )[..., :hd], arena
        k_all, v_all, scales = _gather_pages(arena, base, history, b, hd)
        with jax.named_scope("attention"):
            return _masked_attention(
                config, q, k_all, v_all, valid, scales
            ), arena

    return Part(b, attend)


def _window_step_part(config, cache, ring_tables, pos, rows, offset, live):
    """A decode step's part of a window layer, as ``_full_step_part`` a
    full layer's: the row's new K/V into its ring ``ring_tables[s]``,
    the last ``sliding_window`` positions read from it (the page walk
    over the ring's pages in virtual order with a lower bound on the
    entries that count, or the ring gathered and masked by what each
    entry holds: ``_ring_positions``)."""
    from dcos_commons_tpu.ops.paged_decode import window_decode_attention

    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    p_tok, lanes = cache["k_window"].shape[2], cache["k_window"].shape[-1]
    b, ring = ring_tables.shape
    window, reps = config.sliding_window, h // kv
    ring_phys = ring_tables[rows, (pos // p_tok) % ring]
    kernel = decode_attention_kernel(config, cache)
    if not kernel:
        # the position each ring entry of each row holds once the
        # row's new K/V is written
        held = _ring_positions(
            jnp.arange(ring * p_tok, dtype=jnp.int32)[None, :],
            (pos // p_tok)[:, None], ring, p_tok,
        )
        seen = (
            (held <= pos[:, None]) & (held > pos[:, None] - window)
            & (held >= 0)
        )[:, None, :]                            # [b, 1, ring * P]

    def attend(arena, layer, base, q, k_new, v_new):
        arena = _write_rows(
            arena, base, ring_phys, offset, _rows(k_new, 1), _rows(v_new, 1)
        )
        with jax.named_scope("attention_window"):
            if kernel:
                return window_decode_attention(
                    jnp.pad(_rows(q, 1), ((0, 0), (0, 0), (0, lanes - hd))),
                    arena["k"], arena["v"], base + ring_tables, pos,
                    live, window=window, scale=hd ** -0.5,
                    interpret=kernel == "interpret",
                )[..., :hd], arena
            keys, values, _ = _gather_pages(arena, base, ring_tables, b, hd)
            _m, norm, acc = _softmax_block(
                _softmax_start(b, 1, kv, reps, hd),
                q.reshape(b, 1, kv, reps, hd), keys, values, seen,
                hd ** -0.5,
            )
            return (acc / norm[..., None]).astype(config.dtype), arena

    return Part(b, attend)


def _eva_geometry(config: TransformerConfig, cache, table_len: int):
    """(window pages, summary pages) of an EVA row's table, after
    checking what the layout rests on: a page IS a chunk, so a ring
    page holds one chunk's exact keys and a summary page the
    summaries of ``page_tokens`` chunks (serve/paging.py RowLayout)."""
    if "k_scale" in cache:
        raise ValueError("eva attention has no int8 cache")
    p_tok = cache["k"].shape[2]
    if p_tok != config.chunk_size:
        raise ValueError(
            f"eva attention needs KV_PAGE_TOKENS == chunk_size "
            f"({config.chunk_size}), got {p_tok}"
        )
    window_pages = config.window_size // p_tok
    if table_len <= window_pages:
        raise ValueError(
            f"an eva row's table holds {window_pages} window pages and "
            f"at least one summary page, got {table_len} entries"
        )
    return window_pages, table_len - window_pages


def _eva_summaries(config: TransformerConfig, layer, k, v):
    """One summary a chunk: ``k``, ``v`` ``[n, chunk, kv, hd]`` (roped
    keys) -> ``k~, v~ [n, kv, hd]``.  The chunk's positions are pooled
    by ``softmax_j(s * phi . k_j)``; the summary key adds ``mu``."""
    hd = config.head_dim
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    phi = layer["eva_phi"].astype(jnp.float32)
    pool = jax.nn.softmax(
        jnp.einsum("nckd,kd->nck", kf, phi) * hd ** -0.5, axis=1
    )
    k_sum = jnp.einsum("nck,nckd->nkd", pool, kf) + layer["eva_mu"].astype(
        jnp.float32
    )
    v_sum = jnp.einsum("nck,nckd->nkd", pool, vf)
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def _softmax_start(b: int, c: int, kv: int, reps: int, hd: int):
    """The state of an online softmax that has seen nothing."""
    return (
        jnp.full((b, c, kv, reps), _NEG, jnp.float32),
        jnp.zeros((b, c, kv, reps), jnp.float32),
        jnp.zeros((b, c, kv, reps, hd), jnp.float32),
    )


def _softmax_block(carry, qg, keys, values, mask, scale):
    """One block of an online softmax: ``carry`` is ``(m, l, acc)``
    (running maximum and normaliser ``[b, c, kv, reps]``, weighted
    values ``[b, c, kv, reps, hd]``, float32) over the blocks seen so
    far; ``qg [b, c, kv, reps, hd]`` in the serving dtype, unscaled;
    ``keys``, ``values`` ``[b, L, kv, hd]``, ``mask [b, c, L]``.  A set
    of keys attended in several blocks, or two sets (the exact keys of
    the query's window and the summaries of the windows before it),
    come under ONE softmax this way; ``_softmax_start`` begins it and
    ``acc / l`` ends it.

    Mixed precision as the checkpoint's ``mixedp_attn``: the products
    take their operands in the dtype they are stored in and accumulate
    in float32, the softmax is float32, the probabilities are rounded
    to the values' dtype for their product."""
    m, l, acc = carry
    raw = jnp.einsum(
        "bqkrd,blkd->bqkrl", qg, keys, preferred_element_type=jnp.float32
    ) * scale
    raw = jnp.where(mask[:, :, None, None, :], raw, _NEG)
    m_new = jnp.maximum(m, raw.max(-1))
    shrink = jnp.exp(m - m_new)
    e = jnp.exp(raw - m_new[..., None])
    l = shrink * l + e.sum(-1)
    acc = shrink[..., None] * acc + jnp.einsum(
        "bqkrl,blkd->bqkrd", e.astype(values.dtype), values,
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def chunk_carries_riders(config: TransformerConfig) -> bool:
    """Whether ``paged_prefill_chunk`` takes a decode step as
    ``riders``: the family has a layer that runs a chunk's positions
    and a step's rows as one operand.  EVA's has; a grouped-query
    walk, a mixture's counts and conv state have none yet."""
    return config.attention == "eva"


def _eva_chunk_part(config, cache, table, start, true_len, offs, abs_pos,
                    live):
    """A prefill chunk's part of an EVA layer (``Part``), for the
    positions ``abs_pos = start + offs`` of which the first
    ``true_len`` are ``live``.

    The row's table has two regions (serve/paging.py RowLayout): the
    first ``window_size / P`` entries are a RING of exact K/V pages
    (position ``p`` lives in ring page ``(p % window_size) // P``, so a
    new window writes over the last one in place), the rest hold the
    chunk summaries (chunk ``c`` at entry ``c`` of that region, ``P`` a
    page).  A query at ``p`` sees the exact keys ``j <= p`` of its own
    window and the summaries of every chunk of an EARLIER window.

    The old state is read BEFORE this chunk's writes and the chunk's
    own keys and finished summaries are attended directly: a chunk
    that straddles a window's end writes the new window over ring
    pages its own earlier queries still need.  ``start`` is a multiple
    of the chunk size (the engine advances by whole prefill chunks,
    themselves whole EVA chunks).

    The old state is attended a BLOCK of pages at a time under one
    online softmax, and a block no query of this chunk can see is not
    even gathered (``lax.cond``): of the ring, the blocks behind
    ``start`` in its window; of the summaries, those of the windows
    that are past.  A dense masked softmax over the whole table scores
    4,096 old entries a query where a chunk half way through an
    8,192-byte prompt can see 1,300."""
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    win, chunk, c = config.window_size, config.chunk_size, offs.shape[0]
    if c % chunk or c > win:
        raise ValueError(
            f"an eva prefill chunk is a whole number of {chunk}-position "
            f"chunks and at most one window ({win}), got {c}"
        )
    p_tok = cache["k"].shape[2]
    wp, sp = _eva_geometry(config, cache, table.shape[0])
    per_win, n_c, reps = win // chunk, c // chunk, h // kv
    q_win = abs_pos // win                       # each query's window
    # exact K/V: into the ring (pad positions into the trash page)
    phys = jnp.where(live, table[(abs_pos % win) // p_tok], 0)
    slot_off = abs_pos % p_tok
    # the summaries this chunk finishes: into the summary region
    chunk_id = start // chunk + jnp.arange(n_c, dtype=jnp.int32)
    finished = (jnp.arange(n_c, dtype=jnp.int32) + 1) * chunk <= true_len
    sum_phys = jnp.where(
        finished, table[wp + jnp.minimum(chunk_id // p_tok, sp - 1)], 0
    )
    sum_off = chunk_id % p_tok
    # what each query sees: of this chunk, the causal prefix in its own
    # window and the finished summaries of an earlier window (a
    # straddling chunk's alone); of the ring as it was, its own
    # window's positions before this chunk; of the old summaries, the
    # chunks of earlier windows
    seen = q_win[:, None] * per_win
    mask_new = (
        (offs[None, :] <= offs[:, None]) & (q_win[None, :] == q_win[:, None])
    )[None]                                      # [1, c, c]
    mask_sum_new = (chunk_id[None, :] < seen)[None]   # [1, c, n_c]
    in_first = q_win == start // win
    # blocks of the old state, in pages: one prefill chunk's worth
    # where that divides the window, else the whole window
    block = (c if win % c == 0 else win) // p_tok
    ring_blocks = [
        (lo, min(lo + block, wp)) for lo in range(0, wp, block)
    ]
    sum_blocks = [
        (lo, min(lo + block, sp)) for lo in range(0, sp, block)
    ]
    old_sums = jnp.minimum(
        (start + c - 1) // win * per_win, start // chunk
    )                                            # the most any query sees
    scale = hd ** -0.5

    def attend(arena, layer, base, q, k_new, v_new):
        q, k_new, v_new = (_rows(a) for a in (q, k_new, v_new))
        with jax.named_scope("eva_summarise"):
            k_sum_new, v_sum_new = _eva_summaries(
                config, layer,
                k_new.reshape(n_c, chunk, kv, hd),
                v_new.reshape(n_c, chunk, kv, hd),
            )
        qg = q.reshape(1, c, kv, reps, hd)
        state = _softmax_start(1, c, kv, reps, hd)
        with jax.named_scope("eva_window_attention"):
            state = _softmax_block(
                state, qg, k_new[None], v_new[None], mask_new, scale
            )
        with jax.named_scope("eva_summary_attention"):
            state = _softmax_block(
                state, qg, k_sum_new[None], v_sum_new[None], mask_sum_new,
                scale,
            )

        def old_block(lo, hi, region, mask):
            """Attend table entries ``[region + lo, region + hi)`` as
            they are in the arena now (before this layer's writes)."""
            def attend(state):
                with jax.named_scope("paged_gather"):
                    pages = base + lax.dynamic_slice_in_dim(
                        table, region + lo, hi - lo
                    )
                    n = (hi - lo) * p_tok
                    keys = arena["k"][pages].reshape(1, n, kv, hd)
                    values = arena["v"][pages].reshape(1, n, kv, hd)
                return _softmax_block(state, qg, keys, values, mask, scale)
            return attend

        for lo, hi in ring_blocks:
            idx = jnp.arange(lo * p_tok, hi * p_tok, dtype=jnp.int32)
            mask = (in_first[:, None] & (idx[None, :] < start % win))[None]
            with jax.named_scope("eva_window_attention"):
                state = lax.cond(
                    start % win > lo * p_tok,
                    old_block(lo, hi, 0, mask), lambda st: st, state,
                )
        for lo, hi in sum_blocks:
            idx = jnp.arange(lo * p_tok, hi * p_tok, dtype=jnp.int32)
            mask = (
                (idx[None, :] < seen) & (idx[None, :] < start // chunk)
            )[None]
            with jax.named_scope("eva_summary_attention"):
                state = lax.cond(
                    old_sums > lo * p_tok,
                    old_block(lo, hi, wp, mask), lambda st: st, state,
                )
        with jax.named_scope("kv_write"):
            arena = {
                "k": arena["k"].at[base + phys, slot_off].set(k_new)
                .at[base + sum_phys, sum_off].set(k_sum_new),
                "v": arena["v"].at[base + phys, slot_off].set(v_new)
                .at[base + sum_phys, sum_off].set(v_sum_new),
            }
        with jax.named_scope("attention"):
            _m, norm, acc = state
            attn = (acc / norm[..., None]).astype(config.dtype)
        return attn[0].reshape(c, h * hd), arena

    return Part(c, attend)


def _eva_step_part(config, cache, pos, tables):
    """A decode step's part of an EVA layer, as ``_eva_chunk_part``
    gives a chunk's.  Each row writes its
    new K/V into its ring, attends to its window's ring entries
    ``<= pos`` and the summaries of the windows before, and, where
    ``pos`` ends a chunk, pools that chunk's page into its summary
    entry (any other row's pooled page goes to the trash page)."""
    from dcos_commons_tpu.ops.eva_decode import (
        eva_decode_attention,
        live_pages,
    )

    b = pos.shape[0]
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    win, chunk = config.window_size, config.chunk_size
    p_tok = cache["k"].shape[2]
    wp, sp = _eva_geometry(config, cache, tables.shape[1])
    per_win, reps = win // chunk, h // kv
    rows = jnp.arange(b)
    ring_page = (pos % win) // p_tok
    phys = tables[rows, ring_page]
    slot_off = pos % p_tok
    chunk_id = pos // chunk
    sum_phys = jnp.where(
        pos % chunk == chunk - 1,
        tables[rows, wp + jnp.minimum(chunk_id // p_tok, sp - 1)], 0,
    )
    sum_off = chunk_id % p_tok
    kernel = decode_attention_kernel(config, cache)
    if kernel:
        live, n_ring, n_live, n_win, n_sum = live_pages(
            tables, pos, win, chunk, p_tok
        )
        # a slot that decodes holds at least its first ring page
        decodes = tables[:, 0] > 0
    else:
        mask_win = (
            lax.broadcasted_iota(jnp.int32, (1, 1, win), 2)
            <= (pos % win)[:, None, None]
        )                                        # [b, 1, win]
        mask_sum = (
            lax.broadcasted_iota(jnp.int32, (1, 1, sp * p_tok), 2)
            < ((pos // win) * per_win)[:, None, None]
        )                                        # [b, 1, sp * P]

    def attend(arena, layer, base, q, k_new, v_new):
        q, k_new, v_new = (_rows(a) for a in (q, k_new, v_new))
        with jax.named_scope("kv_write"):
            arena = {
                "k": arena["k"].at[base + phys, slot_off].set(k_new),
                "v": arena["v"].at[base + phys, slot_off].set(v_new),
            }
        if kernel:
            with jax.named_scope("attention"):
                attn = eva_decode_attention(
                    q, arena["k"], arena["v"], base + live, n_ring,
                    n_live, n_win, n_sum, decodes, scale=hd ** -0.5,
                    interpret=kernel == "interpret",
                )
            # the page each row has just written into: its chunk
            with jax.named_scope("paged_gather"):
                k_page = arena["k"][base + phys]
                v_page = arena["v"][base + phys]
        else:
            with jax.named_scope("paged_gather"):
                pages = base + tables
                k_all, v_all = arena["k"][pages], arena["v"][pages]
                k_page = k_all[rows, ring_page]
                v_page = v_all[rows, ring_page]
            qg, scale = q.reshape(b, 1, kv, reps, hd), hd ** -0.5
            with jax.named_scope("eva_window_attention"):
                state = _softmax_block(
                    _softmax_start(b, 1, kv, reps, hd), qg,
                    k_all[:, :wp].reshape(b, win, kv, hd),
                    v_all[:, :wp].reshape(b, win, kv, hd), mask_win, scale,
                )
            with jax.named_scope("eva_summary_attention"):
                _m, norm, acc = _softmax_block(
                    state, qg,
                    k_all[:, wp:].reshape(b, sp * p_tok, kv, hd),
                    v_all[:, wp:].reshape(b, sp * p_tok, kv, hd), mask_sum,
                    scale,
                )
            attn = (acc / norm[..., None]).astype(config.dtype)
        with jax.named_scope("eva_summarise"):
            k_sum_new, v_sum_new = _eva_summaries(
                config, layer, k_page, v_page
            )
        with jax.named_scope("kv_write"):
            arena = {
                "k": arena["k"].at[base + sum_phys, sum_off].set(k_sum_new),
                "v": arena["v"].at[base + sum_phys, sum_off].set(v_sum_new),
            }
        return attn.reshape(b, h * hd), arena

    return Part(b, attend)


def _step_parts(config, cache, pos, tables, ring_pages):
    """A decode step's attention parts by layer kind, and the rows
    that are live (None where no layer asks: EVA's kernel finds them)."""
    if config.attention == "eva":
        part = _eva_step_part(config, cache, pos, tables)
        return {"attention": [part]}, None
    ring = _ring_pages(config, ring_pages)
    # a live row holds at least its first page
    live = tables[:, 0] > 0
    rows = (pos, jnp.arange(pos.shape[0]), pos % cache["k"].shape[2], live)
    # the ring's entries come first in a row's table, then the history
    parts = {"attention": [_full_step_part(
        config, cache, tables[:, ring:] if ring else tables, *rows
    )]}
    if ring:
        parts["sliding"] = [
            _window_step_part(config, cache, tables[:, :ring], *rows)
        ]
    return parts, live


def paged_prefill_chunk(
    config: TransformerConfig,
    params: Params,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    table: jax.Array,
    start: jax.Array,
    true_len: jax.Array,
    slot: jax.Array = 0,
    riders: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    ring_pages: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[jax.Array]]:
    """One CHUNK of a prompt through the trunk into a paged arena.

    ``tokens [1, C]`` carries up to C prompt tokens at virtual
    positions ``[start, start + true_len)`` of one request whose page
    table is ``table [M]`` (physical page per virtual page; 0 =
    unallocated).  Each attention layer writes what the chunk keeps
    through the table and attends to every earlier position through it
    (what an entry is and who sees it is the attention class's:
    ``_full_chunk_part``, ``_window_chunk_part``, ``_eva_chunk_part``).
    Returns (logits at the chunk's last real position [1, vocab] f32,
    updated cache, the mixtures' counts as ``paged_decode_step``
    returns them).

    ``start``, ``true_len`` and ``slot`` are TRACED: one compile covers
    every chunk of every prompt — a request resuming at position k*P
    after a prefix-cache hit runs the same program as one starting at
    0.  ``slot`` is the row's place among the pool's rows: what a
    pattern with conv layers keeps of a row outside its pages lives
    there (``init_paged_kv_cache``), and a chunk with ``start == 0``
    begins it from zeros; no other model reads it.  A long prompt costs
    several SMALL dispatches between decode ticks, not one that blocks
    the pool (head-of-line TTFT).  A mixture computes for the true
    positions alone: the padding reaches no expert.

    ``riders`` is a decode step over the whole pool, ``(token [S],
    pos [S], tables [S, M])`` as ``paged_decode_step`` takes them, that
    rides in this program (``chunk_carries_riders``): its rows are a
    second part of every layer (``_attention_layer``), so the weights
    are read once for both.  The result is then a 4-tuple, the riders'
    logits ``[S, vocab]`` f32 last: what the chunk alone followed by
    ``paged_decode_step`` gives.

    ``ring_pages`` (static; serve/paging.py RowLayout): where the
    pattern has window layers, the first ``ring_pages`` entries of
    ``table`` are the row's ring in the window layers' arena and the
    rest the full layers' history.
    """
    b, c = tokens.shape
    if b != 1:
        raise ValueError(f"prefill chunks are per-request, got batch {b}")
    if riders is not None and not chunk_carries_riders(config):
        raise NotImplementedError(
            f"no layer of attention {config.attention!r} runs a decode "
            "step's rows beside a chunk's positions"
        )
    start = jnp.asarray(start, jnp.int32)
    true_len = jnp.asarray(true_len, jnp.int32)
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = start + offs                     # [c] virtual positions
    live = offs < true_len
    if config.attention == "eva":
        parts = {"attention": [_eva_chunk_part(
            config, cache, table, start, true_len, offs, positions, live
        )]}
    else:
        ring = _ring_pages(config, ring_pages)
        rows = (
            positions, positions % cache["k"].shape[2], live,
            start + true_len,
        )
        parts = {"attention": [_full_chunk_part(
            config, cache, table[ring:] if ring else table, *rows
        )]}
        if ring:
            parts["sliding"] = [
                _window_chunk_part(config, cache, table[:ring], *rows)
            ]
    x = _embed(config, params, tokens)
    if riders is not None:
        token, pos, tables = riders
        pos = jnp.asarray(pos, jnp.int32)
        step, _live = _step_parts(config, cache, pos, tables, ring_pages)
        parts = {kind: parts[kind] + step[kind] for kind in parts}
        x = jnp.concatenate([x, _embed(config, params, token)[None]], axis=1)
        positions = jnp.concatenate([positions, pos])
    x, new_cache, counts = _serving_trunk(
        config, params, cache, x, positions[None], parts,
        _conv_chunk_operator(
            config, start, true_len, jnp.asarray(slot, jnp.int32)
        ), live,
    )
    with jax.named_scope("logits"):
        last = functools.partial(
            lax.dynamic_index_in_dim, index=true_len - 1, axis=1,
            keepdims=False,
        )
        if chunk_carries_riders(config):
            # where riders may ride, the rows anybody reads are picked
            # before the norm: the chunk's last real position, the riders
            rows = last(x)
            if riders is not None:
                rows = jnp.concatenate([rows, x[0, c:]])
            rows = _norm(config, rows, params["final_norm"])
        else:
            rows = last(_norm(config, x, params["final_norm"]))
        logits = _last_logits(config, params, rows)
    if riders is None:
        return logits, new_cache, counts
    return logits[:1], new_cache, counts, logits[1:]


def paged_decode_step(
    config: TransformerConfig,
    params: Params,
    cache: Dict[str, jax.Array],
    token: jax.Array,
    pos: jax.Array,
    tables: jax.Array,
    ring_pages: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[jax.Array]]:
    """One autoregressive step over the whole pool, KV indirected
    through per-row page tables: ``token [S]`` at per-row positions
    ``pos [S]``, ``tables [S, M]`` mapping each row's virtual pages to
    arena pages -> (logits [S, vocab] f32, updated cache, counts).

    Each attention layer writes the row's new entry through its table
    and attends through it (``_full_step_part``, ``_window_step_part``,
    ``_eva_step_part``).  A row whose table is all zeros holds no live
    request: it writes into the trash page, reaches no expert of a
    mixture and leaves its slot's conv state as it was (the slot may
    belong to a row that is still prefilling).  ``counts`` is int32
    ``[2]``: the live (token, expert) assignments the step's mixtures
    routed and the expert groups that held at least one, summed over
    the expert layers; None where the model routes nothing.
    ``ring_pages`` as ``paged_prefill_chunk`` takes it."""
    pos = jnp.asarray(pos, jnp.int32)
    parts, live = _step_parts(config, cache, pos, tables, ring_pages)
    x, new_cache, counts = _serving_trunk(
        config, params, cache, _embed(config, params, token)[:, None, :],
        pos[:, None], parts, _conv_step_operator(config, live), live,
    )
    with jax.named_scope("logits"):
        x = _norm(config, x, params["final_norm"])
        logits = _last_logits(config, params, x[:, 0])
    return logits, new_cache, counts


def decode_attention_kernel(config: TransformerConfig, cache):
    """How a paged decode step's attention runs: ``"compiled"`` (the
    page walk of ops/paged_decode.py, which reads each row's live pages
    in place from the arena) or ``None`` (gather every row's whole
    table into a dense buffer, then a masked softmax over all of it).

    A rule over what the code can observe, as ``ops.rmsnorm`` has one;
    tests patch it to ``"interpret"``.  The gather path stays where the
    kernel cannot run or would not compute the same thing: off a TPU;
    over a quantized arena (the kernel takes no scales); for an EVA
    model whose heads are grouped; and under an ambient mesh of more
    than one device (``PagedPoolModel`` enters the mesh its arena is
    laid over, the serving gang's tp mesh), because a ``pallas_call``
    under a multi-device jit raises unless it is wrapped per shard
    (parallel/mesh.py ``per_shard``), which this call is not.  The
    kernel copies whole pages, so a TPU's arena keeps its entries in
    whole 128-lane rows (``arena_lanes``, which the pool applies): over
    an arena built narrower the kernel does not compile."""
    if "k_scale" in cache:
        return None
    if config.attention == "eva" and config.n_kv_heads != config.n_heads:
        return None
    if jax.sharding.get_abstract_mesh().size > 1:
        return None
    return "compiled" if jax.default_backend() == "tpu" else None


def decode_attention_step(config: TransformerConfig, cache) -> dict:
    """What one step of the page walk does under each kernel name the
    decode program holds (``ops/paged_decode.py walk_step``: the pages
    a step takes and the form of its products, both chosen from the
    arena's shapes); empty where the step takes the gather path."""
    from dcos_commons_tpu.ops.paged_decode import walk_step

    if not decode_attention_kernel(config, cache):
        return {}
    if config.attention == "eva":
        return {"eva_decode_attention": walk_step(cache["k"])}
    steps = {}
    if config.n_layers_of("attention"):
        steps["paged_decode_attention"] = walk_step(cache["k"])
    if config.n_layers_of("sliding"):
        steps["paged_decode_attention_window"] = walk_step(cache["k_window"])
    return steps


def generate(
    config: TransformerConfig,
    params: Params,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature=0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    true_len: Optional[jax.Array] = None,
    kv_dtype: str = "native",
) -> jax.Array:
    """Autoregressive continuation: prompt [b, s] -> tokens
    [b, max_new_tokens].  temperature 0 = greedy; otherwise softmax
    sampling with ``key``.  Jit-friendly end to end, ONE compile
    covering every prompt CONTENT, LENGTH (``true_len``: right-padded
    prompts, traced — a scalar, or a [b] vector for MIXED per-row
    lengths so one dispatch serves heterogeneous requests), and
    TEMPERATURE (traced operand — a server must not recompile per
    requested temperature).

    ``kv_dtype="int8"`` stores the cache quantized per vector:
    decode streams half the cache bytes per step, roughly doubling
    the HBM-bound throughput ceiling, at ~0.4%/element quantization
    error (tests/test_decode.py holds logits agreement)."""
    b, s = prompt.shape
    total = max_len if max_len is not None else s + max_new_tokens
    if total < s + max_new_tokens:
        # dynamic_update_slice CLAMPS out-of-range writes, which would
        # silently corrupt the last cache slot instead of failing
        raise ValueError(
            f"max_len {total} cannot hold prompt {s} + "
            f"{max_new_tokens} new tokens"
        )
    if key is None:
        from jax.core import Tracer

        if isinstance(temperature, Tracer):
            # a TRACED temperature could be > 0 at runtime; silently
            # "sampling" with a fixed default key would look stochastic
            # while returning identical tokens every call
            raise ValueError("a traced temperature needs a PRNG key")
        if float(temperature) > 0.0:  # concrete scalars/arrays coerce
            raise ValueError("sampling (temperature > 0) needs a PRNG key")
    logits, cache = prefill(
        config, params, prompt, total, true_len, kv_dtype=kv_dtype
    )
    key = key if key is not None else jax.random.key(0)
    temp = jnp.asarray(temperature, jnp.float32)
    # split once up front: the prefill pick and the scan step keys must
    # be derived from DISTINCT keys, or the first sampled token's
    # randomness correlates with the step keys (PRNG key reuse)
    first_key, rest_key = jax.random.split(key)
    first = sample_token(logits, temp, first_key)
    start = (
        jnp.asarray(true_len, jnp.int32) if true_len is not None
        else jnp.int32(s)
    )

    def step(carry, step_key):
        token, pos, cache = carry
        logits, cache = decode_step(config, params, cache, token, pos)
        nxt = sample_token(logits, temp, step_key)
        return (nxt, pos + 1, cache), token

    keys = jax.random.split(rest_key, max_new_tokens)
    (_, _, _), out = lax.scan(
        step,
        (first, start, cache),
        keys,
        length=max_new_tokens,
    )
    return out.swapaxes(0, 1)  # [b, max_new_tokens]
