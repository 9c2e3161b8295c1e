"""Blocked flash attention for one device — forward AND backward.

MXU-first design (pallas_guide.md): Q blocks stream through a grid of
(batch*heads, q_blocks); K/V live in VMEM per grid cell and the kernel
walks K blocks with an online-softmax accumulator, so the [S, S] score
matrix never materializes in HBM.  bf16 in, f32 accumulation,
``preferred_element_type`` on every dot.

The backward pass is the FlashAttention-2 recurrence in two kernels:
a dq kernel gridded like the forward (stream K blocks per Q block) and
a dk/dv kernel gridded over K blocks (stream Q blocks), both driven by
the logsumexp residual the forward saves per row.  The residual rides
in a [rows, 128] tile (value replicated across the minor dim) because
Mosaic wants lane-width minor dimensions.

For sequences sharded across devices use
dcos_commons_tpu.parallel.ring.ring_attention, which applies the same
accumulation across ring hops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30
_LANES = 128  # residual tile minor dim (Mosaic layout requirement)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool):
    from jax.experimental import pallas as pl

    q_index = pl.program_id(1)
    block_q = q_ref.shape[0]
    head_dim = q_ref.shape[1]
    seq_k = k_ref.shape[0]
    scale = head_dim ** -0.5

    # MXU rate note: operands stay in the INPUT dtype (bf16) with f32
    # accumulation — casting q/k/v to f32 before the dots would run the
    # systolic array at the f32 rate, half the bf16 rate.  The scale is
    # applied to the f32 scores, not the bf16 operands, so no precision
    # is lost.
    q = q_ref[:]
    m = jnp.full((block_q, 1), _NEG, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, head_dim), jnp.float32)

    q_pos = q_index * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_off = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def make_body(masked: bool):
        def body(j, carry):
            m, l, acc = carry
            from jax.experimental import pallas as pl  # noqa: trace-local

            k = k_ref[pl.ds(j * block_k, block_k), :]
            v = v_ref[pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                valid = q_pos >= (j * block_k + k_off)
                s = jnp.where(valid, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            # p in [0,1] downcast to the value dtype for the MXU; the
            # f32 accumulator keeps the summation exact
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        return body

    # single loop with in-body masking measured FASTER than splitting
    # into an unmasked phase + a diagonal phase (two fori_loops cost
    # more than the mask VPU ops they save); K blocks fully in the
    # future are still skipped via the loop bound
    if causal:
        n_blocks = jnp.minimum(
            pl.cdiv((q_index + 1) * block_q, block_k), seq_k // block_k
        )
    else:
        n_blocks = seq_k // block_k
    m, l, acc = lax.fori_loop(
        0, n_blocks, make_body(masked=causal), (m, l, acc)
    )
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        lse = m + jnp.log(l)
        lse_ref[:] = jnp.broadcast_to(lse, (block_q, _LANES))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, *,
               block_k: int, causal: bool):
    """dq for one Q block: stream K blocks (FA2 eq.: ds = p*(dp - di),
    dq = scale * ds @ k)."""
    from jax.experimental import pallas as pl

    q_index = pl.program_id(1)
    block_q = q_ref.shape[0]
    head_dim = q_ref.shape[1]
    seq_k = k_ref.shape[0]
    scale = head_dim ** -0.5

    # bf16 operands + f32 accumulation throughout (see _fwd_kernel's
    # MXU rate note); the score scale is applied to f32 s, and ds is
    # downcast for its MXU dot — ds = p*(dp-di) with p in [0,1]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:, :1]
    di = di_ref[:, :1]
    acc = jnp.zeros((block_q, head_dim), jnp.float32)

    q_pos = q_index * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_off = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def make_body(masked: bool):
        def body(j, acc):
            from jax.experimental import pallas as pl  # noqa: trace-local

            k = k_ref[pl.ds(j * block_k, block_k), :]
            v = v_ref[pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                valid = q_pos >= (j * block_k + k_off)
                s = jnp.where(valid, s, _NEG)
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(valid, p, 0.0)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - di)
            return acc + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        return body

    if causal:
        n_blocks = jnp.minimum(
            pl.cdiv((q_index + 1) * block_q, block_k), seq_k // block_k
        )
    else:
        n_blocks = seq_k // block_k
    acc = lax.fori_loop(0, n_blocks, make_body(masked=causal), acc)
    dq_ref[:] = (acc * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, *, block_q: int, causal: bool):
    """dk/dv for one K block: stream Q blocks (dv = p^T @ do,
    dk = scale * ds^T @ q)."""
    from jax.experimental import pallas as pl

    k_index = pl.program_id(1)
    block_k = k_ref.shape[0]
    head_dim = k_ref.shape[1]
    seq_q = q_ref.shape[0]
    scale = head_dim ** -0.5

    # bf16 operands + f32 accumulation (see _fwd_kernel's MXU rate
    # note).  q is streamed UNSCALED so its bf16 bits are the caller's;
    # the scale lands once on f32 s and once on the final dk.
    k = k_ref[:]
    v = v_ref[:]
    dk = jnp.zeros((block_k, head_dim), jnp.float32)
    dv = jnp.zeros((block_k, head_dim), jnp.float32)

    k_pos = k_index * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    q_off = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def make_body(masked: bool):
        def body(i, carry):
            dk, dv = carry
            from jax.experimental import pallas as pl  # noqa: trace-local

            q = q_ref[pl.ds(i * block_q, block_q), :]
            do = do_ref[pl.ds(i * block_q, block_q), :]
            lse = lse_ref[pl.ds(i * block_q, block_q), :1]
            di = di_ref[pl.ds(i * block_q, block_q), :1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                valid = (i * block_q + q_off) >= k_pos
                s = jnp.where(valid, s, _NEG)
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(valid, p, 0.0)
            dv_new = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - di)
            dk_new = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return dk_new, dv_new

        return body

    if causal:
        # Q blocks strictly before this K block see none of it
        i_start = (k_index * block_k) // block_q
    else:
        i_start = 0
    dk, dv = lax.fori_loop(
        i_start, seq_q // block_q, make_body(masked=causal), (dk, dv)
    )
    # q was streamed unscaled, so dk takes the single scale factor here
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret",
                     "save_residuals"),
)
def _pallas_attention(q, k, v, causal, block_q, block_k, interpret,
                      save_residuals=False):
    from jax.experimental import pallas as pl

    batch, heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    qr = q.reshape(bh, seq_q, head_dim)
    kr = k.reshape(bh, seq_k, head_dim)
    vr = v.reshape(bh, seq_k, head_dim)
    grid = (bh, seq_q // block_q)
    out_shape = [jax.ShapeDtypeStruct(qr.shape, q.dtype)]
    out_specs = [
        pl.BlockSpec((None, block_q, head_dim), lambda b, i: (b, i, 0))
    ]
    if save_residuals:
        out_shape.append(
            jax.ShapeDtypeStruct((bh, seq_q, _LANES), jnp.float32)
        )
        out_specs.append(
            pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, 0))
        )
        kernel = functools.partial(
            _fwd_kernel, block_k=block_k, causal=causal
        )
    else:
        kernel = functools.partial(
            lambda *refs, **kw: _fwd_kernel(*refs, None, **kw),
            block_k=block_k, causal=causal,
        )
    result = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, seq_k, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, seq_k, head_dim), lambda b, i: (b, 0, 0)),
        ],
        out_specs=out_specs,
        interpret=interpret,
        name="flash_attention_fwd",
    )(qr, kr, vr)
    out = result[0].reshape(batch, heads, seq_q, head_dim)
    if save_residuals:
        return out, result[1]
    return out


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def _pallas_attention_bwd(q, k, v, o, lse, do, causal, block_q, block_k,
                          interpret):
    from jax.experimental import pallas as pl

    batch, heads, seq_q, head_dim = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    qr = q.reshape(bh, seq_q, head_dim)
    kr = k.reshape(bh, seq_k, head_dim)
    vr = v.reshape(bh, seq_k, head_dim)
    dor = do.reshape(bh, seq_q, head_dim)
    # di = rowsum(do * o): cheap elementwise reduce, then lane-tiled to
    # match the residual layout
    di = jnp.sum(
        dor.astype(jnp.float32)
        * o.reshape(bh, seq_q, head_dim).astype(jnp.float32),
        axis=-1,
    )
    di = jnp.broadcast_to(di[..., None], (bh, seq_q, _LANES))

    row_spec = pl.BlockSpec((None, block_q, head_dim), lambda b, i: (b, i, 0))
    lane_spec = pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, 0))
    full = lambda seq: pl.BlockSpec(
        (None, seq, head_dim), lambda b, i: (b, 0, 0)
    )
    full_lanes = pl.BlockSpec((None, seq_q, _LANES), lambda b, i: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        grid=(bh, seq_q // block_q),
        in_specs=[row_spec, full(seq_k), full(seq_k), row_spec, lane_spec,
                  lane_spec],
        out_specs=row_spec,
        interpret=interpret,
        name="flash_attention_dq",
    )(qr, kr, vr, dor, lse, di)

    kcol_spec = pl.BlockSpec((None, block_k, head_dim), lambda b, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal),
        out_shape=[
            jax.ShapeDtypeStruct(kr.shape, k.dtype),
            jax.ShapeDtypeStruct(vr.shape, v.dtype),
        ],
        grid=(bh, seq_k // block_k),
        in_specs=[kcol_spec, kcol_spec, full(seq_q), full(seq_q),
                  full_lanes, full_lanes],
        out_specs=[kcol_spec, kcol_spec],
        interpret=interpret,
        name="flash_attention_dkv",
    )(kr, vr, qr, dor, lse, di)

    shape = (batch, heads, seq_q, head_dim)
    kshape = (batch, heads, seq_k, head_dim)
    return dq.reshape(shape), dk.reshape(kshape), dv.reshape(kshape)


def _dispatch_pallas(q, k, block_q, block_k, force_pallas, interpret) -> bool:
    """Single source of truth for the kernel-vs-reference choice: the
    primal and the residual-saving forward must always agree."""
    seq_q, seq_k = q.shape[2], k.shape[2]
    use_pallas = force_pallas or interpret or jax.default_backend() == "tpu"
    tiles = seq_q % block_q == 0 and seq_k % block_k == 0
    return use_pallas and tiles


def _impl(q, k, v, causal, block_q, block_k, force_pallas, interpret):
    if _dispatch_pallas(q, k, block_q, block_k, force_pallas, interpret):
        return _pallas_attention(q, k, v, causal, block_q, block_k, interpret)
    from dcos_commons_tpu.parallel.ring import reference_attention

    return reference_attention(q, k, v, causal)


@functools.lru_cache(maxsize=None)
def _make_attention(causal, block_q, block_k, force_pallas, interpret):
    """Per-config differentiable attention: Pallas forward AND backward
    (FlashAttention-2 two-kernel recurrence over the saved logsumexp).
    Shapes that don't tile fall back to the dense reference both ways.
    """
    from dcos_commons_tpu.parallel.ring import reference_attention

    @jax.custom_vjp
    def attn(q, k, v):
        return _impl(q, k, v, causal, block_q, block_k, force_pallas, interpret)

    def fwd(q, k, v):
        if _dispatch_pallas(q, k, block_q, block_k, force_pallas, interpret):
            o, lse = _pallas_attention(
                q, k, v, causal, block_q, block_k, interpret,
                save_residuals=True,
            )
            return o, (q, k, v, o, lse)
        return attn(q, k, v), (q, k, v, None, None)

    def bwd(residuals, g):
        q, k, v, o, lse = residuals
        if lse is not None:
            return _pallas_attention_bwd(
                q, k, v, o, lse, g, causal, block_q, block_k, interpret
            )
        _, vjp = jax.vjp(
            lambda q_, k_, v_: reference_attention(q_, k_, v_, causal), q, k, v
        )
        return vjp(g)

    attn.defvjp(fwd, bwd)
    return attn


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """[batch, heads, seq, head_dim] attention, differentiable.

    Dispatch (a shape rule, see :func:`_dispatch_pallas`): the Pallas
    kernels on TPU (or when forced / interpreted for tests) when both
    sequence lengths tile by the block sizes; the jnp reference
    otherwise.  Which branch a compiled program took is visible in its
    text: the kernels are ``tpu_custom_call``s named
    ``flash_attention_{fwd,dq,dkv}`` (ops/introspect.py).

    Under a mesh (``parallel.mesh.per_shard``) the kernels run on each
    device's [batch, heads] shard: a ``pallas_call`` has no GSPMD
    partitioning rule, and left to itself XLA all-gathers q/k/v and
    runs the full global batch on every chip.
    """
    from jax.sharding import PartitionSpec as P

    from dcos_commons_tpu.parallel.mesh import (
        BATCH_AXES,
        ambient_axes,
        per_shard,
    )

    attn = _make_attention(causal, block_q, block_k, force_pallas, interpret)
    if _dispatch_pallas(q, k, block_q, block_k, force_pallas, interpret):
        spec = P(
            ambient_axes(q.shape[0], BATCH_AXES),
            ambient_axes(q.shape[1], ("tp",)),
            None, None,
        )
        attn = per_shard(attn, in_specs=(spec, spec, spec), out_specs=spec)
    return attn(q, k, v)
