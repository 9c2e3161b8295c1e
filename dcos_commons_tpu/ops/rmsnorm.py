"""Fused RMSNorm kernel.

One VMEM round-trip instead of XLA's usual norm decomposition; rows
stream through the grid in (block_rows, d_model) tiles (VPU work, no
MXU).  f32 statistics regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (normed * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _pallas_rms_norm(x, w, eps, block_rows, interpret):
    from jax.experimental import pallas as pl

    rows, d = x.shape
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=interpret,
        name="rms_norm_fwd",
    )(x, w)


def _reference(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _make_rms_norm(eps, block_rows, force_pallas, interpret):
    """Differentiable: Pallas forward, backward via the reference VJP
    (the recompute is one fused elementwise pass — cheap)."""

    @jax.custom_vjp
    def norm(x, w):
        rows = x.shape[0]
        use_pallas = (
            force_pallas or interpret or jax.default_backend() == "tpu"
        )
        if use_pallas and rows % block_rows == 0:
            return _pallas_rms_norm(x, w, eps, block_rows, interpret)
        return _reference(x, w, eps)

    def fwd(x, w):
        return norm(x, w), (x, w)

    def bwd(residuals, g):
        x, w = residuals
        _, vjp = jax.vjp(lambda x_, w_: _reference(x_, w_, eps), x, w)
        return vjp(g)

    norm.defvjp(fwd, bwd)
    return norm


def rms_norm(
    x: jax.Array,
    w: jax.Array,
    eps: float = 1e-6,
    block_rows: int = 256,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """RMSNorm over the last axis; any leading shape. Differentiable.

    Dispatch (a shape rule): the Pallas kernel on TPU (or when forced /
    interpreted) when the row count tiles by ``block_rows``, the jnp
    reference otherwise; the kernel is the ``tpu_custom_call`` named
    ``rms_norm_fwd``.  Under a mesh the leading (batch) axis stays
    sharded and each device norms its own rows (see
    ``ops.attention.flash_attention`` for why)."""
    from jax.sharding import PartitionSpec as P

    from dcos_commons_tpu.parallel.mesh import (
        BATCH_AXES,
        ambient_axes,
        per_shard,
    )

    norm = _make_rms_norm(eps, block_rows, force_pallas, interpret)

    def rows(x, w):
        return norm(x.reshape(-1, x.shape[-1]), w).reshape(x.shape)

    if x.ndim > 1:
        spec = P(
            ambient_axes(x.shape[0], BATCH_AXES), *[None] * (x.ndim - 1)
        )
        rows = per_shard(rows, in_specs=(spec, P()), out_specs=spec)
    return rows(x, w)
