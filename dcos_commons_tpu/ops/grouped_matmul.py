"""Grouped matmul of a serving mixture: sorted rows against the experts
they chose, read in place from the stacked expert weights.

``rows [m, k]`` are a step's assignments sorted by expert; expert ``g``
of the layer owns the ``group_sizes[g]`` rows behind those of the
experts before it, and rows past the last group belong to nobody.
``stack [n * E, k, n_out]`` holds EVERY expert layer's experts on one
leading axis (a reshape of the stored ``[n, E, k, n_out]`` that moves
no byte) and ``first`` is the layer's first expert there: a layer's
slice is never cut out of the stack, which in front of a custom call
would be a copy of the whole layer's experts.  The kernel (the Pallas
``gmm`` of ``jax.experimental.pallas.ops.tpu.megablox``) visits the
groups that hold rows and no other, so the weights read follow the
experts touched.

Off a TPU, and under an ambient mesh of more than one device (a
``pallas_call`` under a multi-device jit raises unless wrapped per
shard), ``jax.lax.ragged_dot`` computes the same contract; tests patch
``grouped_matmul_kernel`` to ``"interpret"``, as they do
``models/decode.py decode_attention_kernel``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# the rows of one m-tile: a step's assignments are padded up to a whole
# number of them
ROW_TILE = 128


def grouped_matmul_kernel():
    """``"compiled"`` where the Pallas kernel runs, else ``None``."""
    if jax.sharding.get_abstract_mesh().size > 1:
        return None
    return "compiled" if jax.default_backend() == "tpu" else None


def _tiling(k: int, n: int):
    """(tk, tn) for a product bound by the bytes of its weights: the whole
    contraction (or 2048 of it) by 512 output columns a step, 2 MiB of
    weights in flight twice over; 1024 columns where the output is wide
    (measured on a v5e, PERF.md section 6, PR 33: at ``[256, 2048] x
    [64, 2048, 1536]`` 512 columns took 0.304 ms against 0.320 at 1024;
    at ``[128, 4096] x [8, 4096, 14336]`` 1.59 against 1.44).  ``k`` and
    ``n`` may leave a remainder, which the kernel masks."""
    return min(k, 2048), 1024 if n >= 4096 else min(n, 512)


def grouped_matmul(rows: jax.Array, stack: jax.Array, group_sizes: jax.Array,
                   first) -> jax.Array:
    """``out[i] = rows[i] @ stack[first + g]`` for row ``i`` of group
    ``g``; rows past the last group come back as zeros.  ``[m, n_out]``
    in ``rows``' dtype, accumulated in float32."""
    kernel = grouped_matmul_kernel()
    m = rows.shape[0]
    n_groups = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    if not kernel:
        experts = lax.dynamic_slice_in_dim(stack, first, n_groups, axis=0)
        return lax.ragged_dot(
            rows, experts.astype(rows.dtype), group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(rows.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    tile = min(m, ROW_TILE)
    padded = -(-m // tile) * tile
    lhs = jnp.pad(rows, ((0, padded - m), (0, 0))) if padded != m else rows
    # the layer's groups at their place among all the stack's groups:
    # every other group is empty and is never visited
    sizes = lax.dynamic_update_slice_in_dim(
        jnp.zeros(stack.shape[0], jnp.int32), group_sizes, first, axis=0
    )
    out = gmm(
        lhs, stack, sizes, preferred_element_type=rows.dtype,
        tiling=(tile,) + _tiling(stack.shape[1], stack.shape[2]),
        interpret=kernel == "interpret",
    )[:m]
    # what no group owns was never written
    owned = jnp.arange(m, dtype=jnp.int32) < group_sizes.sum()
    return jnp.where(owned[:, None], out, 0)
