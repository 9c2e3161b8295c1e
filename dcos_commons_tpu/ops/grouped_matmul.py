"""Grouped matmul of a serving mixture: a dispatch plan worked out once
an expert layer, and the product of the sorted rows against the experts
they chose, read in place from the stacked expert weights.

**The plan** (``dispatch_plan``) is everything the layer's three
products share, from the step's choices ``expert_idx [t, k]`` and
``live [t]``: each assignment's place among the rows sorted by expert
(``back``: its group's offset + its rank inside the group, in the
assignments' own order, a dead row's behind every group), the token
each sorted row comes from (``src``, the inverse of ``back``, padded to
whole row tiles), the groups' sizes and what the layer counts, and the
kernel's tile metadata over the LAYER's ``E`` groups.  All of it is
COUNTED, in a handful of fused compare-and-sums: no sort of the keys,
no second sort to invert the first, no histogram, no cumulative-sum
operator.  How the ranks are counted follows ``t * k``, a shape: blocks
of ``COUNT_BLOCK`` assignments compared among themselves and the blocks
before them by their counts, so a decode step's 128-256 assignments are
one block and a chunk's 4,096 cost 4,096 x 256 compares, not 4,096
squared; the inverse is one more compare-and-sum up to
``INVERT_BY_COUNTING`` assignments and ONE sort (of ``back``, a
permutation) above.

**The product** (``grouped_matmul``): ``rows [m, k]`` are the plan's
sorted rows; expert ``g`` of the layer owns the ``group_sizes[g]`` rows
behind those of the experts before it, and rows past the last group
belong to nobody: the kernel never writes them, and the caller leaves
them out (models/moe.py ``moe_serve_ffn`` gives a dead row no weight).
``stack [n * E, k, n_out]`` holds EVERY expert layer's experts on one
leading axis (a reshape of the stored ``[n, E, k, n_out]`` that moves
no byte) and ``first`` is the layer's first expert there, a scalar the
weights' index map adds: a layer's slice is never cut out of the stack,
which in front of a custom call would be a copy of the whole layer's
experts.  The kernel is the repo's own: the forward ``gmm`` of
``jax.experimental.pallas.ops.tpu.megablox`` (its body, its index maps,
its operation name), forward only, taking the plan's metadata where
megablox derived its own from the group sizes in every call (two
``repeat``s, a histogram, two rolls over the stack's ``n * E`` groups:
68 operations a call at 512 groups).  It visits the groups that hold
rows and no other, so the weights read follow the experts touched.

Off a TPU, and under an ambient mesh of more than one device (a
``pallas_call`` under a multi-device jit raises unless wrapped per
shard), ``jax.lax.ragged_dot`` computes the same contract from the same
plan's ``group_sizes``, which is why it stays; tests patch
``grouped_matmul_kernel`` to ``"interpret"``, as they do
``models/decode.py decode_attention_kernel``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the rows of one m-tile: a step's assignments are padded up to a whole
# number of them
ROW_TILE = 128
# the assignments whose ranks are counted against one another; those of
# the blocks before them count by block
COUNT_BLOCK = 256
# up to this many assignments ``src`` is ``back`` inverted by one
# compare-and-sum (m x t*k compares); above, by one sort
INVERT_BY_COUNTING = 512


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


class TilePlan(NamedTuple):
    """The kernel's visits: ``group_offsets [E + 1]`` (group ``g`` owns
    rows ``[g] .. [g + 1]``); for visit ``v`` of at most ``tiles_m + E
    - 1`` its group ``group_ids[v]`` and its row tile ``m_tile_ids[v]``
    (a tile that two groups share is visited once by each, one after
    the other); ``num_tiles``, the visits there are."""

    group_offsets: jax.Array
    group_ids: jax.Array
    m_tile_ids: jax.Array
    num_tiles: jax.Array


class DispatchPlan(NamedTuple):
    """What ``dispatch_plan`` works out (module docstring).  ``tiles``
    is None where ``ragged_dot`` computes the products."""

    src: jax.Array              # int32 [m]: the token of each sorted row
    back: jax.Array             # int32 [t * k]: an assignment's sorted row
    group_sizes: jax.Array      # int32 [E]
    counts: jax.Array           # int32 [2]: live assignments, groups touched
    tiles: Optional[TilePlan]


def _count(where, axis):
    return jnp.sum(where, axis=axis, dtype=jnp.int32)


def _tile_plan(offsets: jax.Array, sizes: jax.Array, tile: int,
               tiles_m: int) -> TilePlan:
    """The visits of ``E`` groups at ``offsets [E + 1]`` over row tiles
    of ``tile``: group ``g`` visits every tile its rows touch, an empty
    group none; the visits are numbered group by group."""
    e = sizes.shape[0]
    groups = jnp.arange(e, dtype=jnp.int32)
    first_tile = offsets[:-1] // tile
    visits = jnp.where(
        sizes > 0, (offsets[1:] + tile - 1) // tile - first_tile, 0
    )
    # the visits of the groups up to and with g
    through = _count(jnp.where(
        groups[None, :] <= groups[:, None], visits[None, :], 0
    ), 1)
    visit = jnp.arange(tiles_m + e - 1, dtype=jnp.int32)
    # the groups whose visits end at or before this one; the last group
    # is left out, so past the last visit the id stays in its range
    group_ids = _count(through[None, :-1] <= visit[:, None], 1)
    # a group's n-th visit is its first tile + n; past the last visit
    # the last tile at most
    lead = first_tile - (through - visits)
    m_tile_ids = _count(jnp.where(
        group_ids[:, None] == groups[None, :],
        jnp.minimum(lead[None, :] + visit[:, None], tiles_m - 1), 0,
    ), 1)
    return TilePlan(offsets, group_ids, m_tile_ids, through[-1])


def dispatch_plan(expert_idx: jax.Array, live: Optional[jax.Array],
                  n_experts: int) -> DispatchPlan:
    """The plan of one expert layer's step: ``expert_idx [t, k]`` are
    the tokens' choices among ``n_experts``, ``live [t]`` (all, where
    None) the tokens that stand for something.  The order inside a group
    is the assignments' own (token by token, choice by choice), as a
    stable sort by expert gives it."""
    t, k = expert_idx.shape
    a, e = t * k, n_experts
    kernel = grouped_matmul_kernel()
    keys = expert_idx.astype(jnp.int32)
    if live is not None:
        # a dead row's assignments: behind every group
        keys = jnp.where(live[:, None], keys, e)
    block = min(a, COUNT_BLOCK)
    n_blocks = -(-a // block)
    keys = keys.reshape(-1)
    if n_blocks * block != a:
        keys = jnp.pad(keys, (0, n_blocks * block - a), constant_values=e)
    keys = keys.reshape(n_blocks, block)
    groups = jnp.arange(e + 2, dtype=jnp.int32)
    # a block's assignments in the groups before g, the dead as group E
    below = _count(keys[:, None, :] < groups[None, :, None], 2)
    offsets = (below.sum(0) if n_blocks > 1 else below[0])[:e + 1]
    # the assignments of its own block that sort before each: those of
    # an earlier group, and of its own group the earlier ones
    place = jnp.arange(block, dtype=jnp.int32)
    back = _count(
        (keys[:, None, :] < keys[:, :, None])
        | ((keys[:, None, :] == keys[:, :, None])
           & (place[None, :] < place[:, None])[None]), 2,
    )
    if n_blocks > 1:
        # and those of the other blocks: every block's of an earlier
        # group, the earlier blocks' of its own
        blocks = jnp.arange(n_blocks, dtype=jnp.int32)
        earlier = _count(jnp.where(
            (blocks[None, :] < blocks[:, None])[:, :, None],
            below[None, :, :], 0,
        ), 1)
        ahead = (
            offsets[None, :] - below[:, :e + 1]
            + earlier[:, 1:] - earlier[:, :e + 1]
        )
        back = back + _count(jnp.where(
            keys[:, :, None] == groups[None, None, :e + 1],
            ahead[:, None, :], 0,
        ), 2)
    back = back.reshape(-1)[:a]
    tile = min(a, ROW_TILE)
    m = -(-a // tile) * tile if kernel else a
    token = jnp.arange(a, dtype=jnp.int32) // k
    if a <= INVERT_BY_COUNTING:
        src = _count(jnp.where(
            back[None, :] == jnp.arange(m, dtype=jnp.int32)[:, None],
            token[None, :], 0,
        ), 1)
    else:
        _, src = lax.sort_key_val(back, token, is_stable=False)
        if m != a:
            src = jnp.pad(src, (0, m - a))
    group_sizes = offsets[1:] - offsets[:-1]
    counts = jnp.stack([offsets[e], _count(group_sizes > 0, 0)])
    return DispatchPlan(
        src, back, group_sizes, counts,
        _tile_plan(offsets, group_sizes, tile, m // tile) if kernel else None,
    )


def _gmm_kernel(group_offsets, group_ids, m_tile_ids, first, lhs, rhs, out,
                acc, *, tm, tn, tiles_k, k_rem, input_dtype):
    """One (n tile, visit, k tile) step of megablox's forward ``gmm``:
    the tile's product accumulated in float32 over the k tiles, and at
    the last the rows of the visit's group stored, the other rows of a
    shared tile left as they are."""
    del first
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero_acc():
        acc[...] = jnp.zeros_like(acc)

    def mask_k_rem(x, *, dim):
        if k_rem == 0:
            return x
        orig_dtype = x.dtype
        iota = lax.broadcasted_iota(jnp.int32, x.shape, dim)
        x = x.astype(jnp.float32)
        return jnp.where(iota < k_rem, x, 0).astype(orig_dtype)

    def _store_accum():
        group = group_ids[visit]
        rows = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + (
            m_tile_ids[visit] * tm
        )
        mask = jnp.logical_and(
            rows >= group_offsets[group], rows < group_offsets[group + 1]
        )
        out[...] = lax.select(
            mask, acc[...], out[...].astype(jnp.float32)
        ).astype(out.dtype)

    def _accum(is_last_k_tile):
        if is_last_k_tile:
            mask_lhs = functools.partial(mask_k_rem, dim=1)
            mask_rhs = functools.partial(mask_k_rem, dim=0)
        else:
            mask_lhs = mask_rhs = lambda x: x
        acc[...] += lax.dot_general(
            mask_lhs(lhs[...]).astype(input_dtype),
            mask_rhs(rhs[...]).astype(input_dtype),
            preferred_element_type=jnp.float32,
            dimension_numbers=(((1,), (0,)), ((), ())),
        )
        if is_last_k_tile:
            _store_accum()

    lax.cond(
        k_i == tiles_k - 1,
        functools.partial(_accum, True),
        functools.partial(_accum, False),
    )


def _gmm(lhs: jax.Array, stack: jax.Array, tiles: TilePlan, first,
         interpret: bool) -> jax.Array:
    """``lhs [m, k]`` (whole row tiles) against the groups' experts in
    ``stack``; a row no group owns is not written."""
    m, k = lhs.shape
    n = stack.shape[2]
    tm = min(m, ROW_TILE)
    tk, tn = _tiling(k, n)
    tiles_k, k_rem = -(-k // tk), k % tk
    tiles_n = -(-n // tn)
    both_bf16 = lhs.dtype == stack.dtype == jnp.bfloat16
    visits = tiles.group_ids.shape[0]
    # as megablox reckons: the rows once an n tile, a group's weights a
    # visit (not all of the stack is read)
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=(
            lhs.size * lhs.itemsize * tiles_n
            + k * n * stack.itemsize * visits + m * n * lhs.itemsize
        ),
    )
    return pl.pallas_call(
        functools.partial(
            _gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k, k_rem=k_rem,
            input_dtype=jnp.bfloat16 if both_bf16 else jnp.float32,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda n_i, v, k_i, _o, _g, mt, _f:
                    (mt[v], k_i),
                ),
                pl.BlockSpec(
                    (None, tk, tn), lambda n_i, v, k_i, _o, g, _mt, f:
                    (g[v] + f[0], k_i, n_i),
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, _o, _g, mt, _f: (mt[v], n_i)
            ),
            grid=(tiles_n, tiles.num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        cost_estimate=cost,
        name="gmm",
    )(
        tiles.group_offsets, tiles.group_ids, tiles.m_tile_ids,
        jnp.asarray(first, jnp.int32).reshape(1), lhs, stack,
    )


def take_rows(x: jax.Array, index: jax.Array) -> jax.Array:
    """``x[index]`` along the first of two axes for a plan's ``src`` or
    ``back``, which lie inside what they index: a bare gather, without
    the wrap of negative indices and the clamp that ``x[index]`` puts
    in front of one (two small operations a gather)."""
    return lax.gather(
        x, index[:, None],
        lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,)
        ),
        slice_sizes=(1, x.shape[1]),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def grouped_matmul(rows: jax.Array, stack: jax.Array, plan: DispatchPlan,
                   first) -> jax.Array:
    """``out[i] = rows[i] @ stack[first + g]`` for row ``i`` of the
    plan's group ``g``, ``[m, n_out]`` in ``rows``' dtype, accumulated
    in float32.  What rows past the last group hold is not defined (the
    kernel never writes them; ``ragged_dot`` gives zeros)."""
    if plan.tiles is None:
        experts = lax.dynamic_slice_in_dim(
            stack, first, plan.group_sizes.shape[0], axis=0
        )
        return lax.ragged_dot(
            rows, experts.astype(rows.dtype), plan.group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(rows.dtype)
    return _gmm(
        rows, stack, plan.tiles, first,
        interpret=grouped_matmul_kernel() == "interpret",
    )
