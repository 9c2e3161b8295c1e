"""Decode attention of a pool's rows, read in place from the paged arena.

One query a row a head against the entries the row holds.  XLA can only
gather every row's whole table into a dense buffer first, live or not,
and read that again: at 64 rows of 128 pages of ``bf16[16, 8, 128]``
that is a write and two reads of 0.5 GB a layer for a few tens of MB of
live entries.  The kernel here walks each row's own list of live pages
and copies every page once, HBM -> VMEM, double buffered, under the
arithmetic of the page before it; the cost follows what the rows hold.

The grid is one program a row.  Page ids and the four numbers that say
which entries count ride in SMEM (scalar prefetch); K and V stay in
HBM (``memory_space=ANY``) and are reached by ``make_async_copy``.
The score of an entry is a product and a lane sum on the VPU (one
query a head is no work for the MXU); the softmax state lives with KV
heads on sublanes (``[kv, 1]``, ``[kv, hd]``), once for each of the
``reps`` query heads that share a KV head, so that no step transposes.

Three callers, one ``tpu_custom_call`` name each:
``paged_decode_attention`` below (full history, heads grouped over
``n_kv_heads``), ``window_decode_attention`` below it (the last
``window`` positions out of a row's ring: the one kernel with a LOWER
bound on the entries that count, ``paged_decode_attention_window`` in a
trace) and ``eva_decode_attention`` (ops/eva_decode.py: two regions, no
grouping).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30


def _kernel(ids_ref, n_first_ref, n_pages_ref, bound_first_ref,
            bound_rest_ref, *refs, scale: float, lower: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # with ``lower``, one more prefetched scalar a row: the first
    # region's entries count from that index on
    lower_ref = refs[0] if lower else None
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs[int(lower):]
    row = pl.program_id(0)
    n_pages, n_first = n_pages_ref[row], n_first_ref[row]
    bound_first, bound_rest = bound_first_ref[row], bound_rest_ref[row]
    page, kv_heads, head_dim = k_buf.shape[1:]
    reps = q_ref.shape[1] // kv_heads
    q = q_ref[0].astype(jnp.float32) * scale              # [reps * kv, hd]
    q = [q[r * kv_heads:(r + 1) * kv_heads] for r in range(reps)]

    def copies(j, slot):
        at = ids_ref[row, j]
        return (
            pltpu.make_async_copy(k_hbm.at[at], k_buf.at[slot],
                                  sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[at], v_buf.at[slot],
                                  sem.at[1, slot]),
        )

    for copy in copies(0, 0):      # every row has its first page
        copy.start()

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_pages)
        def _():
            for copy in copies(j + 1, 1 - slot):
                copy.start()

        for copy in copies(j, slot):
            copy.wait()
        k = k_buf[slot].astype(jnp.float32)               # [P, kv, hd]
        v = v_buf[slot].astype(jnp.float32)
        # the list holds the first region's pages, then the rest: an
        # entry counts while its index in its own region is under the
        # region's bound
        in_first = j < n_first
        first = jnp.where(in_first, j, j - n_first) * page
        bound = jnp.where(in_first, bound_first, bound_rest)
        index = first + lax.broadcasted_iota(
            jnp.int32, (page, kv_heads, 1), 0
        )
        counts = index < bound
        if lower:
            counts &= index >= jnp.where(in_first, lower_ref[row], 0)
        state = []
        for q_r, (m, l, acc) in zip(q, carry):
            s = jnp.sum(k * q_r[None], axis=-1, keepdims=True)  # [P, kv, 1]
            s = jnp.where(counts, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=0))         # [kv, 1]
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[None])                  # [P, kv, 1]
            l = alpha * l + p.sum(axis=0)
            acc = alpha * acc + jnp.sum(p * v, axis=0)    # [kv, hd]
            state.append((m_new, l, acc))
        return tuple(state)

    state = lax.fori_loop(0, n_pages, body, tuple(
        (
            jnp.full((kv_heads, 1), _NEG, jnp.float32),
            jnp.zeros((kv_heads, 1), jnp.float32),
            jnp.zeros((kv_heads, head_dim), jnp.float32),
        )
        for _ in range(reps)
    ))
    out = [acc / l for _m, l, acc in state]
    out = out[0] if reps == 1 else jnp.concatenate(out, axis=0)
    o_ref[0] = out.astype(o_ref.dtype)


def page_walk_attention(q, arena_k, arena_v, page_ids, n_first, n_pages,
                        bound_first, bound_rest, *, scale: float, name: str,
                        interpret: bool = False, lower_first=None):
    """``q [S, reps * kv, hd]`` against ``arena_k``/``arena_v
    [N, P, kv, hd]``: query head ``r * kv + g`` reads KV head ``g``.

    Row ``s`` reads the pages ``page_ids[s, :n_pages[s]]`` (rows of the
    arena's leading axis): first ``n_first[s]`` pages whose entries
    count while their index among those pages' entries is under
    ``bound_first[s]``, then the rest, whose entries count while their
    index is under ``bound_rest[s]``.  Every row has at least its first
    page with one entry that counts (an idle row's is the trash page).
    With ``lower_first [S]`` an entry of the first region counts only
    from that index on (a kernel of its own: the others carry no such
    operand).  Returns ``[S, reps * kv, hd]`` in ``q``'s dtype from the
    ``tpu_custom_call`` called ``name``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, head_dim = q.shape
    page, kv_heads = arena_k.shape[1:3]
    block = (1, heads, head_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + (lower_first is not None),
        grid=(rows,),
        in_specs=[
            pl.BlockSpec(block, lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(block, lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, kv_heads, head_dim), arena_k.dtype),
            pltpu.VMEM((2, page, kv_heads, head_dim), arena_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    scalars = [page_ids, n_first, n_pages, bound_first, bound_rest]
    if lower_first is not None:
        scalars.append(lower_first)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, lower=lower_first is not None
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name,
    )(*(a.astype(jnp.int32) for a in scalars), q, arena_k, arena_v)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, arena_k, arena_v, page_ids, pos, *,
                           scale: float, interpret: bool = False):
    """Full-history attention of rows at ``pos [S]``: ``q [S, H, hd]``
    against the entries ``0 .. pos[s]`` of row ``s``, which lie in
    virtual order in the pages ``page_ids[s, :pos[s] // P + 1]`` of
    ``arena_k``/``arena_v [N, P, kv, hd]``.  Head ``h`` reads KV head
    ``h // (H // kv)``.  Returns ``[S, H, hd]`` in ``q``'s dtype."""
    rows, heads, head_dim = q.shape
    page, kv_heads = arena_k.shape[1:3]
    reps = heads // kv_heads
    n_pages = jnp.minimum(pos // page + 1, page_ids.shape[1])
    out = page_walk_attention(
        q.reshape(rows, kv_heads, reps, head_dim).swapaxes(1, 2).reshape(
            q.shape
        ),
        arena_k, arena_v, page_ids, n_pages, n_pages, pos + 1,
        jnp.zeros_like(pos), scale=scale, name="paged_decode_attention",
        interpret=interpret,
    )
    return out.reshape(rows, reps, kv_heads, head_dim).swapaxes(1, 2).reshape(
        q.shape
    )


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def window_decode_attention(q, arena_k, arena_v, ring_ids, pos, *,
                            window: int, scale: float,
                            interpret: bool = False):
    """Window attention of rows at ``pos [S]`` out of their RINGS:
    ``q [S, H, hd]`` against the positions ``j`` with ``pos[s] - window
    < j <= pos[s]`` of row ``s``, where position ``p`` lies in page
    ``ring_ids[s, (p // P) % R]`` of ``arena_k``/``arena_v [N, P, kv,
    hd]`` at entry ``p % P`` (serve/paging.py RowLayout).  The kernel
    is handed the ring's pages in virtual order, from the page of the
    first position seen to the page of ``pos``, and the index of the
    first entry that counts among them; it reads those pages and no
    other.  Head ``h`` reads KV head ``h // (H // kv)``.  Returns
    ``[S, H, hd]`` in ``q``'s dtype."""
    rows, heads, head_dim = q.shape
    page, kv_heads = arena_k.shape[1:3]
    reps = heads // kv_heads
    ring = ring_ids.shape[1]
    first_seen = jnp.maximum(pos - window + 1, 0)
    first_page = first_seen // page
    n_pages = pos // page - first_page + 1
    # a window's positions lie in at most this many pages
    most = min(ring, -(-window // page) + 1)
    order = (first_page[:, None] + jnp.arange(most, dtype=jnp.int32)) % ring
    out = page_walk_attention(
        q.reshape(rows, kv_heads, reps, head_dim).swapaxes(1, 2).reshape(
            q.shape
        ),
        arena_k, arena_v, jnp.take_along_axis(ring_ids, order, axis=1),
        n_pages, n_pages, pos + 1 - first_page * page, jnp.zeros_like(pos),
        scale=scale, name="paged_decode_attention_window",
        interpret=interpret, lower_first=first_seen - first_page * page,
    )
    return out.reshape(rows, reps, kv_heads, head_dim).swapaxes(1, 2).reshape(
        q.shape
    )
