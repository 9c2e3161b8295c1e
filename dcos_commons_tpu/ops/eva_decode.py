"""Decode attention of an EVA row, read in place from the paged arena.

One query a row a head against the row's LIVE entries: the exact keys
of its current window (ring pages) and the chunk summaries of the
windows before it (summary pages), one softmax over both
(models/decode.py ``_eva_decode_step``).  XLA can only gather a row's
whole table into a dense buffer first, live or not, and read that
again: at 24 rows of 256 pages that is 4.8 GB a layer for 0.4 GB of
live entries.  This kernel walks each row's own list of live pages and
copies every page once, HBM -> VMEM, double buffered, under the
arithmetic of the page before it; the cost follows what the rows hold.

The grid is one program a row.  Page ids and the four numbers that say
which entries count ride in SMEM (scalar prefetch); K and V stay in
HBM (``memory_space=ANY``) and are reached by ``make_async_copy``.
The score of an entry is a product and a lane sum on the VPU (one
query a head is no work for the MXU); the softmax state lives with
heads on sublanes (``[H, 1]``, ``[H, hd]``) so that no step transposes.
The ``tpu_custom_call`` is named ``eva_decode_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30


def _kernel(ids_ref, n_ring_ref, n_pages_ref, n_win_ref, n_sum_ref,
            q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, *,
            scale: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row = pl.program_id(0)
    n_pages, n_ring = n_pages_ref[row], n_ring_ref[row]
    n_win, n_sum = n_win_ref[row], n_sum_ref[row]
    page, heads, head_dim = k_buf.shape[1:]
    q = q_ref[0].astype(jnp.float32) * scale              # [H, hd]

    def copies(j, slot):
        at = ids_ref[row, j]
        return (
            pltpu.make_async_copy(k_hbm.at[at], k_buf.at[slot],
                                  sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[at], v_buf.at[slot],
                                  sem.at[1, slot]),
        )

    for copy in copies(0, 0):      # every row has its first ring page
        copy.start()

    def body(j, carry):
        m, l, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n_pages)
        def _():
            for copy in copies(j + 1, 1 - slot):
                copy.start()

        for copy in copies(j, slot):
            copy.wait()
        k = k_buf[slot].astype(jnp.float32)               # [P, H, hd]
        v = v_buf[slot].astype(jnp.float32)
        # the list holds the ring's pages, then the summaries': an
        # entry counts while its index in its own region is under the
        # region's bound
        in_ring = j < n_ring
        first = jnp.where(in_ring, j, j - n_ring) * page
        bound = jnp.where(in_ring, n_win, n_sum)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # [P, H, 1]
        index = first + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(index < bound, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=0))             # [H, 1]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[None])                      # [P, H, 1]
        l = alpha * l + p.sum(axis=0)
        acc = alpha * acc + jnp.sum(p * v, axis=0)        # [H, hd]
        return m_new, l, acc

    m, l, acc = lax.fori_loop(0, n_pages, body, (
        jnp.full((heads, 1), _NEG, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, head_dim), jnp.float32),
    ))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def eva_decode_attention(q, arena_k, arena_v, page_ids, n_ring, n_pages,
                         n_window, n_summary, *, scale: float,
                         interpret: bool = False):
    """``q [S, H, hd]`` against ``arena_k``/``arena_v [N, P, H, hd]``.

    Row ``s`` reads the pages ``page_ids[s, :n_pages[s]]`` (rows of the
    arena's leading axis): first its ``n_ring[s]`` ring pages, whose
    entries count while their index in the ring is under
    ``n_window[s]``, then its summary pages, whose entries count while
    their index is under ``n_summary[s]``.  Every row has at least its
    first ring page with one entry that counts (an idle row's is the
    trash page).  Returns ``[S, H, hd]`` in ``q``'s dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, head_dim = q.shape
    page = arena_k.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, heads, head_dim), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, head_dim), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, heads, head_dim), arena_k.dtype),
            pltpu.VMEM((2, page, heads, head_dim), arena_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="eva_decode_attention",
    )(
        page_ids.astype(jnp.int32), n_ring.astype(jnp.int32),
        n_pages.astype(jnp.int32), n_window.astype(jnp.int32),
        n_summary.astype(jnp.int32), q, arena_k, arena_v,
    )


def live_pages(tables, pos, window: int, chunk: int, page: int):
    """What ``eva_decode_attention`` reads for rows at ``pos [S]`` with
    tables ``[S, M]`` (serve/paging.py RowLayout: ``window / page``
    ring entries, then the summary pages): ``(ids [S, M], n_ring,
    n_pages, n_window, n_summary)``, ``ids`` relative to the layer's
    first page.  A ring page is live when its first entry is a
    position of the row's window up to ``pos``; a summary page when its
    first summary is of a window that is past."""
    window_pages = window // page
    n_window = pos % window + 1
    n_summary = (pos // window) * (window // chunk)
    n_ring = (n_window + page - 1) // page
    n_pages = n_ring + (n_summary + page - 1) // page
    j = jnp.arange(tables.shape[1], dtype=jnp.int32)[None, :]
    entry = jnp.where(
        j < n_ring[:, None], j, window_pages + j - n_ring[:, None]
    )
    ids = jnp.take_along_axis(
        tables, jnp.minimum(entry, tables.shape[1] - 1), axis=1
    )
    return ids, n_ring, n_pages, n_window, n_summary
