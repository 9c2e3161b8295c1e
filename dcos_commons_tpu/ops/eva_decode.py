"""Decode attention of an EVA row, read in place from the paged arena.

One query a row a head against the row's LIVE entries: the exact keys
of its current window (ring pages) and the chunk summaries of the
windows before it (summary pages), one softmax over both
(models/decode.py ``_eva_step_part``).  XLA can only gather a row's
whole table into a dense buffer first, live or not, and read that
again: at 24 rows of 256 pages that is 4.8 GB a layer for 0.4 GB of
live entries.  ``live_pages`` lists each row's live pages and the page
walk of ops/paged_decode.py copies every one of them once, a block of
pages a step (8 at this model's 32 heads of 128), and visits no slot
that decodes nothing; the cost follows what the rows hold.  No head is
grouped here (``reps`` 1): the walk's one form, every head's query
against every (entry, head) row of a block in one MXU product and a
mask that keeps each head its own, replaces the lane sum a head an
entry.  The walk's two regions are the ring and the summaries, and a
block may hold the end of one and the start of the other.
The ``tpu_custom_call`` is named ``eva_decode_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dcos_commons_tpu.ops.paged_decode import page_walk_attention


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def eva_decode_attention(q, arena_k, arena_v, page_ids, n_ring, n_pages,
                         n_window, n_summary, live=None, *, scale: float,
                         interpret: bool = False):
    """``q [S, H, hd]`` against ``arena_k``/``arena_v [N, P, H, hd]``.

    Row ``s`` reads the pages ``page_ids[s, :n_pages[s]]`` (rows of the
    arena's leading axis): first its ``n_ring[s]`` ring pages, whose
    entries count while their index in the ring is under
    ``n_window[s]``, then its summary pages, whose entries count while
    their index is under ``n_summary[s]``.  Rows that ``live [S]`` says
    are idle are not read and give zeros; every other row has at least
    its first ring page with one entry that counts.  Returns ``[S, H,
    hd]`` in ``q``'s dtype."""
    return page_walk_attention(
        q, arena_k, arena_v, page_ids, n_ring, n_pages, n_window,
        n_summary, scale=scale, name="eva_decode_attention",
        interpret=interpret, live=live,
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
