"""Decode attention of a pool's rows, read in place from the paged arena.

One query a row a head against the entries the row holds.  XLA can only
gather every row's whole table into a dense buffer first, live or not,
and read that again: at 64 rows of 128 pages of ``bf16[16, 8, 128]``
that is a write and two reads of 0.5 GB a layer for a few tens of MB of
live entries.  The kernel here walks the LIVE rows' own lists of live
pages and copies every page once, HBM -> VMEM; the cost follows what
the rows hold, and a slot that decodes nothing costs nothing.

One program walks the pool: the scalar core steps from live row to
live row (a row that holds no page is passed over; its output is the
zeros the program starts from).  A step of the walk is a BLOCK of
pages (``step_pages``: as many as ``STEP_VMEM_BYTES`` holds two deep
of K and of V, at most ``STEP_PAGES``; 8, 32 and 64 pages at 32, 8 and
4 KV heads of 128 lanes in bfloat16, 4,096 rows of the MXU's operand
each time).  A block's copies are in flight together, one a page, two
blocks deep, and the block behind a row's last is the next live row's
first, so the copies' latency is paid once a call and not once a row.
Page ids and the numbers that say which entries count ride in SMEM
(scalar prefetch); K and V stay in HBM (``memory_space=ANY``), seen as
``[N, page * kv, hd]`` so that a block is a plain matrix of (entry, KV
head) rows and nothing in the kernel regroups it.

The arithmetic of a block is two products on the MXU.  ALL the query
heads score ALL the block's rows at once (``[heads, hd] x [hd, rows]``,
the operands as stored, float32 accumulation) and a mask keeps, of
each head's scores, those of its own KV head and of the entries that
count; the weights, exact as three parts in the stored format of each
float32 one, multiply V in one ``[3 * heads, rows] x [rows, hd]``.
The scores of another head's rows are work the MXU does in the time
it loads a block's tiles anyway; what it buys is that no step
transposes, regroups or reduces over lanes, whatever ``kv_heads`` and
``reps`` are: one form for all three callers.  Softmax state, scale,
max, exp, sums and the division are float32; a part-filled block is
masked by the same bounds as a part-filled page.

Three callers, one ``tpu_custom_call`` name each:
``paged_decode_attention`` below (full history, heads grouped over
``n_kv_heads``), ``window_decode_attention`` below it (the last
``window`` positions out of a row's ring: the one kernel with a LOWER
bound on the entries that count and a list of pages that is a ring,
``paged_decode_attention_window`` in a trace) and
``eva_decode_attention`` (ops/eva_decode.py: two regions, no grouping).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30
# what a step's two blocks of K and of V may hold of the chip's fast
# memory, and the most pages a step takes
STEP_VMEM_BYTES = 4 * 2 ** 20
STEP_PAGES = 64


def step_pages(page: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """The pages one step of the walk takes: two blocks each of K and V
    within ``STEP_VMEM_BYTES``, at most ``STEP_PAGES``."""
    held = 4 * page * kv_heads * head_dim * itemsize
    return max(1, min(STEP_PAGES, STEP_VMEM_BYTES // held))


def walk_step(cache_k) -> dict:
    """What a step of the walk over an arena like ``cache_k [..., P, kv,
    lanes]`` does, for ``/stats``: its pages and the form of its
    products (one form: every head against every row, masked)."""
    page, kv_heads, lanes = cache_k.shape[-3:]
    return {
        "pages": step_pages(page, kv_heads, lanes, cache_k.dtype.itemsize),
        "scores": "mxu_masked_heads",
    }


def _kernel(ids_ref, n_first_ref, n_pages_ref, bound_first_ref,
            bound_rest_ref, *refs, scale: float, page: int,
            lower: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # with ``lower``, two more prefetched scalars a row: the first
    # region's entries count from that index on, and the row's list of
    # pages is a ring that starts at that place
    lower_ref, turn_ref = refs[:2] if lower else (None, None)
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs[2 * int(lower):]
    slots, heads, head_dim = q_ref.shape
    span = k_hbm.shape[1]                  # a page's rows: page * kv
    kv_heads = span // page
    width = k_buf.shape[1]                 # a block's rows
    pages = width // span
    stored = k_buf.dtype
    whole = lax.Precision.HIGHEST if stored == jnp.float32 else None

    # an idle slot's output; and no NaN among the rows of V that a
    # part-filled block leaves unwritten, whose weights are zeros (a
    # score of such a row of K is masked, whatever it is)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    v_buf[...] = jnp.zeros(v_buf.shape, stored)

    def copies(at, i, slot):
        rows = pl.ds(pl.multiple_of(i * span, span), span)
        return (
            pltpu.make_async_copy(k_hbm.at[at], k_buf.at[slot, rows],
                                  sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[at], v_buf.at[slot, rows],
                                  sem.at[1, slot]),
        )

    def each_page(row, block, do):
        held = jnp.minimum(n_pages_ref[row] - block * pages, pages)
        lax.fori_loop(0, held, lambda i, _: do(i) or 0, 0)

    def start(row, block, slot):
        def one(i):
            j = block * pages + i
            if lower:
                # once round the ring at most: no division a page
                ring = ids_ref.shape[1]
                j = turn_ref[row] + j
                j = jnp.where(j >= ring, j - ring, j)
            for copy in copies(ids_ref[row, j], i, slot):
                copy.start()

        each_page(row, block, one)

    def wait(row, block, slot):
        def one(i):
            for copy in copies(0, i, slot):
                copy.wait()

        each_page(row, block, one)

    def live_from(row):
        """The first slot from ``row`` on that holds a page; ``slots``
        where none does."""
        return lax.while_loop(
            lambda r: (r < slots)
            & (n_pages_ref[jnp.minimum(r, slots - 1)] <= 0),
            lambda r: r + 1, row,
        )

    # what a block's row stands for: a page of the block, an entry of
    # the page, a KV head; a query head reads its own KV head's rows
    at = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    at_page, at_entry = at // span, at % span // kv_heads
    own = (
        lax.broadcasted_iota(jnp.int32, (heads, width), 0)
        // (heads // kv_heads)
        == lax.broadcasted_iota(jnp.int32, (heads, width), 1) % kv_heads
    )

    def walk(carry):
        row, done = carry
        n_pages, n_first = n_pages_ref[row], n_first_ref[row]
        bound_first, bound_rest = bound_first_ref[row], bound_rest_ref[row]
        blocks = (n_pages + pages - 1) // pages
        behind = live_from(row + 1)
        q = q_ref[row]                                    # [heads, hd]

        def step(b, carry):
            m, l, acc = carry
            slot = (done + b) % 2

            # the block behind this one: the row's next, or the next
            # live row's first
            @pl.when(b + 1 < blocks)
            def _():
                start(row, b + 1, 1 - slot)

            @pl.when((b + 1 == blocks) & (behind < slots))
            def _():
                start(jnp.minimum(behind, slots - 1), 0, 1 - slot)

            wait(row, b, slot)
            k, v = k_buf[slot], v_buf[slot]               # [width, hd]
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=whole,
            ) * scale                                     # [heads, width]
            # the list holds the first region's pages, then the rest: an
            # entry counts while its index in its own region is under
            # the region's bound
            j = b * pages + at_page
            in_first = j < n_first
            index = jnp.where(in_first, j, j - n_first) * page + at_entry
            counts = (j < n_pages) & (
                index < jnp.where(in_first, bound_first, bound_rest)
            )
            if lower:
                counts &= index >= jnp.where(in_first, lower_ref[row], 0)
            s = jnp.where(own & counts, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)                        # [heads, width]
            l = alpha * l + p.sum(axis=1, keepdims=True)
            if whole:
                pv = jnp.dot(p, v, preferred_element_type=jnp.float32,
                             precision=whole)
            else:
                # three parts in the stored format hold a float32
                # weight whole, and the MXU's products of two such
                # numbers are exact
                high = p.astype(stored)
                rest = p - high.astype(jnp.float32)
                mid = rest.astype(stored)
                low = (rest - mid.astype(jnp.float32)).astype(stored)
                pv = jnp.dot(
                    jnp.concatenate([high, mid, low], axis=0), v,
                    preferred_element_type=jnp.float32,
                )
                pv = pv[:heads] + pv[heads:2 * heads] + pv[2 * heads:]
            return m_new, l, alpha * acc + pv

        _m, l, acc = lax.fori_loop(0, blocks, step, (
            jnp.full((heads, 1), _NEG, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, head_dim), jnp.float32),
        ))
        o_ref[row] = (acc / l).astype(o_ref.dtype)
        return behind, done + blocks

    first = live_from(0)

    @pl.when(first < slots)
    def _():
        start(jnp.minimum(first, slots - 1), 0, 0)

    lax.while_loop(lambda carry: carry[0] < slots, walk, (first, 0))


def page_walk_attention(q, arena_k, arena_v, page_ids, n_first, n_pages,
                        bound_first, bound_rest, *, scale: float, name: str,
                        interpret: bool = False, lower_first=None,
                        ring_first=None, live=None):
    """``q [S, kv * reps, hd]`` against ``arena_k``/``arena_v
    [N, P, kv, hd]``: query head ``h`` reads KV head ``h // reps``.

    Row ``s`` reads the pages ``page_ids[s, :n_pages[s]]`` (rows of the
    arena's leading axis): first ``n_first[s]`` pages whose entries
    count while their index among those pages' entries is under
    ``bound_first[s]``, then the rest, whose entries count while their
    index is under ``bound_rest[s]``.  With ``lower_first [S]`` and
    ``ring_first [S]`` an entry of the first region counts only from
    that index on, and a row's list of pages is a RING: its ``j``-th
    page is ``page_ids[s, (ring_first[s] + j) % M]`` (a kernel of its
    own: the others carry no such operands).  A row that ``live [S]``
    (bool; all rows where None) says is idle, or that holds no page, is
    not visited: no copy, no arithmetic, and zeros for its output.
    Every visited row has at least one entry that counts.  Returns
    ``[S, kv * reps, hd]`` in ``q``'s dtype from the ``tpu_custom_call``
    called ``name``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    head_dim = q.shape[-1]
    n_arena, page, kv_heads = arena_k.shape[:3]
    span = page * kv_heads
    pages = step_pages(page, kv_heads, head_dim, arena_k.dtype.itemsize)
    if live is not None:
        n_pages = jnp.where(live, n_pages, 0)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    scalars = [page_ids, n_first, n_pages, bound_first, bound_rest]
    if lower_first is not None:
        scalars += [lower_first, ring_first]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=[
            whole(q.shape),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=whole(q.shape),
        scratch_shapes=[
            pltpu.VMEM((2, pages * span, head_dim), arena_k.dtype),
            pltpu.VMEM((2, pages * span, head_dim), arena_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, page=page, lower=lower_first is not None
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name,
    )(
        *(a.astype(jnp.int32) for a in scalars), q,
        arena_k.reshape(n_arena, span, head_dim),
        arena_v.reshape(n_arena, span, head_dim),
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, arena_k, arena_v, page_ids, pos, live=None, *,
                           scale: float, interpret: bool = False):
    """Full-history attention of rows at ``pos [S]``: ``q [S, H, hd]``
    against the entries ``0 .. pos[s]`` of row ``s``, which lie in
    virtual order in the pages ``page_ids[s, :pos[s] // P + 1]`` of
    ``arena_k``/``arena_v [N, P, kv, hd]``.  Head ``h`` reads KV head
    ``h // (H // kv)``.  Rows that ``live [S]`` says are idle are not
    read and give zeros.  Returns ``[S, H, hd]`` in ``q``'s dtype."""
    page = arena_k.shape[1]
    n_pages = jnp.minimum(pos // page + 1, page_ids.shape[1])
    return page_walk_attention(
        q, arena_k, arena_v, page_ids, n_pages, n_pages, pos + 1,
        jnp.zeros_like(pos), scale=scale, name="paged_decode_attention",
        interpret=interpret, live=live,
    )


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def window_decode_attention(q, arena_k, arena_v, ring_ids, pos, live=None, *,
                            window: int, scale: float,
                            interpret: bool = False):
    """Window attention of rows at ``pos [S]`` out of their RINGS:
    ``q [S, H, hd]`` against the positions ``j`` with ``pos[s] - window
    < j <= pos[s]`` of row ``s``, where position ``p`` lies in page
    ``ring_ids[s, (p // P) % R]`` of ``arena_k``/``arena_v [N, P, kv,
    hd]`` at entry ``p % P`` (serve/paging.py RowLayout).  The kernel
    is handed the ring, the place in it of the page of the first
    position seen and the index of the first entry that counts; it
    reads the ring's pages in virtual order from there to the page of
    ``pos`` and no other.  Head ``h`` reads KV head ``h // (H // kv)``.
    Rows that ``live [S]`` says are idle are not read and give zeros.
    Returns ``[S, H, hd]`` in ``q``'s dtype."""
    page = arena_k.shape[1]
    first_seen = jnp.maximum(pos - window + 1, 0)
    first_page = first_seen // page
    n_pages = pos // page - first_page + 1
    return page_walk_attention(
        q, arena_k, arena_v, ring_ids, n_pages, n_pages,
        pos + 1 - first_page * page, jnp.zeros_like(pos), scale=scale,
        name="paged_decode_attention_window", interpret=interpret,
        lower_first=first_seen - first_page * page,
        ring_first=first_page % ring_ids.shape[1], live=live,
    )
