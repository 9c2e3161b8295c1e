"""Paged KV memory for the serving engine: page allocator, admission
budget, and the refcounted prefix cache.

KV memory is a fixed arena of ``page_tokens``-sized pages and each
request holds a PAGE TABLE (virtual position ``p`` lives in physical
page ``table[p // page_tokens]``), so a short request holds exactly
the pages its tokens need — a 10-token reply does not strand a
MAX_LEN row — and the freed remainder admits more concurrent requests
under the SAME HBM budget (vLLM's PagedAttention shape).  This module
is the host-side half.

Everything here is jax-free bookkeeping driven by the engine's loop
thread; the device half (arena tensors, gather-attention) lives in
``models/decode.py`` + ``serve/pool.py``.  Three pieces:

* **Free-list allocator with admission budgeting** — a request is
  admitted only when its WORST-CASE page need (every token decoded,
  no early EOS) fits ``available - reserved``; the need is then
  RESERVED and consumed lazily as positions cross page boundaries, so
  an admitted request can never hit a mid-generation out-of-pages
  (reservations are the invariant: ``reserved <= available`` always).
  Pages freed by early retirement (EOS) return immediately.

* **Prefix cache** — full prompt pages are published into an
  exact-match chain (key = (parent entry, the page's tokens); no
  hash collisions by construction) as READ-ONLY shared pages.  A new
  request whose prompt starts with a cached chain skips prefilling
  those pages entirely: it pins the entries (refcount) and maps them
  into its own table.  At millions-of-users scale most traffic shares
  system prompts, so this multiplies effective KV capacity.

* **Copy-on-write by recompute** — shared pages are never written.
  Cache hits are FULL-page-granular, and a hit is capped so at least
  one prompt token is always prefilled privately; a request that
  diverges mid-page simply misses that page and prefills its own
  private copy, and generated tokens always land in private pages
  (the first decode write position lies past every shared page by
  construction).  Zero-ref entries stay resident and are evicted
  leaf-first in LRU order only under budget pressure.

* **Row layout** — what a table entry MEANS is the attention's to
  say (``RowLayout``).  With full attention entry ``v`` is virtual
  page ``v`` of the row's history, for ever.  With EVA attention
  (models/decode.py) a row keeps two kinds of entry of one shape in
  one arena: a RING of ``window / P`` pages of exact K/V, written over
  in place when a window ends, and behind it the pages of chunk
  summaries, ``P`` chunks a page, which are written once and are the
  only pages such a row can share.  A 32,768-position row then needs
  256 pages where full attention needs 2,048, and the admission rule
  reserves by that.  Where WINDOW layers stand among full ones
  (``sliding_window``), a row keeps one cache a KIND of layer: entries
  ``[0, ring_pages)`` are a ring of the window layers' last
  ``sliding_window`` + one prefill chunk of positions, in an arena of
  their own whose pages belong to the row's SLOT, and the entries
  behind them the whole history of the full layers, drawn from the
  page budget as ever.

``paged_config_from_env`` is the ONE env -> paged-geometry contract,
shared by both serve workers, shardcheck's ``_serve_leaves`` footprint
model, and (through the serve workload profiles) the PR 9 admission
gate — a page budget that cannot hold even one max-length request is
a deploy-time SpecError, not a permanent runtime 503.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

# physical page 0 is the TRASH page: never allocated, the scatter
# target for padding/inactive-row writes in the device kernels (a
# page-table entry of 0 also means "virtual page not yet allocated" —
# such positions are always masked out of attention)
TRASH_PAGE = 0


def pages_for(tokens: int, page_tokens: int) -> int:
    """Pages covering ``tokens`` KV positions (ceil)."""
    return (tokens + page_tokens - 1) // page_tokens


def worst_case_pages(prompt_len: int, max_new: int, page_tokens: int) -> int:
    """Worst-case pages one request can ever WRITE: positions
    ``[0, prompt_len + max_new - 1)`` — the final sampled token is
    returned but its K/V is never written (nothing decodes after
    it).  The full-attention rule; ``RowLayout.worst_case_pages`` is
    the rule of whichever layout a pool runs."""
    return pages_for(prompt_len + max_new - 1, page_tokens)


@dataclass(frozen=True)
class RowLayout:
    """How one row's positions map onto its page table, and what that
    costs: the one place that knows what a table entry stands for.

    ``window == 0``: full attention, entry ``v`` holds positions
    ``[v*P, (v+1)*P)``.  ``window > 0`` (EVA, with ``chunk == P``):
    entries ``[0, window/P)`` are the ring of the current window's
    exact K/V (position ``p`` in ring page ``(p % window) // P``);
    entry ``window/P + i`` holds the summaries of chunks
    ``[i*P, (i+1)*P)``, written as each chunk ends.

    ``state_bytes_per_row``: what a row keeps on the device OUTSIDE
    its pages, by its slot and however long it is (a conv layer's last
    gated inputs, models/decode.py ``init_paged_kv_cache``).  It is
    resident for every slot, so a free slot is all that admission
    needs of it; but a row's pages alone then no longer say what the
    row has seen: such a layout shares no prefix page, and a session
    of it cannot be exported, spliced or handed off
    (``carries_state``).

    ``sliding_window > 0``: the model has window attention layers
    among full ones, and a row one cache a KIND.  Entries ``[0,
    ring_pages)`` are the window layers' RING: position ``p`` lives in
    ring page ``(p // P) % ring_pages``, and ``ring_pages`` covers the
    window and one prefill chunk (``with_chunk`` sets it: a layout is
    whole once it knows the chunk), so a chunk's first
    query still finds its ``sliding_window - 1`` predecessors once the
    chunk's last keys are written.  A ring's pages are the row's
    SLOT's, in the window layers' own arena (``ring_entries``: resident
    for every slot as conv state is, so they are never allocated, and
    a free slot is all that admission needs of them).  Entry
    ``ring_pages + v`` holds positions ``[v*P, (v+1)*P)`` of the full
    layers' history, for ever, from the page budget.  A ring holds
    what the prefix before it left, so such a row's pages do not
    travel either (``carries_state``)."""

    page_tokens: int
    window: int = 0
    chunk: int = 0
    state_bytes_per_row: int = 0
    sliding_window: int = 0
    ring_pages: int = 0

    def __post_init__(self) -> None:
        if self.sliding_window and self.window:
            raise ValueError(
                "a row has window layers among full ones or EVA's window "
                "and summaries, not both"
            )
        if self.ring_pages and not self.sliding_window:
            raise ValueError(
                f"a ring of {self.ring_pages} pages and no window layer "
                "to keep it (sliding_window 0)"
            )
        if not self.window:
            return
        if self.chunk != self.page_tokens:
            raise ValueError(
                f"a windowed row keeps one chunk a page: chunk "
                f"{self.chunk} != page_tokens {self.page_tokens}"
            )
        if self.window % (self.chunk * self.page_tokens):
            raise ValueError(
                f"window {self.window} is not a whole number of summary "
                f"pages ({self.chunk * self.page_tokens} positions each)"
            )

    def with_chunk(self, chunk_tokens: int) -> "RowLayout":
        """This layout for prefill chunks of ``chunk_tokens``: window
        layers' rings then hold the window and one chunk, in whole
        pages (one more where the window ends inside a page)."""
        if not self.sliding_window:
            return self
        p = self.page_tokens
        if chunk_tokens % p:
            raise ValueError(
                f"a ring is written a prefill chunk at a time, in whole "
                f"pages: chunk {chunk_tokens} of {p}-token pages"
            )
        return dataclasses.replace(self, ring_pages=pages_for(
            self.sliding_window + chunk_tokens, p
        ) + bool(self.sliding_window % p))

    @property
    def window_pages(self) -> int:
        return self.window // self.page_tokens if self.window else 0

    def ring_entries(self, slot: int) -> List[int]:
        """The ring of the row in ``slot``: its pages of the window
        layers' arena (page 0 there is that arena's trash page)."""
        first = 1 + slot * self.ring_pages
        return list(range(first, first + self.ring_pages))

    def window_entries(self, positions: int) -> int:
        """Ring entries that still count for a row with ``positions``
        behind it: what its next step reads in a window layer."""
        return min(positions, self.sliding_window)

    @property
    def carries_state(self) -> str:
        """Why this layout's pages may not travel or be shared without
        the row they belong to ("" where they may): the reason a
        client is given."""
        if self.ring_pages:
            return (
                f"a row of this model keeps the last {self.sliding_window} "
                "positions of its window attention layers in a ring of its "
                "slot, which no history page carries: its pages are not "
                "shared, exported, spliced or handed off"
            )
        if not self.state_bytes_per_row:
            return ""
        return (
            f"a row of this model keeps {self.state_bytes_per_row} bytes "
            "of recurrent state outside its pages (conv layers), which "
            "no page carries: its pages are not shared, exported, "
            "spliced or handed off"
        )

    @property
    def share_tokens(self) -> int:
        """Prompt positions one SHAREABLE page stands for: a page of
        exact K/V, or a page of summaries (``P`` chunks)."""
        return self.page_tokens * (self.chunk if self.window else 1)

    @property
    def share_quantum(self) -> int:
        """Shareable pages a prefix hit comes in: whole windows only
        (a window's exact K/V is of no use once the window is past,
        so a hit must end where a window ends)."""
        return self.window // self.share_tokens if self.window else 1

    def share_slot(self, i: int) -> int:
        """Table entry of the row's ``i``-th shareable page."""
        return self.window_pages + i

    def table_len(self, max_len: int) -> int:
        """Page-table length of a row of up to ``max_len`` positions."""
        if not self.window:
            return self.ring_pages + pages_for(max_len, self.page_tokens)
        return self.window_pages + pages_for(
            max_len // self.chunk, self.page_tokens
        )

    def write_slots(self, first_pos: int, last_pos: int) -> List[int]:
        """Table entries that writing positions ``[first_pos,
        last_pos]`` touches, in order of first touch."""
        p = self.page_tokens
        if last_pos < first_pos:
            return []
        pages = range(first_pos // p, last_pos // p + 1)
        if not self.window:
            # the history's; a ring's pages are resident
            return [self.ring_pages + v for v in pages]
        # consecutive pages of positions are consecutive ring entries
        ring = [
            v % self.window_pages
            for v in pages[:min(len(pages), self.window_pages)]
        ]
        # chunks that END inside the span get their summary written
        chunks = range(first_pos // self.chunk,
                       (last_pos + 1) // self.chunk)
        sums = (
            range(chunks[0] // p, chunks[-1] // p + 1) if chunks else ()
        )
        return ring + [self.window_pages + i for i in sums]

    def live_slots(self, kv_end: int) -> List[int]:
        """Table entries whose pages a later step can still READ once
        positions ``[0, kv_end)`` are written: what a migration has to
        carry.  A past window's ring pages are dead."""
        p = self.page_tokens
        if not self.window:
            # of a ring, the pages of the positions the next query sees
            seen = range(
                max(0, kv_end - self.sliding_window + 1) // p,
                pages_for(kv_end, p),
            ) if self.ring_pages else ()
            return sorted({v % self.ring_pages for v in seen}) + [
                self.ring_pages + v for v in range(pages_for(kv_end, p))
            ]
        ring = pages_for(kv_end % self.window, p)
        sums = pages_for(kv_end // self.chunk, p)
        return list(range(ring)) + [
            self.window_pages + i for i in range(sums)
        ]

    def worst_case_pages(self, prompt_len: int, max_new: int,
                         cached_pages: int = 0) -> int:
        """Private pages a request can ever write, behind
        ``cached_pages`` shared ones."""
        return len(self.write_slots(
            cached_pages * self.share_tokens, prompt_len + max_new - 2
        ))

    def entries(self, positions: int) -> int:
        """Cache ENTRIES the next step of a row reads when ``positions``
        are behind it: every one (in a full layer; what a window layer
        reads beside them is ``window_entries``), or the current
        window's and one a chunk of the windows before."""
        if not self.window:
            return positions
        return positions % self.window + (
            positions // self.window
        ) * (self.window // self.chunk)

    def rollovers(self, first_pos: int, last_pos: int) -> int:
        """Window ends crossed by writing ``[first_pos, last_pos]``."""
        if not self.window or last_pos < first_pos:
            return 0
        return last_pos // self.window - max(first_pos - 1, 0) // self.window

    def summaries(self, first_pos: int, last_pos: int) -> int:
        """Chunk summaries written with ``[first_pos, last_pos]``."""
        if not self.window or last_pos < first_pos:
            return 0
        return (last_pos + 1) // self.chunk - first_pos // self.chunk


def _model_file(env) -> dict:
    """What the configuration file ``MODEL_CONFIG`` names states, under
    its published key names; {} where the env names none."""
    import json

    path = env.get("MODEL_CONFIG", "")
    if not path:
        return {}
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def layout_from_env(env, page_tokens: int) -> RowLayout:
    """The row layout the env's model asks for: the window and chunk of
    a ``MODEL_CONFIG`` file whose ``attention_class`` is "eva" (read
    here as JSON, by the file's published key names, so this module stays
    jax-free), else every token for ever, beside the state that the
    file's conv layers keep outside the pages."""
    data = _model_file(env)
    if not data:
        return RowLayout(page_tokens)
    if data.get("attention_class") != "eva":
        types = data.get("layer_types") or ()
        if "sliding_attention" in types:
            # the ring's size follows the prefill chunk (``with_chunk``)
            return RowLayout(
                page_tokens, sliding_window=int(data["sliding_window"])
            )
        # a conv layer's state, at the 2 bytes an element the chip
        # serves in (the pool states what its arena really holds)
        n_conv = sum(t == "conv" for t in types)
        return RowLayout(page_tokens, state_bytes_per_row=(
            n_conv * (int(data.get("conv_L_cache", 3)) - 1)
            * int(data["hidden_size"]) * 2 if n_conv else 0
        ))
    return RowLayout(
        page_tokens, window=int(data["window_size"]),
        chunk=int(data["chunk_size"]),
    )


# a chip's bf16 FLOP/s over its HBM bytes/s: 197e12 / 819e9 on a TPU
# v5e.  One constant and no table by device kind, because the scheduler
# that checks a spec has no device: every mixture chooses the ceiling
# below at any ratio from 140 up, and a dense model 256 from 129 to 256
# (a v5p reads 166; on a v6e, 560, it would choose 512)
_CHIP_FLOPS_PER_BYTE = 240
# the widest chunk the code chooses for itself: every live row's next
# token waits behind a chunk (20 ms at 512 tokens against a 4-9 ms
# decode step: PERF.md section 6, PR 36), and it is the widest any
# cell has run
_CHOSEN_CHUNK_CEILING = 512


def chunk_weights_from_env(env) -> Tuple[int, int]:
    """(``read``, ``per_token``): the weight elements a prefill chunk
    READS whatever its width (each layer's attention or conv
    projections, its router and EVERY expert, or its dense FFN), and
    those one token MULTIPLIES (the same with ``top_k`` experts in
    place of all).  From the size names ``models.config_from_env``
    reads, under its defaults, and the published keys of a
    ``MODEL_CONFIG`` file, which win; jax-free, so the scheduler's spec
    check and every worker reckon alike
    (tests/test_prefill_chunk_choice.py holds ``read`` to the tree
    ``init_params`` builds)."""
    data = _model_file(env)
    sizes = {
        "hidden_size": int(env.get("D_MODEL", "512")),
        "num_hidden_layers": int(env.get("N_LAYERS", "4")),
        "num_attention_heads": int(env.get("N_HEADS", "8")),
        "num_key_value_heads": int(env.get("N_KV_HEADS", "8")),
        "intermediate_size": int(env.get("D_FF", "1408")),
        "num_local_experts": int(env.get("N_EXPERTS", "0")),
        # what only a file states (TransformerConfig's defaults)
        "num_experts_per_tok": 2, "num_dense_layers": 0,
        "moe_intermediate_size": 0, "conv_L_cache": 3,
    }
    sizes.update(
        {key: int(data[key]) for key in sizes if data.get(key) is not None}
    )
    d, d_ff = sizes["hidden_size"], sizes["intermediate_size"]
    heads, kv_heads = (
        sizes["num_attention_heads"], sizes["num_key_value_heads"]
    )
    # a second published name for the same field wins where both stand
    experts = int(data.get("num_experts", sizes["num_local_experts"]))
    operators = (
        data.get("layer_types")
        or ("full_attention",) * sizes["num_hidden_layers"]
    )
    head_dim = int(data.get("head_dim") or d // heads)
    # wq and wo (and the output gate's wg), wk and wv
    attention = (
        (3 if data.get("attention_gate") else 2) * d * heads * head_dim
        + 2 * d * kv_heads * head_dim
    )
    shared = int(data.get("num_shared_experts") or 0)
    mixer = {
        "full_attention": attention, "sliding_attention": attention,
        # the three gates' in-projection, the taps, the out-projection
        "conv": 3 * d * d + d * sizes["conv_L_cache"] + d * d,
    }
    expert = 3 * d * (sizes["moe_intermediate_size"] or d_ff)
    moe_layers = (
        max(0, len(operators) - sizes["num_dense_layers"])
        if experts > 0 else 0
    )
    read = (
        sum(mixer[operator] for operator in operators)
        + (len(operators) - moe_layers) * 3 * d * d_ff
        + moe_layers * (d * experts + (experts + shared) * expert)
    )
    idle = max(0, experts - sizes["num_experts_per_tok"])
    per_token = read - moe_layers * idle * expert
    return read, per_token


def chosen_chunk_tokens(
    read: int, per_token: int, layout: RowLayout, max_len: int,
) -> int:
    """The prefill chunk's width where the env states none.

    A chunk of T tokens is bound by its READ of the weights until
    T x 2 x ``per_token`` FLOPs take as long as ``read`` x 2 bytes:
    until T = ``read`` / ``per_token`` x the chip's FLOPs a byte.
    Under that width a wider chunk costs far less than the chunks it
    saves (on a v5e 64 -> 512 tokens took a mixture's chunk from 12.0
    to 20.6 ms and from 14.4 to 19.4 ms, for a fifth of the calls:
    PERF.md section 6, PR 36), so: the smallest power of two at or
    above it, held to what the geometry serves: at most
    ``_CHOSEN_CHUNK_CEILING``, ``max_len`` and one window, in whole
    pages and whole layout chunks (one page at the least)."""
    ridge = -(-read * _CHIP_FLOPS_PER_BYTE // per_token)
    width = 1 << (ridge - 1).bit_length()
    width = min(width, _CHOSEN_CHUNK_CEILING, max_len,
                layout.window or width)
    # a windowed layout keeps one chunk a page (RowLayout)
    unit = layout.page_tokens
    return max(unit, width - width % unit)


@dataclass
class PagedServeConfig:
    """The env -> paged-serving geometry contract (one source for
    workers, shardcheck, and the admission gate)."""

    page_tokens: int       # KV positions per page
    pages: int             # usable pages (trash page NOT included)
    chunk_tokens: int      # prefill chunk width (the compile width)
    max_len: int           # virtual per-request position cap
    slots: int             # max concurrent decode rows
    prefix_cache: bool     # share read-only prompt pages
    layout: Optional[RowLayout] = None   # None: full attention
    # what chunk_tokens was chosen from (chunk_weights_from_env) where
    # the code chose it; None: the env stated PREFILL_CHUNK_TOKENS
    chunk_weights: Optional[Tuple[int, int]] = None

    def __post_init__(self) -> None:
        if self.layout is None:
            self.layout = RowLayout(self.page_tokens)

    @property
    def pages_per_row(self) -> int:
        """Page-table length per request row."""
        return self.layout.table_len(self.max_len)

    @property
    def arena_pages(self) -> int:
        """Physical arena size: usable pages + the trash page."""
        return self.pages + 1

    @property
    def window_arena_pages(self) -> int:
        """Pages of the window layers' arena: every slot's ring and
        the trash page; 0 where the model has no window layer."""
        ring = self.layout.ring_pages
        return self.slots * ring + 1 if ring else 0

    @property
    def chunk_source(self) -> str:
        """Who fixed ``chunk_tokens``: "env" or "model"."""
        return "env" if self.chunk_weights is None else "model"

    @property
    def chunk_stats(self) -> dict:
        """What a chosen width was chosen from, as ``/stats``' ``model``
        carries it ({} where the env stated the width)."""
        if self.chunk_weights is None:
            return {}
        read, per_token = self.chunk_weights
        return {"chunk_read_weights": read, "chunk_token_weights": per_token}

    @property
    def chunk_note(self) -> str:
        """The chunk width, who fixed it and from what: for a worker's
        start-up line."""
        if self.chunk_weights is None:
            return f"chunk {self.chunk_tokens} ({self.chunk_source})"
        read, per_token = self.chunk_weights
        return (
            f"chunk {self.chunk_tokens} ({self.chunk_source}: a chunk "
            f"reads {read} weights, a token multiplies {per_token})"
        )


def paged_config_from_env(env) -> PagedServeConfig:
    """Derive the paged-serving geometry from a task env.  Raises
    ``SpecError`` for a geometry that cannot serve (so admission and
    CI reject the spec and a worker fails deploy loudly)."""
    from dcos_commons_tpu.specification.specs import SpecError

    page_tokens = int(env.get("KV_PAGE_TOKENS") or "16")
    if page_tokens < 1:
        raise SpecError(
            f"KV_PAGE_TOKENS must be >= 1, got {page_tokens}: the slot "
            "pool that 0 selected is gone, serving.kv_page_tokens is "
            "only the arena's page size"
        )
    max_len = int(env.get("MAX_LEN", "256"))
    # unset SERVE_BATCH means a bare/dev launch; fall back to one
    # slot rather than the deploy default 8 (see options.json
    # serving.batch description)
    # sdklint: disable=config-default-drift — dev fallback
    batch = int(env.get("SERVE_BATCH", "1"))
    slots = int(env.get("SERVE_SLOTS") or 0) or batch
    # default budget = full residency for every row (NO overcommit:
    # SERVE_SLOTS rows of MAX_LEN positions); operators lower
    # KV_PAGES below slots x pages_per_row to overcommit on the mean
    # request, or raise SERVE_SLOTS at fixed KV_PAGES for free
    # concurrency on short traffic
    try:
        layout = layout_from_env(env, page_tokens)
    except (OSError, ValueError, KeyError) as e:
        raise SpecError(f"MODEL_CONFIG does not give a row layout: {e}")
    # a window layer's ring is its slot's and outside the page budget
    per_row = layout.table_len(max_len) - layout.ring_pages
    pages = int(env.get("KV_PAGES") or 0) or slots * per_row
    # unset (or 0): the code chooses the width from the model that the
    # same env describes; a stated width is the operator's and is held
    # to the same checks as ever
    chunk = int(env.get("PREFILL_CHUNK_TOKENS") or 0)
    if chunk < 0:
        raise SpecError(
            f"PREFILL_CHUNK_TOKENS must be >= 1, got {chunk}"
        )
    weights = None
    if not chunk:
        try:
            weights = chunk_weights_from_env(env)
            chunk = chosen_chunk_tokens(*weights, layout, max_len)
        except (OSError, ValueError, KeyError, ZeroDivisionError) as e:
            raise SpecError(
                f"the env's model sizes give no prefill chunk width: {e!r}"
            )
    if layout.window and (chunk % layout.chunk or chunk > layout.window):
        raise SpecError(
            f"PREFILL_CHUNK_TOKENS {chunk} must be a whole number of "
            f"{layout.chunk}-position chunks and at most one window "
            f"({layout.window})"
        )
    try:
        layout = layout.with_chunk(chunk)
    except ValueError as e:
        raise SpecError(f"PREFILL_CHUNK_TOKENS {chunk}: {e}")
    need_one = layout.worst_case_pages(max_len, 0)
    if pages < need_one:
        raise SpecError(
            f"KV page budget overcommitted: {pages} pages x "
            f"{page_tokens} tokens cannot hold one MAX_LEN={max_len} "
            f"request ({need_one} pages worst-case) — raise "
            f"serving.kv_pages or lower MAX_LEN"
        )
    prefix = (env.get("PREFIX_CACHE", "1") or "1") not in ("0", "false")
    # pages that do not say all a row has seen are never shared
    prefix = prefix and not layout.carries_state
    return PagedServeConfig(
        page_tokens=page_tokens, pages=pages, chunk_tokens=chunk,
        max_len=max_len, slots=slots, prefix_cache=prefix,
        layout=layout, chunk_weights=weights,
    )


class _PrefixEntry:
    """One cached read-only prompt page in the exact-match chain."""

    __slots__ = ("eid", "key", "page", "refs", "children")

    def __init__(self, eid: int, key: tuple, page: int):
        self.eid = eid
        self.key = key          # (parent_eid, page-token tuple)
        self.page = page
        self.refs = 0           # active requests reading this page
        self.children = 0       # resident entries chained below


class Admission:
    """The allocator's answer to one admitted request: the pinned
    prefix-chain entries plus the reservation the request draws its
    private pages from."""

    __slots__ = ("matched", "reserve_left", "chain_tail", "chain_open")

    def __init__(self, matched: List[_PrefixEntry], need: int):
        self.matched = matched
        self.reserve_left = need     # un-allocated reservation remainder
        # registration chains onto the last matched entry; a register
        # that finds its key already published closes the chain (the
        # canonical entry belongs to another request)
        self.chain_tail: Optional[_PrefixEntry] = (
            matched[-1] if matched else None
        )
        self.chain_open = True

    @property
    def cached_pages(self) -> int:
        return len(self.matched)


class PageAllocator:
    """Free-list page allocator + prefix cache + admission budget.

    Single-threaded by contract: every call happens on the engine's
    loop thread (or under the engine's cv for stats) — the same
    discipline as the engine's other bookkeeping.  All page ids are
    in ``[1, pages]``; 0 is the trash page and is never owned.

    Core invariant (the budget soundness the property tests hold):
    ``reserved <= available()`` at every step, where ``available`` is
    free pages plus evictable zero-ref cache leaves — so an alloc
    drawn from a reservation can NEVER fail mid-generation.
    """

    def __init__(self, pages: int, page_tokens: int,
                 prefix_cache: bool = True,
                 layout: Optional[RowLayout] = None):
        if pages < 1:
            raise ValueError(f"page arena needs >= 1 page, got {pages}")
        if page_tokens < 1:
            raise ValueError(
                f"pages need >= 1 token, got {page_tokens}"
            )
        self.pages_total = pages
        self.page_tokens = page_tokens
        self.layout = layout if layout is not None else RowLayout(page_tokens)
        self._prefix_enabled = prefix_cache
        self._free: List[int] = list(range(pages, 0, -1))  # pop -> 1
        self._free_set = set(self._free)
        self._reserved = 0
        self._entries: Dict[tuple, _PrefixEntry] = {}
        self._by_id: Dict[int, _PrefixEntry] = {}
        self._cache_pages = set()  # pages owned by cache entries
        self._lru: "OrderedDict[int, _PrefixEntry]" = OrderedDict()
        # zero-ref entries: ALL reclaimable.  Matching pins whole
        # prefix chains (root-first) and retire unpins them whole, so
        # refcounts are monotone down a chain — a zero-ref entry's
        # entire subtree is zero-ref and leaf-first eviction reaches
        # it transitively.  The LRU holds only the current leaves;
        # this counter is the admission-budget view
        self._zero_refs = 0
        self._next_eid = 1
        # telemetry
        self.prefix_lookups = 0    # prompt pages eligible for a hit
        self.prefix_hits = 0       # prompt pages served from cache
        self.evictions = 0

    def reset(self) -> None:
        """Drop every ownership and cache entry (the engine's
        fail-all path: all admissions died with their groups and the
        arena's contents are no longer trustworthy).  Telemetry
        counters survive — a reset is not a statistics amnesty."""
        self._free = list(range(self.pages_total, 0, -1))
        self._free_set = set(self._free)
        self._reserved = 0
        self._entries.clear()
        self._by_id.clear()
        self._cache_pages.clear()
        self._lru.clear()
        self._zero_refs = 0

    # -- budget ------------------------------------------------------

    def available(self) -> int:
        """Pages an admission may draw on: free + zero-ref cache
        entries (all transitively evictable, leaf-first — see
        ``_zero_refs``)."""
        return len(self._free) + self._zero_refs

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Resident prefix-cache pages (pinned + reclaimable)."""
        return len(self._by_id)

    @property
    def reclaimable_pages(self) -> int:
        return self._zero_refs

    @property
    def reserved_pages(self) -> int:
        return self._reserved

    def _match_and_need(self, prompt: Sequence[int], max_new: int):
        """The ONE admission formula (shared by ``admit`` and
        ``would_admit`` so the budget decision and the 503 timeout
        classification can never drift): match the prefix chain and
        compute (matched entries, lookup cap, worst-case private-page
        need, budget charge incl. pins of zero-ref entries).  The hit
        is capped so >= 1 prompt token is always prefilled privately:
        the model output at the LAST prompt position is what samples
        the first token — a fully-cached prompt still needs that
        forward pass."""
        plen = len(prompt)
        # a shareable page stands for ``p`` prompt positions, and a
        # hit comes in whole quanta of them (serve/paging.py
        # RowLayout: one page, or one window's summary pages)
        p = self.layout.share_tokens
        quantum = self.layout.share_quantum
        limit = (plen - 1) // p
        limit -= limit % quantum
        matched: List[_PrefixEntry] = []
        if self._prefix_enabled and limit > 0:
            parent_eid = 0
            for i in range(limit):
                key = (parent_eid, tuple(prompt[i * p:(i + 1) * p]))
                entry = self._entries.get(key)
                if entry is None:
                    break
                matched.append(entry)
                parent_eid = entry.eid
            del matched[len(matched) - len(matched) % quantum:]
        need = self.layout.worst_case_pages(plen, max_new, len(matched))
        # pinning a zero-ref entry removes it from ``available``, so
        # the admission check must charge for those pins too
        charge = need + sum(1 for e in matched if e.refs == 0)
        return matched, limit, need, charge

    def admit(
        self, prompt: Sequence[int], max_new: int,
    ) -> Optional[Admission]:
        """Transactional admission: match the prefix cache, compute
        the worst-case private-page need, and admit only if it fits.

        Returns ``None`` (leave the request queued, nothing mutated)
        when the budget cannot cover it.  On success the matched
        entries are PINNED and the need RESERVED (an admission must
        never push ``reserved`` past ``available``)."""
        matched, limit, need, charge = self._match_and_need(
            prompt, max_new
        )
        if charge + self._reserved > self.available():
            return None
        # count the hit telemetry only for ADMITTED requests ("nothing
        # mutated" on the None return): a budget-blocked head is
        # re-attempted every engine tick, and counting those retries
        # would drown prefix_cache_hit_rate in retry noise exactly
        # when the arena is saturated
        if self._prefix_enabled and limit > 0:
            self.prefix_lookups += limit
            self.prefix_hits += len(matched)
        for entry in matched:
            self._pin(entry)
        self._reserved += need
        return Admission(matched, need)

    def would_admit(self, prompt: Sequence[int], max_new: int) -> bool:
        """The admission check WITHOUT side effects (the submit-path
        timeout uses it to name the blocking resource)."""
        _, _, _, charge = self._match_and_need(prompt, max_new)
        return charge + self._reserved <= self.available()

    # -- page movement -----------------------------------------------

    def alloc(self, admission: Admission) -> int:
        """Hand one page to an admitted request, drawn from its
        reservation (evicting a zero-ref cache leaf if the free list
        is dry).  A reservation underflow or an empty arena here is an
        ENGINE bug — the admission check exists to make it
        impossible — so it raises instead of limping."""
        if admission.reserve_left <= 0:
            raise RuntimeError(
                "page alloc past the admission's worst-case reservation"
            )
        if not self._free:
            self._evict_one()
        page = self._free.pop()
        self._free_set.discard(page)
        admission.reserve_left -= 1
        self._reserved -= 1
        return page

    def free_page(self, page: int) -> None:
        """Return a PRIVATE page (double-free and trash/cache-page
        frees raise: each is a table-corruption bug upstream)."""
        if page == TRASH_PAGE or not 1 <= page <= self.pages_total:
            raise RuntimeError(f"freeing invalid page {page}")
        if page in self._free_set:
            raise RuntimeError(f"double free of page {page}")
        if page in self._cache_pages:
            raise RuntimeError(
                f"freeing page {page} owned by the prefix cache"
            )
        self._free.append(page)
        self._free_set.add(page)

    def retire(self, admission: Admission,
               private_pages: Sequence[int]) -> None:
        """Release everything one request held: the un-consumed
        reservation, its private pages, and its pins (matched AND
        self-registered entries — a registered page must stay pinned
        while its registrant can still gather from it)."""
        self._reserved -= admission.reserve_left
        admission.reserve_left = 0
        for page in private_pages:
            self.free_page(page)
        for entry in admission.matched:
            self._unpin(entry)

    # -- prefix cache ------------------------------------------------

    def register(
        self, admission: Admission, page_tokens: Tuple[int, ...],
        page: int,
    ) -> bool:
        """Publish one fully-prefilled PRIVATE prompt page into the
        cache, chained onto the request's current tail.  Ownership of
        ``page`` transfers to the cache; the registrant keeps a pin
        until retire (``admission.matched`` grows the new entry).

        Returns False (page stays private) when the chain is closed
        or the key already exists — a concurrent identical prompt
        published first; this request keeps its duplicate private and
        the canonical entry serves future hits.  Once closed, the
        chain stays closed: deeper pages cannot chain onto another
        request's entry without pinning machinery admission never
        budgeted for."""
        if not self._prefix_enabled or not admission.chain_open:
            return False
        if len(page_tokens) != self.layout.share_tokens:
            raise RuntimeError(
                f"registering a partial page ({len(page_tokens)} of "
                f"{self.layout.share_tokens} tokens)"
            )
        parent = admission.chain_tail
        key = ((parent.eid if parent else 0), tuple(page_tokens))
        if key in self._entries:
            admission.chain_open = False
            return False
        entry = _PrefixEntry(self._next_eid, key, page)
        self._next_eid += 1
        entry.refs = 1  # the registrant's pin, released at retire
        if parent is not None:
            # parent is pinned by this request (matched or registered
            # earlier in this chain): refs >= 1, so it cannot be in
            # the LRU and gaining a child never shrinks ``available``
            parent.children += 1
        self._entries[key] = entry
        self._by_id[entry.eid] = entry
        self._cache_pages.add(page)
        admission.matched.append(entry)
        admission.chain_tail = entry
        return True

    def _pin(self, entry: _PrefixEntry) -> None:
        if entry.refs == 0:
            self._zero_refs -= 1
        entry.refs += 1
        self._lru.pop(entry.eid, None)

    def _unpin(self, entry: _PrefixEntry) -> None:
        entry.refs -= 1
        if entry.refs < 0:
            raise RuntimeError(f"refcount underflow on entry {entry.eid}")
        if entry.refs == 0:
            self._zero_refs += 1
            if entry.children == 0:
                self._lru[entry.eid] = entry
                self._lru.move_to_end(entry.eid)

    def _evict_one(self) -> None:
        if not self._lru:
            raise RuntimeError(
                "page arena empty with nothing evictable (budget "
                "invariant violated)"
            )
        _eid, entry = self._lru.popitem(last=False)  # oldest leaf
        del self._entries[entry.key]
        del self._by_id[entry.eid]
        self._cache_pages.discard(entry.page)
        self._zero_refs -= 1  # lru membership implies refs == 0
        parent_eid = entry.key[0]
        if parent_eid:
            parent = self._by_id.get(parent_eid)
            if parent is not None:
                parent.children -= 1
                if parent.refs == 0 and parent.children == 0:
                    self._lru[parent.eid] = parent
        self._free.append(entry.page)
        self._free_set.add(entry.page)
        self.evictions += 1

    # -- introspection (tests + stats) -------------------------------

    def check_invariants(self, private_pages: Sequence[int] = ()) -> None:
        """Conservation + budget soundness; the property tests call
        this after every op.  ``private_pages``: every page currently
        owned by live requests (the engine's tables)."""
        cached = {e.page for e in self._by_id.values()}
        private = list(private_pages)
        if len(cached) != len(self._by_id):
            raise AssertionError("two cache entries share a page")
        if len(set(private)) != len(private):
            raise AssertionError("two requests own the same page")
        if set(private) & cached:
            raise AssertionError("a private page is also cache-owned")
        if set(private) & self._free_set or cached & self._free_set:
            raise AssertionError("an owned page is on the free list")
        total = len(self._free) + len(cached) + len(private)
        if total != self.pages_total:
            raise AssertionError(
                f"page conservation broken: {len(self._free)} free + "
                f"{len(cached)} cached + {len(private)} private != "
                f"{self.pages_total}"
            )
        if self._reserved < 0:
            raise AssertionError("negative reservation")
        if self._reserved > self.available():
            raise AssertionError(
                f"reserved {self._reserved} > available "
                f"{self.available()}: an admitted request can OOM"
            )
        zero = 0
        for entry in self._by_id.values():
            zero += entry.refs == 0
            evictable = entry.refs == 0 and entry.children == 0
            if evictable != (entry.eid in self._lru):
                raise AssertionError(
                    f"entry {entry.eid} LRU membership inconsistent "
                    f"(refs={entry.refs}, children={entry.children})"
                )
            parent_eid = entry.key[0]
            if parent_eid and entry.refs > 0:
                parent = self._by_id.get(parent_eid)
                if parent is None or parent.refs <= 0:
                    raise AssertionError(
                        f"pinned entry {entry.eid} has an unpinned/"
                        "evicted parent (chain-pin monotonicity broken)"
                    )
        if zero != self._zero_refs:
            raise AssertionError(
                f"zero-ref count drifted: {self._zero_refs} tracked, "
                f"{zero} actual"
            )

    def stats(self) -> dict:
        lookups = self.prefix_lookups
        return {
            "kv_pages_total": self.pages_total,
            "kv_pages_free": len(self._free),
            "kv_pages_cached": len(self._by_id),
            # all zero-ref entries, matching the admission view — not
            # just the current LRU leaves (a zero-ref CHAIN is
            # transitively evictable, and the gauge must agree with
            # what available() would actually hand an admission)
            "kv_pages_reclaimable": self._zero_refs,
            "kv_pages_reserved": self._reserved,
            "prefix_cache_hits": self.prefix_hits,
            "prefix_cache_lookups": lookups,
            "prefix_cache_evictions": self.evictions,
            "prefix_cache_hit_rate": round(
                self.prefix_hits / lookups, 4
            ) if lookups else 0.0,
        }
