"""Continuous-batching engine (the model-agnostic half): per-step
scheduling over a paged KV arena.

``PagedEngine`` admits waiting requests at EVERY tick and retires
finished rows (per-row EOS / max-token / cache-exhausted) at once, so
a new request's time-to-first-token is O(one decode tick + its own
prefill) and a short answer never pads out to the longest row.  KV
memory is a fixed budget of pages handed out through per-request page
tables (serve/paging.py: admission-time reservation, refcounted
prefix cache, what a table entry stands for), prompts prefill one
chunk per row per tick between decode steps, and shapes are static:
XLA never recompiles as occupancy changes.

The engine is jax-free: the device half is two injected callables
(the single-chip server binds them straight to a
``serve.pool.PagedPoolModel``; the gang driver wraps them in
ADMIT/DECODE broadcast ticks so every rank steps the same program).
Liveness rules: FIFO admission, queue-timeout removal (abandoned work
never reaches the chip: an active abandoned row retires at the next
tick, freeing its row and pages early), and an ``on_idle`` hook so an
SPMD gang keeps meeting in collectives with no traffic.

Serving load telemetry: ``stats()`` reports queue depth, active
slots, KV occupancy, tokens/s and TTFT percentiles, and
``stats_path`` mirrors them to ``servestats.json`` in the task
sandbox, where the scheduler's ``GET /v1/debug/serving`` collects
them per pod — the load signal ROADMAP item 2 names for scale-out
decisions.

The loop runs ONE DEVICE CALL AHEAD (ISSUE 31): a tick dispatches
the next decode step and only then resolves the one before it, and a
prefill chunk is fetched only when it is its prompt's last.  What a
step needs of the step before it is known without reading that
step's tokens (a continuing row's position is ``pos + 1``, its pages
come from its reservation by position, and its input token is carried
on the device), so the device always has a program queued behind the
one that runs.  A device half that cannot carry a token on the device
(no ``resolve_decode_fn``: the gang driver, the tests' fakes) is the
same loop at depth 0: its step is resolved when the call returns.
Everything that reads a row's host state or fences it first resolves
and applies the outstanding step (``_drain``).

The engine's own timeline (ISSUE 24) has one instrumentation point at
each boundary of the loop and of a request's life, feeding three
sinks.  ``_phase(name)`` wraps the parts of the loop thread's work
that cost enough to ask about (``PHASES``): it adds the elapsed time
to a cumulative per-phase counter and closes an ``engine.<phase>``
host span through the injected ``annotate`` (the workers pass
``jax.profiler.TraceAnnotation``; the engine stays jax-free).  The
time between two phases goes to ``other``, so the counters partition
the loop thread's life exactly.  They and the per-request sums (queue
wait, prefill, decode) ride ``stats()`` under the one key ``loop``,
all cumulative: a reader windows them by the difference of two
samples.  With a ``trace.TraceRecorder`` of non-zero capacity the
engine also records ``engine.queue`` / ``engine.prefill`` /
``engine.decode`` under the caller's ``request`` span and one
``engine.tick`` per tick.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Callable, ContextManager, List, Optional, Sequence

import numpy as np

from dcos_commons_tpu.serve.paging import PageAllocator, RowLayout
from dcos_commons_tpu.trace import NULL_TRACER, Span, TraceRecorder

SERVESTATS_NAME = "servestats.json"
_TTFT_WINDOW = 512      # TTFT samples kept for the percentile gauges
_RATE_WINDOW_S = 10.0   # tokens/s sliding window

# where the loop thread's life goes.  ``wait`` is the loop parked on
# its cv (and the gang's idle tick); the two ``_call`` phases are the
# injected device callables, whose time is the device's and the
# blocking fetch's (``decode_call`` holds the wait for the PREVIOUS
# step's tokens, ``prefill_call`` the dispatch of an unfetched chunk);
# ``decode_prep`` / ``decode_apply`` the host's work
# on either side of the decode call, ``stats`` a ``servestats.json``
# write.  ``other`` is every instant outside a ``with`` block:
# admission, page IO, a chunk's padding and bookkeeping, the glue
PHASES = (
    "wait", "prefill_call", "decode_prep", "decode_call",
    "decode_apply", "stats", "other",
)
_NO_SPAN = contextlib.nullcontext()


class QueueTimeoutError(RuntimeError):
    """A request expired waiting for chip capacity in the engine's
    admission queue.  This is server SATURATION, not caller error:
    HTTP handlers map it to 503 so load generators and clients can
    tell overload apart from a 400 bad request.

    ``kind`` names the starved resource so operators can tell
    saturation-by-memory from saturation-by-compute in the 503 body
    and the split timeout counters (``stats()``):

    * ``kv-page-budget`` — the request's worst-case KV page need
      never fit the arena's budget (memory saturation: add
      pages/HBM, shrink MAX_LEN, or rely on prefix caching);
    * ``kv-slot`` — no decode row freed up (concurrency saturation);
    * ``stalled`` — admitted but the pool produced no new token for a
      full window (compute saturation or a wedged device).
    """

    def __init__(self, message: str = "", kind: str = "kv-slot"):
        super().__init__(message)
        self.kind = kind


class _Phase:
    """``with engine._phase(name)``: one reusable object a phase (the
    loop thread is the only user and a phase never nests).  Entry
    charges ``other`` with the time since the previous phase ended,
    exit charges the phase, so the counters leave no instant out."""

    __slots__ = ("_engine", "_name", "_span_name", "_span")

    def __init__(self, engine: "PagedEngine", name: str):
        self._engine = engine
        self._name = name
        self._span_name = "engine." + name
        self._span = _NO_SPAN

    def __enter__(self) -> None:
        engine = self._engine
        now = time.monotonic()
        engine._phase_s["other"] += now - engine._phase_t
        engine._phase_t = now
        self._span = engine._annotate(self._span_name)
        self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        engine = self._engine
        now = time.monotonic()
        engine._phase_s[self._name] += now - engine._phase_t
        engine._phase_t = now
        return False


class _Group:
    """One ``submit()`` call: N rows answered together."""

    __slots__ = ("rows", "remaining", "done", "error", "abandoned")

    def __init__(self, rows: List["_Row"]):
        self.rows = rows
        self.remaining = len(rows)
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.abandoned = False


class _Row:
    """One prompt riding one decode row and its PAGE TABLE
    (serve/paging.py): ``table[v]`` is the physical arena page behind
    table entry ``v`` (what an entry stands for is the layout's to
    say); 0 = unallocated."""

    __slots__ = (
        "tokens", "n", "temp", "eos", "seed", "out", "group",
        "arrival", "slot", "rid", "frozen",
        "admit_t", "first_t", "first_tick", "chunks", "cached",
        "trace_id", "parent_id",
        "table", "fill_pos", "admission", "private_pages",
        "registered_to",
    )

    def __init__(self, tokens, n, temp, eos, seed, group):
        self.tokens = tokens
        self.n = n
        self.temp = temp
        self.eos = eos
        self.seed = seed
        self.out: List[int] = []
        self.group = group
        self.arrival = time.monotonic()
        self.slot = -1
        # migration identity/fence (serve/migration.py, ISSUE 16):
        # rid is the pod-local session id; a frozen row holds its
        # slot and pages but is excluded from every dispatch until
        # unfrozen, released to a peer, or activated after a splice
        self.rid = -1
        self.frozen = False
        # the timeline: when the slot (and page budget) was granted
        # and when the first token landed, on the arrival's clock; 0.0
        # = not yet, and a spliced-in migrated row never gets an
        # admit_t, which keeps it out of the per-request sums
        self.admit_t = 0.0
        self.first_t = 0.0
        self.first_tick = 0    # the engine's decode calls at first_t
        self.chunks = 0        # prefill calls this row took
        self.cached = 0        # prompt pages the prefix cache served
        self.trace_id = 0      # the request's trace (0 = recorder off)
        self.parent_id = 0     # the caller's ``request`` span
        self.table = None            # np.int32 [M], built at admission
        self.fill_pos = 0            # next prompt position to prefill
        self.admission = None        # paging.Admission while admitted
        self.private_pages: List[int] = []
        self.registered_to = 0       # next prompt page to publish


class PagedEngine:
    """Continuous batching over a PAGED KV arena: block-granular
    allocation, chunked prefill, and prefix caching (vLLM's
    PagedAttention + SGLang's RadixAttention shape).

    * **Admission is page-budgeted** (serve/paging.py): a request
      enters the pool only when a free decode row exists AND its
      worst-case page need fits ``available - reserved`` — admitted
      work can never OOM mid-generation, and a short reply returns
      its unused pages immediately instead of stranding a MAX_LEN
      row.  FIFO stays strict: a budget-blocked head is never jumped
      by a smaller later request.
    * **Prefill is chunked**: prompts run ``chunk_tokens`` at a time
      — one chunk per PREFILLING REQUEST per engine tick, interleaved
      with decode — so a long prompt does not block the tick it
      rides and queued requests do not pay head-of-line TTFT.
      Chunk progress counts as progress for the 503 timeout (a long
      prefill is not a stall).
    * **Prefix caching**: fully-prefilled prompt pages are published
      read-only; an identical later prefix pins them instead of
      recomputing (COW-by-recompute on mid-page divergence — shared
      pages are never written; see serve/paging.py).

    ``prefill_chunk_fn(padded [1, C] i32, slot=, table= [M] i32,
    start=, true_len=, temp=, seed=) -> first token`` runs one chunk
    (the return value is consumed only when the chunk completes the
    prompt; the scalars are passed by KEYWORD — transposing two of
    them is a silent cache corruption); ``decode_fn(tok [S], pos [S],
    temps [S], seeds [S], tables [S, M] i32, n_active) -> next tokens
    [S]`` advances EVERY row one step through its page table
    (inactive and frozen rows ride a zero table: their writes land in
    the trash page and their samples are discarded).  Both run
    OUTSIDE the engine lock; only host-side bookkeeping holds it.

    With ``resolve_decode_fn`` (``PagedPoolModel.resolve_decode``) the
    device half runs one call ahead: ``prefill_chunk_fn`` is also told
    ``final=`` and fetches only a prompt's last chunk; ``decode_fn``
    is also given ``carry=`` (bool [S]: rows whose input token is the
    previous step's output, still on the device) and returns the
    PREVIOUS step's tokens, or an empty array when none was
    outstanding; ``resolve_decode_fn()`` fetches the outstanding step
    without dispatching another.  Without it the two callables are called as
    above and a step is resolved when its call returns.

    With ``chunk_riders`` beside it (``PagedPoolModel.chunk_riders``:
    the device half's chunk program carries a decode step) a tick that
    has a chunk hands its decode step to the tick's first chunk call,
    ``prefill_chunk_fn(..., riders={tok, pos, temps, seeds, tables,
    carry})``, which stands for the ``decode_fn`` call as well and
    returns ``(first token or None, the PREVIOUS step's tokens)``: one
    device program that tick, not two.
    """

    def __init__(
        self,
        prefill_chunk_fn: Callable,
        decode_fn: Callable,
        slots: int,
        max_len: int,
        prompt_len: int,
        *,
        page_tokens: int,
        pages: int,
        chunk_tokens: int,
        prefix_cache: bool = True,
        layout: Optional[RowLayout] = None,
        role: str = "unified",
        read_page: Optional[Callable] = None,
        write_page: Optional[Callable] = None,
        handoff: Optional[Callable] = None,
        resolve_decode_fn: Optional[Callable] = None,
        chunk_riders: bool = False,
        device_counters: Optional[Callable[[], dict]] = None,
        queue_timeout_s: float = 600.0,
        on_idle: Optional[Callable[[], None]] = None,
        idle_every_s: float = 0.05,
        stats_path: Optional[str] = None,
        stats_every_s: float = 1.0,
        log: Optional[Callable[[str], None]] = None,
        extra_stats: Optional[dict] = None,
        annotate: Optional[Callable[[str], ContextManager]] = None,
        tracer: TraceRecorder = NULL_TRACER,
    ):
        if slots < 1:
            raise ValueError(f"the pool needs >= 1 decode row, got {slots}")
        self._prefill_fn = prefill_chunk_fn
        self._decode_fn = decode_fn
        self._resolve_fn = resolve_decode_fn
        # a step rides a chunk only as a step dispatched ahead
        self._chunk_riders = bool(chunk_riders) and (
            resolve_decode_fn is not None
        )
        # cumulative sums the device half counted itself and fetched
        # with its steps' tokens (PagedPoolModel.loop_counters): they
        # join ``loop`` in ``stats()``
        self._device_counters = device_counters
        # decode steps dispatched and not yet applied, oldest first:
        # each is its ``dispatched`` rows by slot.  At most one between
        # ticks, and none without a ``resolve_decode_fn``.  Only the
        # loop thread changes it, under the cv
        self._inflight: deque = deque()
        self._slots = slots
        self._max_len = max_len
        self._prompt_len = prompt_len
        self._queue_timeout_s = queue_timeout_s
        self._on_idle = on_idle
        self._idle_every_s = idle_every_s
        self._stats_path = stats_path
        self._stats_every_s = stats_every_s
        self._log = log
        self._page_tokens = int(page_tokens)
        # what a table entry stands for (serve/paging.py RowLayout):
        # the one thing here that differs between attention classes
        self._layout = (
            layout if layout is not None else RowLayout(int(page_tokens))
        )
        if self._layout.page_tokens != self._page_tokens:
            raise ValueError(
                f"row layout has {self._layout.page_tokens}-token pages, "
                f"the arena {self._page_tokens}"
            )
        self._pages_per_row = self._layout.table_len(int(max_len))
        # pages that do not say all a row has seen are never shared,
        # and never travel (RowLayout.carries_state)
        prefix_cache = prefix_cache and not self._layout.carries_state
        self._prefix_cache = bool(prefix_cache)
        if handoff is not None and self._layout.carries_state:
            raise ValueError(
                "no prefill hand-off: " + self._layout.carries_state
            )
        self._chunk_tokens = int(chunk_tokens)
        if self._layout.window and (
            self._chunk_tokens % self._layout.chunk
            or self._chunk_tokens > self._layout.window
        ):
            raise ValueError(
                f"prefill chunks of {self._chunk_tokens} are not whole "
                f"{self._layout.chunk}-position chunks within one "
                f"window of {self._layout.window}"
            )
        self._allocator = PageAllocator(
            int(pages), int(page_tokens), prefix_cache,
            layout=self._layout,
        )
        self._prefilling: deque = deque()
        # migration state (serve/migration.py, ISSUE 16).  role is
        # the pod's advertised serving posture (unified / prefill /
        # decode) — telemetry and routing read it; the HANDOFF hook's
        # presence is what actually diverts finished prefills.
        # read_page/write_page are the device half of page mobility
        # (PagedPoolModel.export_page/import_page on real pods); both
        # run ONLY on the engine loop thread (_device_io), preserving
        # the single-device-caller discipline.
        self._role = str(role)
        self._read_page = read_page
        self._write_page = write_page
        self._handoff = handoff
        self._page_io: deque = deque()
        self._spliced: dict = {}    # rid -> parked row (pre-cutover)
        self._migrated: dict = {}   # rid -> spliced row (collectable)
        self._migrated_in = 0
        self._migrated_out = 0
        # the timeline's sinks: ``annotate(name)`` opens a host span
        # on the profiler's clock (jax.profiler.TraceAnnotation in the
        # workers: a flag test outside a profiler session), ``tracer``
        # is the span ring (capacity 0 = every call a no-op)
        self._annotate = (
            annotate if annotate is not None else (lambda name: _NO_SPAN)
        )
        self._tracer = tracer
        # loop-thread only (stats() copies them under the cv)
        self._phases = {
            name: _Phase(self, name) for name in PHASES if name != "other"
        }
        self._phase_s = dict.fromkeys(PHASES, 0.0)
        self._phase_t = time.monotonic()
        self._decode_calls = 0
        self._prefill_calls = 0
        # the loop one call ahead: decode calls dispatched while the
        # previous call's tokens were unread, chunks dispatched with no
        # fetch, and rows of a step whose sample was dropped because
        # the step before ended them
        self._decode_ahead_calls = 0
        self._prefill_unfetched_calls = 0
        self._ahead_discarded_rows = 0
        # chunk calls that carried a decode step with a live row in it
        self._prefill_rider_calls = 0
        # what the decode calls themselves computed for: rows, and the
        # cache entries those rows read (the gauges `active_slots` /
        # `kv_live_tokens` are instants, and the second also holds rows
        # still prefilling, which no decode call reads)
        self._decode_rows_sum = 0
        self._decode_entries_sum = 0
        # and the ring entries they read in a window layer (0 where
        # the model has none)
        self._decode_window_entries_sum = 0
        # a windowed row layout's events (serve/paging.py RowLayout);
        # both stay 0 where every token of a row is kept
        self._window_rollovers = 0
        self._summary_entries = 0
        self._tick_rows = 0  # rows the tick under way decodes for
        # per-request sums over rows that finished normally (cv)
        self._queue_wait_s_sum = 0.0
        self._prefill_s_sum = 0.0
        self._decode_s_sum = 0.0
        self._decode_tokens_sum = 0
        self._requests_timed = 0

        self._cv = threading.Condition()
        self._queue: deque = deque()
        self._rows: List[Optional[_Row]] = [None] * slots
        self._free = list(range(slots - 1, -1, -1))  # pop() -> slot 0 first
        self._active = 0
        self._tok = np.zeros(slots, np.int32)
        self._pos = np.zeros(slots, np.int32)
        self._temps = np.zeros(slots, np.float32)
        self._seeds = np.zeros(slots, np.int32)
        self._stopped = False
        self._next_rid = 1  # session ids (migration's addressing unit)
        # telemetry (counters under the cv; deques pruned on append)
        self._admitted = 0
        self._completed = 0
        self._timeouts = 0
        self._timeouts_by_kind: dict = {}
        self._tokens_out = 0
        self._ttft: deque = deque(maxlen=_TTFT_WINDOW)
        self._rate: deque = deque()  # (monotonic, tokens) per tick
        self._merge_logged = False
        self._stats_written = 0.0  # loop-thread only
        # stats consumers may annotate the snapshot with facts the
        # engine cannot know (the worker's actually-bound HTTP port:
        # the /v1/endpoints advertisement, ISSUE 12).  Constructor-
        # passed extras precede the loop thread's first flush, so the
        # sandbox snapshot carries them from its very first write
        self._extra_stats: dict = dict(extra_stats or {})
        # loop-liveness stamp for the stats_age_s gauge: the router
        # and HealthMonitor discard gauges whose engine stopped
        # ticking instead of balancing on a wedged pod's last-good
        # numbers.  Stamped at every loop wake AND at submit-time
        # enqueue (an idle engine is trivially responsive — its age
        # must start at the arrival, not at the end of the idle gap)
        self._last_tick_mono = time.monotonic()
        self._thread = threading.Thread(
            target=self._loop, name="slot-engine", daemon=True
        )
        self._thread.start()

    # -- client surface ----------------------------------------------

    def submit(
        self,
        rows: Sequence[Sequence[int]],
        max_new_tokens: int,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        trace_parent: Optional[Span] = None,
    ) -> List[List[int]]:
        """Queue ``rows`` (each its own slot, admitted independently
        as slots free up — a multi-row request may overlap several
        pool generations) and block until every row finished.  Raises
        ``QueueTimeoutError`` on saturation (handlers map it to 503),
        ``ValueError`` on caller error (400).  ``trace_parent`` is the
        caller's ``request`` span: the engine's spans of these rows
        nest under it and share its trace id."""
        if not rows:
            raise ValueError("tokens must be non-empty")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        for row in rows:
            if len(row) < 1:
                raise ValueError("prompts must be non-empty")
            if len(row) > self._prompt_len:
                raise ValueError(
                    f"prompt length {len(row)} exceeds the server's "
                    f"context {self._prompt_len}"
                )
            if len(row) + max_new_tokens > self._max_len:
                raise ValueError(
                    f"prompt {len(row)} + {max_new_tokens} new tokens "
                    f"cannot fit the {self._max_len}-position slot"
                )
        group = _Group([])
        group.rows = [
            _Row(
                [int(t) for t in row], max_new_tokens, float(temperature),
                eos_id,
                int.from_bytes(os.urandom(4), "little") % (2 ** 31),
                group,
            )
            for row in rows
        ]
        group.remaining = len(group.rows)
        if self._tracer.enabled:
            for r in group.rows:
                if trace_parent is not None and trace_parent.trace_id:
                    r.trace_id = trace_parent.trace_id
                    r.parent_id = trace_parent.span_id
                else:
                    r.trace_id = self._tracer.new_trace_id()
        with self._cv:
            now = time.monotonic()
            if not self._has_work_locked():
                # idle -> working transition: liveness is measured
                # from THIS arrival, not across the idle gap
                self._last_tick_mono = now
            for r in group.rows:
                r.rid = self._next_rid
                self._next_rid += 1
            self._queue.extend(group.rows)
            self._cv.notify_all()
        # the timeout bounds SATURATION, not a healthy generation: a
        # window with no row admitted (starved for a slot) or no new
        # token across the whole group (the pool stalled) abandons;
        # an admitted group that keeps producing is never cut off
        # mid-generation just for being long
        last_progress = -1
        while not group.done.wait(timeout=self._queue_timeout_s):
            with self._cv:
                admitted = any(r.slot >= 0 for r in group.rows)
                progress = self._progress_locked(group)
                if admitted and progress > last_progress:
                    last_progress = progress
                    continue
                # abandoned work never reaches the chip: queued rows
                # leave the queue NOW; already-active rows retire at
                # the next tick, freeing their slots early instead of
                # decoding a dead request to completion
                group.abandoned = True
                self._queue = deque(
                    r for r in self._queue if r.group is not group
                )
                reason, kind = self._timeout_reason_locked(
                    group, admitted
                )
                self._timeouts += 1
                self._timeouts_by_kind[kind] = (
                    self._timeouts_by_kind.get(kind, 0) + 1
                )
            raise QueueTimeoutError(reason, kind=kind)
        if group.error is not None:
            raise group.error
        return [list(r.out) for r in group.rows]

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        self._thread.join(timeout=10)

    def _progress_locked(self, group: _Group) -> int:
        """Monotone per-group progress measure for the timeout loop:
        tokens produced plus prompt positions prefilled (a long
        prompt mid-chunked-prefill IS making progress and must not be
        cut off as "stalled" just because no token landed yet)."""
        return sum(len(r.out) + r.fill_pos for r in group.rows)

    def _timeout_reason_locked(self, group, admitted: bool):
        """(reason string, QueueTimeoutError kind) for a timed-out
        group — the 503 body and the split timeout counters."""
        if admitted:
            return (
                f"no decode progress in {self._queue_timeout_s}s",
                "stalled",
            )
        alloc = self._allocator
        budget_reason = (
            "request timed out waiting for the KV page budget "
            f"({alloc.free_pages} pages free of "
            f"{alloc.pages_total}, {alloc.reserved_pages} "
            "reserved)",
            "kv-page-budget",
        )
        slot_reason = (
            "request timed out waiting for a KV slot", "kv-slot"
        )
        own = next(
            (r for r in group.rows if r.slot < 0), group.rows[0]
        )
        if not alloc.would_admit(own.tokens, own.n):
            return budget_reason
        if not self._free:
            return slot_reason
        # our own rows fit and decode rows are free, so the
        # starvation came from strict FIFO behind a blocked HEAD
        # (our rows left the queue before this ran): classify by
        # what blocks the head — a small request stuck behind a
        # big budget-blocked one is memory saturation too
        head = self._queue[0] if self._queue else None
        if head is not None and not alloc.would_admit(
                head.tokens, head.n):
            return budget_reason
        return slot_reason

    # -- telemetry ---------------------------------------------------

    def annotate_stats(self, **extra) -> None:
        """Attach static facts to every future ``stats()`` snapshot
        (the worker's actually-bound ``http_port``; anything the
        engine itself cannot know).  Keys must not collide with the
        engine's own gauges."""
        with self._cv:
            self._extra_stats.update(extra)

    def stats(self) -> dict:
        """Serving-load snapshot (the per-pod gauges ROADMAP item 2
        names as the scale-out signal)."""
        now = time.monotonic()
        with self._cv:
            # loop-liveness stamp: 0 while idle (a parked loop is
            # trivially responsive; admission wakes it), else the
            # time since the loop last proved alive — the wedge
            # signal the router's staleness gate keys on
            stats_age = (
                max(0.0, now - self._last_tick_mono)
                if self._has_work_locked() else 0.0
            )
            # positions behind each live row, decoding or prefilling
            positions = [
                int(self._pos[s])
                for s, row in enumerate(self._rows) if row is not None
            ] + [r.fill_pos for r in self._prefilling]
            live_tokens = sum(self._layout.entries(n) for n in positions)
            alloc = self._allocator
            window = [n for (t, n) in self._rate
                      if t > now - _RATE_WINDOW_S]
            ttft = sorted(self._ttft)
            kinds = self._timeouts_by_kind
            out = {
                "slots": self._slots,
                "max_len": self._max_len,
                "queue_depth": len(self._queue),
                "active_slots": self._active,
                "free_slots": len(self._free),
                # cache ENTRIES the live rows' next steps read (what
                # occupancy and a roofline need) and the positions
                # they stand for; equal where every token is kept
                "kv_live_tokens": live_tokens,
                "context_live_tokens": sum(positions),
                # what the window layers' rings hold of them that still
                # counts (0 where no layer keeps a window), and the
                # pages in use by pool: the rings' (a live row's whole
                # ring is its slot's) and the history's
                "kv_window_live_tokens": sum(
                    self._layout.window_entries(n) for n in positions
                ),
                "kv_window_pages_in_use": (
                    len(positions) * self._layout.ring_pages
                ),
                "kv_history_pages_in_use": (
                    alloc.pages_total - alloc.free_pages
                ),
                # PHYSICAL occupancy: shared prefix pages count
                # once, not once per pinning row — under heavy
                # sharing the virtual sum can exceed the arena and
                # would falsely breach kv_occupancy_slo while
                # headroom exists.  Occupied = pages neither free nor
                # reclaimable-by-admission.
                "kv_occupancy": round(
                    (alloc.pages_total - alloc.free_pages
                     - alloc.reclaimable_pages)
                    / float(alloc.pages_total),
                    4,
                ),
                "tokens_per_s": round(
                    sum(window) / _RATE_WINDOW_S, 2
                ),
                "requests_admitted": self._admitted,
                "requests_completed": self._completed,
                "requests_timed_out": self._timeouts,
                # the saturation split (QueueTimeoutError kinds):
                # memory = the arena's page budget never fit;
                # compute = no decode row freed / admitted but stalled
                "requests_timed_out_memory": kinds.get(
                    "kv-page-budget", 0
                ),
                "requests_timed_out_compute": (
                    kinds.get("kv-slot", 0) + kinds.get("stalled", 0)
                ),
                "tokens_out": self._tokens_out,
                "stats_age_s": round(stats_age, 4),
                "loop": self._loop_stats_locked(),
                **alloc.stats(),
                "kv_page_tokens": self._page_tokens,
                # what a row keeps outside its pages, resident for
                # every slot; where it is not 0 the prefix cache is off
                # and says why
                "state_bytes_per_row": self._layout.state_bytes_per_row,
                "prefix_cache": (
                    "on" if self._prefix_cache else
                    "off: " + self._layout.carries_state
                    if self._layout.carries_state else "off"
                ),
                "prefill_chunk_tokens": self._chunk_tokens,
                # prompt tokens not yet prefilled (queued +
                # mid-chunk): the chunked-prefill pressure signal —
                # sustained growth means prefill demand outruns the
                # chunk-per-tick budget
                "prefill_chunk_backlog": int(
                    sum(len(r.tokens) - r.fill_pos
                        for r in self._prefilling)
                    + sum(len(r.tokens) for r in self._queue)
                ),
                # migration surfaces (ISSUE 16): the pod's serving
                # posture — the router's role-aware placement and the
                # role-aware health gating (health/detectors.py) key
                # on serving_role — and the protocol's traffic
                # counters for /v1/debug/serving
                "serving_role": self._role,
                "migrations_in": self._migrated_in,
                "migrations_out": self._migrated_out,
                **self._extra_stats,
            }
        if ttft:
            from dcos_commons_tpu.metrics.registry import percentile

            out["ttft_p50_s"] = round(percentile(ttft, 50), 4)
            out["ttft_p95_s"] = round(percentile(ttft, 95), 4)
        out["t"] = time.time()
        return out

    def _loop_stats_locked(self) -> dict:
        """The timeline's counters, all cumulative since the engine
        started: a reader takes the difference of two samples.
        ``phase_s`` partitions the loop thread's life (it lags by the
        phase under way); the per-request sums cover rows that
        finished normally, and ``queue_wait + prefill + decode`` of a
        row is its retire time minus its arrival."""
        return {
            "decode_calls": self._decode_calls,
            "prefill_calls": self._prefill_calls,
            "prefill_rider_calls": self._prefill_rider_calls,
            "decode_ahead_calls": self._decode_ahead_calls,
            "prefill_unfetched_calls": self._prefill_unfetched_calls,
            "ahead_discarded_rows": self._ahead_discarded_rows,
            "decode_rows_sum": self._decode_rows_sum,
            "decode_entries_sum": self._decode_entries_sum,
            "decode_window_entries_sum": self._decode_window_entries_sum,
            "window_rollovers": self._window_rollovers,
            "summary_entries_written": self._summary_entries,
            "phase_s": {
                k: round(v, 6) for k, v in self._phase_s.items()
            },
            "queue_wait_s_sum": round(self._queue_wait_s_sum, 6),
            "prefill_s_sum": round(self._prefill_s_sum, 6),
            "decode_s_sum": round(self._decode_s_sum, 6),
            "decode_tokens_sum": self._decode_tokens_sum,
            "requests_timed": self._requests_timed,
            **(self._device_counters() if self._device_counters else {}),
        }

    def _phase(self, name: str) -> _Phase:
        return self._phases[name]

    # -- the loop ----------------------------------------------------

    def _loop(self) -> None:
        # persists across iterations: the on_idle servers (gang) pass
        # through the outer loop once per idle TICK, and the terminal
        # flush must happen once per idle PERIOD, not at 20 Hz forever
        flushed_idle = False
        self._phase_t = time.monotonic()  # the thread's life starts
        while True:
            idle = False
            flush_now = False
            with self._cv:
                self._last_tick_mono = time.monotonic()
                while not self._has_work_locked() and not self._stopped:
                    if not flushed_idle:
                        # flush the terminal snapshot before parking:
                        # an idle server's LAST burst must be visible
                        # to /v1/debug/serving, not its second-to-last.
                        # The write itself happens OUTSIDE the lock —
                        # file IO on a slow sandbox must not block
                        # submit() callers needing the cv
                        flushed_idle = True
                        flush_now = True
                        break
                    if self._on_idle is None:
                        with self._phase("wait"):
                            self._cv.wait()
                        self._last_tick_mono = time.monotonic()
                    else:
                        with self._phase("wait"):
                            self._cv.wait(timeout=self._idle_every_s)
                        self._last_tick_mono = time.monotonic()
                        if not self._has_work_locked():
                            break  # fire on_idle OUTSIDE the lock
                if self._stopped:
                    break
                idle = not self._has_work_locked()
                if not idle:
                    flushed_idle = False  # work resumed: re-arm
                    self._admit_locked()
            if flush_now:
                self._write_stats(force=True)
                continue
            if idle:
                with self._phase("wait"):
                    self._safe_idle()
                continue
            try:
                self._tick_rows = 0
                chunks_before = self._prefill_calls
                riders_before = self._prefill_rider_calls
                with self._tracer.span("engine.tick", track="loop") as tick:
                    self._work_tick()
                    tick.set_attr("rows", self._tick_rows)
                    tick.set_attr(
                        "chunks", self._prefill_calls - chunks_before
                    )
                    tick.set_attr(
                        "riders",
                        self._prefill_rider_calls - riders_before,
                    )
                self._write_stats()
            except Exception as e:  # noqa: BLE001 — fail FAST, not silent
                # a bookkeeping bug (bad decode shape, broken stats
                # path) must not kill this thread silently: every
                # client would then block its full timeout and the
                # gang's followers would wedge in a stale collective.
                # Fan the error out and keep the loop alive.
                self._fail_all(e)
        # stopped: leave no step behind on the device half
        with contextlib.suppress(Exception):
            self._drain()

    def _has_work_locked(self) -> bool:
        # a step still outstanding is work: the loop never parks on
        # its cv before resolving it
        return bool(
            self._queue or self._active or self._prefilling
            or self._page_io or self._inflight
        )

    def _work_tick(self) -> None:
        """One scheduling round (loop thread, OUTSIDE the cv): page
        IO, one chunk for every prefilling row, then the next decode
        step for every active row and the outstanding one's tokens,
        unless that step rode one of the chunks."""
        self._run_page_io()
        stepped = self._prefill_tick()
        # loop thread is the only writer of both
        if not stepped and (self._active or self._inflight):
            self._decode_tick()

    def _admit_locked(self) -> None:
        """FIFO admission under BOTH constraints — a free decode row
        and the page budget — into the prefilling set.  Strictly in
        order: the first request that does not fit blocks the queue
        (admitting a smaller later one would starve large requests
        forever)."""
        while self._queue and self._free:
            row = self._queue[0]
            if row.group.abandoned:
                self._queue.popleft()
                continue
            admission = self._allocator.admit(row.tokens, row.n)
            if admission is None:
                break
            self._queue.popleft()
            row.slot = self._free.pop()
            row.admission = admission
            row.table = np.zeros(self._pages_per_row, np.int32)
            # window layers' ring: the slot's own pages, never drawn
            row.table[:self._layout.ring_pages] = (
                self._layout.ring_entries(row.slot)
            )
            for i, entry in enumerate(admission.matched):
                row.table[self._layout.share_slot(i)] = entry.page
            # prefill resumes past the cache-served pages
            row.cached = len(admission.matched)
            row.fill_pos = row.cached * self._layout.share_tokens
            row.registered_to = row.cached
            self._admitted_locked(row)
            self._prefilling.append(row)

    def _admitted_locked(self, row: _Row) -> None:
        """The row holds its slot (and its page budget) from now on:
        its queue wait is over."""
        row.admit_t = time.monotonic()
        self._tracer.interval(
            "engine.queue", row.arrival, row.admit_t,
            trace_id=row.trace_id, parent_id=row.parent_id, track="req",
            rid=row.rid,
        )

    def _first_token_locked(self, row: _Row, first: int, now: float):
        """Admission and TTFT are counted where the first token
        lands; the row's prefill is over."""
        self._admitted += 1
        self._ttft.append(now - row.arrival)
        row.out.append(first)
        row.first_t = now
        row.first_tick = self._decode_calls
        self._count_tokens_locked(1, now)
        self._tracer.interval(
            "engine.prefill", row.admit_t or row.arrival, now,
            trace_id=row.trace_id, parent_id=row.parent_id, track="req",
            rid=row.rid, prompt_tokens=len(row.tokens),
            chunks=row.chunks, cached_pages=row.cached,
        )

    def _apply_admit_locked(self, row: _Row, first: int, now: float):
        self._first_token_locked(row, first, now)
        if self._row_finished(row, first, int(len(row.tokens))):
            self._retire_locked(row)
            return
        self._install_decode_locked(row)

    def _install_decode_locked(self, row: _Row) -> None:
        """Enter ``row`` into the decode set at its current progress
        — a fresh admission (out == [first]) and a spliced-in
        migrated session (out carries every token so far) resume
        through the same door: decode continues from (out[-1],
        plen + len(out) - 1), wherever that state was produced."""
        slot = row.slot
        self._rows[slot] = row
        self._active += 1
        self._tok[slot] = row.out[-1]
        self._pos[slot] = len(row.tokens) + len(row.out) - 1
        self._temps[slot] = row.temp
        self._seeds[slot] = row.seed

    def _decode_prep_locked(self):
        """Build the next decode step from what the loop knows BEFORE
        the outstanding step's tokens are read: allocate its write
        pages, snapshot every row's page table, and say which rows it
        computes for (kept as the newest of ``_inflight``).  Returns
        ``(tok, pos, tables, carry)``, or None when no row rides a
        next step and the outstanding one only has to be resolved.

        A row that rides the outstanding step and continues takes its
        token from that step's output on the device (``carry``), its
        position is ``pos + 1`` and its page comes from its
        reservation by position alone.  A row whose outstanding token
        is its last by ``n`` or ``max_len``, or that was abandoned, is
        left out; one that can still end by ``eos`` rides, and if it
        did end there its sample is dropped at apply and its write
        lands in pages freed only after it (in device order any new
        owner writes later)."""
        riding = self._inflight[-1] if self._inflight else None
        tok = self._tok.copy()
        pos = self._pos.copy()
        carry = np.zeros(self._slots, np.bool_)
        tables = np.zeros(
            (self._slots, self._pages_per_row), np.int32
        )
        # who this step actually computes for: a row installed into a
        # slot AFTER this point (a splice activation or an unfreeze,
        # both on peer threads, or a prompt's final chunk) must not
        # be credited this step's sample — it was computed from the
        # slot's previous state.  A FROZEN row is not dispatched and
        # rides a zero table: its pages must stop changing the moment
        # the migration fence drops
        dispatched: List[Optional[_Row]] = [None] * self._slots
        for slot, row in enumerate(self._rows):
            if row is None or row.frozen:
                continue
            if riding is not None and riding[slot] is row:
                if (row.group.abandoned
                        or len(row.out) + 1 >= row.n
                        or int(pos[slot]) + 1 >= self._max_len):
                    tok[slot] = pos[slot] = 0  # as the empty slot it
                    continue                   # is about to become
                carry[slot] = True
                pos[slot] += 1
            at = int(pos[slot])
            if not row.group.abandoned:
                # an abandoned row retires at apply; its write this
                # step lands in the trash page or a page it still
                # holds (masked, discarded)
                self._ensure_pages_locked(row, at, at)
                self._count_layout_events(at, at)
            tables[slot] = row.table
            dispatched[slot] = row
            self._decode_rows_sum += 1
            self._decode_entries_sum += self._layout.entries(at)
            self._decode_window_entries_sum += (
                self._layout.window_entries(at + 1)
            )
        if riding is not None and not any(
            row is not None for row in dispatched
        ):
            return None
        if riding is not None:
            self._decode_ahead_calls += 1
        self._decode_calls += 1
        self._inflight.append(dispatched)
        return tok, pos, tables, carry

    def _rider_step(self) -> Optional[dict]:
        """The tick's decode step for a chunk call to carry (loop
        thread, outside the cv), as ``decode_fn``'s keywords; None
        when no row decodes, or the outstanding step only has to be
        resolved: the tick's ``_decode_tick`` does that."""
        with self._phase("decode_prep"), self._cv:
            if not (self._active or self._inflight):
                return None
            step = self._decode_prep_locked()
            if step is None:
                return None
            active = self._active
            if any(row is not None for row in self._inflight[-1]):
                self._prefill_rider_calls += 1
        self._tick_rows = active
        tok, pos, tables, carry = step
        return {
            "tok": tok, "pos": pos, "temps": self._temps.copy(),
            "seeds": self._seeds.copy(), "tables": tables, "carry": carry,
        }

    def _decode_tick(self) -> None:
        """Dispatch the next step, then resolve the oldest outstanding
        one: at depth 0 that is the step just dispatched."""
        with self._phase("decode_prep"), self._cv:
            step = self._decode_prep_locked()
            active = self._active
        try:
            with self._phase("decode_call"):
                if step is None:
                    nxt = self._resolve_fn()
                else:
                    tok, pos, tables, carry = step
                    self._tick_rows = active
                    ahead = (
                        {} if self._resolve_fn is None
                        else {"carry": carry}
                    )
                    nxt = self._decode_fn(
                        tok, pos, self._temps.copy(),
                        self._seeds.copy(), tables, active, **ahead,
                    )
        except Exception as e:  # noqa: BLE001 — fan out, keep serving
            self._fail_all(e)
            return
        self._apply_decode(nxt)

    def _apply_decode(self, nxt) -> None:
        """Apply the oldest outstanding step's tokens (loop thread,
        outside the cv).  From a device half that runs ahead, an
        empty ``nxt`` says it had no step to resolve yet."""
        nxt = np.asarray(nxt)
        if self._resolve_fn is not None and not nxt.size:
            return
        now = time.monotonic()
        merged = None
        with self._phase("decode_apply"), self._cv:
            self._apply_decode_locked(nxt, now, self._inflight.popleft())
            if self._active >= 2 and not self._merge_logged:
                self._merge_logged = True
                merged = self._active
            elif self._active <= 1:
                self._merge_logged = False
        if merged is not None and self._log is not None:
            self._log(
                f"continuous-batch: {merged} rows sharing one decode "
                "step over the paged arena"
            )

    def _drain(self) -> None:
        """Resolve and apply the outstanding step, if there is one
        (loop thread, outside the cv).  Everything that reads a row's
        host state or fences it comes after this: the migration verbs
        and queued page IO (``_on_loop``), the prefill hand-off,
        ``_fail_all``, ``stop`` and parking on the cv (a step
        outstanding counts as work)."""
        if not self._inflight or self._resolve_fn is None:
            return
        with self._phase("decode_call"):
            nxt = self._resolve_fn()
        self._apply_decode(nxt)

    def _apply_decode_locked(self, nxt: np.ndarray, now: float,
                             dispatched) -> None:
        produced = 0
        for slot in range(self._slots):
            row = self._rows[slot]
            if dispatched[slot] is not row:
                # not this step's row.  The slot was filled or its row
                # unfrozen after the step was built (its first real
                # sample is the next step's), or the step's row is
                # gone: the step before ended it while this one was
                # already queued
                if dispatched[slot] is not None:
                    self._ahead_discarded_rows += 1
                continue
            if row is None:
                continue
            if row.group.abandoned:
                self._retire_locked(row)
                continue
            token = int(nxt[slot])
            row.out.append(token)
            produced += 1
            self._pos[slot] += 1
            self._tok[slot] = token
            if (self._row_finished(row, token, int(self._pos[slot]))):
                self._retire_locked(row)
        self._count_tokens_locked(produced, now)

    def _row_finished(self, row: _Row, token: int, pos: int) -> bool:
        return (
            len(row.out) >= row.n
            or (row.eos is not None and token == row.eos)
            or pos >= self._max_len  # slot cache exhausted
        )

    def _end_of(self, row: _Row) -> str:
        """Why a retiring row ended.  The first three are the normal
        ends (``_row_finished``); a migrated row's group already
        carries its redirect."""
        if row.group.abandoned:
            return "abandoned"
        if row.group.error is not None:
            return "migrated"
        if row.eos is not None and row.out and row.out[-1] == row.eos:
            return "eos"
        if len(row.out) >= row.n:
            return "max_tokens"
        return "max_len"

    def _close_timeline_locked(self, row: _Row) -> None:
        """The retiring row's share of the per-request sums and its
        ``engine.decode`` span.  Only a row that was admitted HERE,
        got its first token and ended normally is summed: abandoned
        and migrated rows (either direction) are not a whole request
        of this engine.  A row retired before its first token has
        nothing left to record."""
        if not row.first_t:
            return
        now = time.monotonic()
        end = self._end_of(row)
        if row.admit_t and end not in ("abandoned", "migrated"):
            self._queue_wait_s_sum += row.admit_t - row.arrival
            self._prefill_s_sum += row.first_t - row.admit_t
            self._decode_s_sum += now - row.first_t
            self._decode_tokens_sum += len(row.out) - 1
            self._requests_timed += 1
        self._tracer.interval(
            "engine.decode", row.first_t, now,
            trace_id=row.trace_id, parent_id=row.parent_id, track="req",
            rid=row.rid, tokens=len(row.out),
            ticks=self._decode_calls - row.first_tick, end=end,
        )

    def _retire_locked(self, row: _Row) -> None:
        self._close_timeline_locked(row)
        slot = row.slot
        if self._rows[slot] is row:
            self._rows[slot] = None
            self._active -= 1
            self._tok[slot] = 0
            self._pos[slot] = 0
            self._temps[slot] = 0.0
            self._seeds[slot] = 0
        self._free.append(slot)
        if row.admission is not None:
            self._allocator.retire(row.admission, row.private_pages)
            row.admission = None
            row.private_pages = []
            row.table = None
        group = row.group
        group.remaining -= 1
        if group.remaining <= 0 and not group.abandoned:
            self._completed += 1
            group.done.set()

    def _fail_all(self, error: BaseException) -> None:
        """Loop thread, outside the cv: what the outstanding step
        still produced is applied first (a row it finished is
        answered, not failed), and no handle stays behind on the
        device half.  An asynchronous dispatch's own error surfaces
        at the later fetch and lands here like any other."""
        with contextlib.suppress(Exception):
            self._drain()
        with self._cv:
            self._fail_all_locked(error)

    def _fail_all_locked(self, error: BaseException) -> None:
        """A model-call failure fans out to every waiting, prefilling,
        parked and active request and clears the pool: every row
        returns its slot, and the arena's bookkeeping is rebuilt."""
        held = (
            [r for r in self._rows if r is not None]
            + list(self._prefilling)
            # parked spliced rows die with everything else: their
            # groups error out so a blocked collect() unblocks
            + list(self._spliced.values())
        )
        groups = {r.group for r in self._queue} | {r.group for r in held}
        for row in held:
            self._free.append(row.slot)
            row.slot = -1
            row.admission = None
        self._queue.clear()
        self._prefilling.clear()
        self._spliced.clear()
        self._rows[:] = [None] * self._slots
        self._inflight.clear()
        self._active = 0
        self._tok[:] = 0
        self._pos[:] = 0
        self._temps[:] = 0.0
        self._seeds[:] = 0
        # every admission died with its group: rebuild the arena
        # bookkeeping (the prefix cache's pages may hold K/V written
        # before the failure — integrity unknown, so drop them too)
        self._allocator.reset()
        for group in groups:
            group.error = error
            group.done.set()

    def _count_tokens_locked(self, n: int, now: float) -> None:
        if n <= 0:
            return
        self._tokens_out += n
        self._rate.append((now, n))
        while self._rate and self._rate[0][0] < now - _RATE_WINDOW_S:
            self._rate.popleft()

    def _safe_idle(self) -> None:
        try:
            self._on_idle()
        except Exception:  # noqa: BLE001, sdklint: disable=swallowed-exception — idle hook must not kill serving
            pass

    def _write_stats(self, force: bool = False) -> None:
        """Mirror the gauges to the sandbox (loop thread only): the
        scheduler's /v1/debug/serving reads this per task."""
        if self._stats_path is None:
            return
        now = time.monotonic()
        if not force and now - self._stats_written < self._stats_every_s:
            return
        self._stats_written = now
        try:
            with self._phase("stats"):
                tmp = self._stats_path + ".tmp"
                # durcheck: dur-file-discipline=telemetry mirror: loss on power failure is acceptable, the rename alone keeps readers partial-free
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump(self.stats(), f)
                os.replace(tmp, self._stats_path)
        except OSError:
            pass  # sdklint: disable=swallowed-exception — telemetry must never take the server down

    def _run_page_io(self) -> None:
        """Run the queued loop jobs: migration verbs and page
        reads/writes (loop thread, outside the cv — device calls like
        any dispatch).  The outstanding step is resolved first: a job
        finds every row's host state settled."""
        while True:
            with self._cv:
                if not self._page_io:
                    return
            self._drain()
            with self._cv:
                job = self._page_io.popleft()
            job()

    # -- chunked prefill ---------------------------------------------

    def _prefill_tick(self) -> None:
        """Advance EVERY prefilling row by one chunk, FIFO order.

        Per-ROW chunking is the head-of-line fix: a long prompt costs
        several small dispatches interleaved with decode ticks instead
        of one prompt-wide dispatch that blocks the pool — while a
        BURST of short prompts still admits in one tick (each is one
        cheap chunk; serializing them across decode ticks would tax
        every short request one full decode per queue position).
        Per-tick prefill work stays bounded by the slot count: one
        chunk-wide call a row.

        Where the chunk program carries riders, the tick's decode step
        rides the first chunk call (a row that finishes its prompt in
        this tick was not in it: it joins the next tick's), and the
        tokens that call resolves are applied as a decode call's are.
        Returns whether the step went that way."""
        with self._cv:
            rows = list(self._prefilling)
        stepped = False
        for row in rows:
            with self._cv:
                if row.admission is None:
                    continue  # already retired/failed this tick
                if row.frozen:
                    continue  # fenced mid-prefill for migration
                if row.group.abandoned:
                    # abandoned before its first token: free the
                    # pages/slot now, nothing ever reached the client
                    self._prefilling.remove(row)
                    self._retire_locked(row)
                    continue
                plen = len(row.tokens)
                start = row.fill_pos
                clen = min(self._chunk_tokens, plen - start)
                self._ensure_pages_locked(row, start, start + clen - 1)
                table = row.table.copy()
            padded = np.zeros((1, self._chunk_tokens), np.int32)
            padded[0, :clen] = row.tokens[start:start + clen]
            # only a prompt's last chunk is fetched: its token is the
            # one anybody reads.  The pages an unfetched chunk fills
            # are published below while their write may still be
            # queued: every later reader is a later device program
            ahead = {}
            if self._resolve_fn is not None:
                ahead["final"] = start + clen >= plen
                if not ahead["final"]:
                    self._prefill_unfetched_calls += 1
            if self._chunk_riders and not stepped:
                riders = self._rider_step()
                if riders is not None:
                    ahead["riders"] = riders
                    stepped = True
            with self._phase("prefill_call"):
                self._prefill_calls += 1
                row.chunks += 1
                first = self._prefill_fn(
                    padded, slot=row.slot, table=table, start=start,
                    true_len=clen, temp=row.temp, seed=row.seed,
                    **ahead,
                )
            nxt = None
            if "riders" in ahead:
                first, nxt = first
            now = time.monotonic()
            handoff_row = None
            with self._cv:
                self._count_layout_events(start, start + clen - 1)
                row.fill_pos = start + clen
                self._register_pages_locked(row)
                if row.fill_pos >= plen:
                    if row.group.abandoned:
                        self._prefilling.remove(row)
                        self._retire_locked(row)
                    elif self._handoff is not None:
                        # disaggregation: the prompt is prefilled and
                        # its first token sampled — this pod's work
                        # is done.  Count admission/TTFT HERE (the
                        # destination replays neither), fence the row
                        # and ship it to a decode pod outside the cv
                        self._first_token_locked(row, int(first), now)
                        if self._row_finished(row, int(first), plen):
                            self._prefilling.remove(row)
                            self._retire_locked(row)
                        else:
                            row.frozen = True
                            handoff_row = row
                    else:
                        self._prefilling.remove(row)
                        self._apply_admit_locked(row, int(first), now)
            if nxt is not None:
                self._apply_decode(nxt)
            if handoff_row is not None:
                self._run_handoff(handoff_row)
        return stepped

    def _run_handoff(self, row) -> None:
        """Hand a finished prefill to the decode pool (loop thread,
        outside the cv).  Any pre-cutover failure falls back to
        decoding locally — a prefill pod degrades to unified rather
        than failing the request.  A post-cutover failure
        (ReleasePendingError) leaves the row frozen: the destination
        owns the session now, and resuming here would double-serve."""
        from dcos_commons_tpu.serve.migration import (
            ReleasePendingError,
        )

        self._drain()
        try:
            ok = self._handoff(self, row.rid)
        except ReleasePendingError:
            if self._log is not None:
                self._log(
                    f"handoff of session {row.rid} cut over but "
                    "release failed; holding the frozen source row "
                    "for a retried release"
                )
            return
        except Exception as e:  # noqa: BLE001 — degrade, don't fail the request
            ok = None
            if self._log is not None:
                self._log(
                    f"prefill handoff failed ({e}); decoding locally"
                )
        if ok is None:
            with self._cv:
                if row.frozen:
                    self._unfreeze_locked(row)

    def _ensure_pages_locked(self, row, first_pos: int,
                             last_pos: int) -> None:
        """Allocate the pages that writing positions [first_pos,
        last_pos] touches — drawn from the row's admission
        reservation, so this cannot fail for an admitted row.  A
        windowed row's ring pages are allocated in its first window
        and written over in place ever after."""
        for v in self._layout.write_slots(first_pos, last_pos):
            if row.table[v] == 0:
                page = self._allocator.alloc(row.admission)
                row.table[v] = page
                row.private_pages.append(page)

    def _count_layout_events(self, first_pos: int, last_pos: int) -> None:
        """Window ends crossed and chunk summaries written with
        positions [first_pos, last_pos] (cv held; 0 for full rows)."""
        self._window_rollovers += self._layout.rollovers(first_pos, last_pos)
        self._summary_entries += self._layout.summaries(first_pos, last_pos)

    def _register_pages_locked(self, row) -> None:
        """Publish every newly-completed FULL shareable prompt page
        (a page of exact K/V, or of chunk summaries: the layout's)
        into the prefix cache.  The last (partial) prompt page stays
        private — decode keeps writing into it, and shared pages are
        read-only by contract."""
        p = self._layout.share_tokens
        while ((row.registered_to + 1) * p <= row.fill_pos
               and (row.registered_to + 1) * p <= len(row.tokens)):
            v = self._layout.share_slot(row.registered_to)
            page = int(row.table[v])
            toks = tuple(row.tokens[
                row.registered_to * p:(row.registered_to + 1) * p
            ])
            if self._allocator.register(row.admission, toks, page):
                row.private_pages.remove(page)
            row.registered_to += 1

    # -- migration (serve/migration.py, ISSUE 16) --------------------

    def _on_loop(self, fn):
        """Run ``fn`` on the loop thread (the engine's one device
        caller) BETWEEN ticks, with no decode step outstanding, and
        return its result: page reads/writes, and the migration verbs
        that fence a row or hand a slot over.  Called FROM the loop
        thread (prefill handoff, which drained before it began) it
        runs inline; from a migration thread it queues and blocks
        until the loop executes it.  A caller that gives up waiting
        takes its job back: a verb either ran or never will."""
        from dcos_commons_tpu.serve.migration import MigrationError

        if threading.current_thread() is self._thread:
            return fn()
        done = threading.Event()
        box: dict = {}

        def job():
            with self._cv:
                if "cancelled" in box:
                    return
                box["started"] = True
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — re-raised in the waiter
                box["error"] = e
            finally:
                done.set()

        with self._cv:
            if self._stopped:
                raise MigrationError("engine stopped")
            self._page_io.append(job)
            self._cv.notify_all()
        if not done.wait(timeout=60.0):
            with self._cv:
                if "started" not in box:
                    box["cancelled"] = True
                    raise MigrationError(
                        "page io stalled on the engine loop"
                    )
            done.wait()
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _find_rid_locked(self, rid: int):
        for row in self._rows:
            if row is not None and row.rid == rid:
                return row
        for row in self._prefilling:
            if row.rid == rid:
                return row
        return None

    def sessions(self) -> List[dict]:
        """Live migratable sessions: rows holding pages that are not
        already fenced — the drain/rebalance work list."""
        out: List[dict] = []
        with self._cv:
            for row in self._prefilling:
                if not row.frozen and not row.group.abandoned:
                    out.append({
                        "rid": row.rid, "tokens": list(row.tokens),
                        "state": "prefill",
                        "pages": int(np.count_nonzero(row.table)),
                    })
            for row in self._rows:
                if (row is not None and not row.frozen
                        and not row.group.abandoned
                        and row.admission is not None):
                    out.append({
                        "rid": row.rid, "tokens": list(row.tokens),
                        "state": "decode",
                        "pages": int(np.count_nonzero(row.table)),
                    })
        return out

    def freeze(self, rid: int) -> None:
        """Fence a session: the fence drops BETWEEN ticks, after the
        outstanding decode step was resolved and applied (a step
        queued behind it may already have overwritten a windowed
        row's ring, so a sample in flight is never discarded): from
        then on decode/prefill leave the row out, its pages stop
        changing, and ``(tok, pos)`` say exactly what its pages
        hold."""
        self._on_loop(lambda: self._freeze(rid))

    def _pages_travel(self) -> None:
        """Refuse a migration verb where a session is more than its
        pages: never pages without their state."""
        from dcos_commons_tpu.serve.migration import MigrationError

        if self._layout.carries_state:
            raise MigrationError(self._layout.carries_state)

    def _freeze(self, rid: int) -> None:
        from dcos_commons_tpu.serve.migration import MigrationError

        self._pages_travel()
        with self._cv:
            row = self._find_rid_locked(rid)
            if row is None or row.admission is None:
                raise MigrationError(f"no live session {rid} to freeze")
            row.frozen = True

    def unfreeze(self, rid: int) -> None:
        """Drop the fence: an aborted migration resumes exactly where
        it froze.  Silently a no-op when the session is gone (a
        failure fan-out already answered its client)."""
        with self._cv:
            row = self._find_rid_locked(rid)
            if row is None:
                return
            if row.frozen:
                self._unfreeze_locked(row)
            self._cv.notify_all()

    def _unfreeze_locked(self, row) -> None:
        row.frozen = False
        if row in self._prefilling and row.fill_pos >= len(row.tokens):
            # a prefill-COMPLETE fenced row (handoff path): it never
            # entered the decode set, so resuming means installing it
            self._prefilling.remove(row)
            if self._row_finished(
                row, row.out[-1], len(row.tokens) + len(row.out) - 1
            ):
                self._retire_locked(row)
            else:
                self._install_decode_locked(row)
        self._cv.notify_all()

    def export_frozen(self, rid: int):
        """Snapshot a frozen session for the wire: request + progress
        + every mapped page's payload, keyed by VIRTUAL index
        (physical ids never leave the pod).  Page reads run on the
        loop thread."""
        from dcos_commons_tpu.serve.migration import (
            MigrationError,
            SessionSnapshot,
        )

        self._pages_travel()
        if self._read_page is None:
            raise MigrationError(
                "no page reader bound (PagedEngine read_page=...)"
            )
        with self._cv:
            row = self._find_rid_locked(rid)
            if row is None or row.admission is None:
                raise MigrationError(f"no live session {rid} to export")
            if not row.frozen:
                raise MigrationError(
                    f"session {rid} is not frozen — export without a "
                    "fence would race decode"
                )
            plen = len(row.tokens)
            kv_end = (
                plen + len(row.out) - 1
                if row.fill_pos >= plen and row.out else row.fill_pos
            )
            # a windowed row's past ring pages are dead: not shipped
            live = (
                self._layout.live_slots(kv_end) if self._layout.window
                else range(len(row.table))
            )
            pages = [
                (v, int(row.table[v])) for v in live if row.table[v] != 0
            ]
            meta = (
                list(row.tokens), row.n, row.temp, row.eos, row.seed,
                list(row.out), row.fill_pos,
            )
        payloads = self._on_loop(
            lambda: [(v, self._read_page(p)) for v, p in pages]
        )
        tokens, n, temp, eos, seed, out, fill_pos = meta
        return SessionSnapshot(
            rid=rid, tokens=tokens, max_new=n, temperature=temp,
            eos=eos, seed=seed, out=out, fill_pos=fill_pos,
            kv_end=kv_end, page_tokens=self._page_tokens,
            pages=payloads, source=self._role,
            window=self._layout.window, chunk=self._layout.chunk,
        )

    def splice(self, snap) -> int:
        """Admit a migrated session under the SAME transactional rule
        a fresh request faces (paging.admit — worst-case reservation,
        prefix-cache matching), copy only the pages the local prefix
        cache cannot serve, and PARK the row.  Nothing decodes until
        ``activate``; ``abort_splice`` undoes everything.  Returns
        the destination-local rid."""
        return self._on_loop(lambda: self._splice(snap))

    def _splice(self, snap) -> int:
        from dcos_commons_tpu.serve.migration import MigrationError

        self._pages_travel()
        if self._write_page is None:
            raise MigrationError(
                "no page writer bound (PagedEngine write_page=...)"
            )
        if int(snap.page_tokens) != self._page_tokens:
            raise MigrationError(
                f"page geometry mismatch: snapshot has "
                f"{snap.page_tokens}-token pages, this arena "
                f"{self._page_tokens}"
            )
        if (int(snap.window), int(snap.chunk)) != (
            self._layout.window, self._layout.chunk
        ):
            raise MigrationError(
                f"row layout mismatch: snapshot has window/chunk "
                f"{snap.window}/{snap.chunk}, this pool "
                f"{self._layout.window}/{self._layout.chunk}"
            )
        plen = len(snap.tokens)
        if plen > self._prompt_len or plen + snap.max_new > self._max_len:
            raise MigrationError(
                f"session does not fit this pod's geometry "
                f"({plen}+{snap.max_new} vs {self._max_len})"
            )
        incoming = dict(snap.pages)
        with self._cv:
            if not self._free:
                raise MigrationError("no free decode row")
            admission = self._allocator.admit(snap.tokens, snap.max_new)
            if admission is None:
                raise MigrationError(
                    "page budget cannot admit the migrated session"
                )
            m = len(admission.matched)
            layout = self._layout
            shared = {layout.share_slot(i) for i in range(m)}
            # every entry a later step reads that the local prefix
            # cache does not serve
            wanted = [
                v for v in layout.live_slots(int(snap.kv_end))
                if v not in shared
            ]
            missing = [v for v in wanted if v not in incoming]
            if missing:
                self._allocator.retire(admission, [])
                raise MigrationError(
                    f"snapshot is missing pages {missing}"
                )
            group = _Group([])
            row = _Row(
                list(snap.tokens), snap.max_new, snap.temperature,
                snap.eos, snap.seed, group,
            )
            group.rows = [row]
            group.remaining = 1
            row.rid = self._next_rid
            self._next_rid += 1
            if self._tracer.enabled:
                row.trace_id = self._tracer.new_trace_id()
            row.slot = self._free.pop()
            row.admission = admission
            row.table = np.zeros(self._pages_per_row, np.int32)
            # window layers' ring: the slot's own pages, never drawn
            row.table[:self._layout.ring_pages] = (
                self._layout.ring_entries(row.slot)
            )
            for i, entry in enumerate(admission.matched):
                row.table[layout.share_slot(i)] = entry.page
            row.registered_to = m
            # the local cache may hold MORE of the prompt than the
            # source had prefilled — prefill resumes past it
            row.fill_pos = max(int(snap.fill_pos),
                               m * layout.share_tokens)
            row.out = [int(t) for t in snap.out]
            row.frozen = True
            imports = []
            for v in wanted:
                page = self._allocator.alloc(admission)
                row.table[v] = page
                row.private_pages.append(page)
                imports.append((page, incoming[v]))
            self._spliced[row.rid] = row
            self._migrated[row.rid] = row
            if len(self._migrated) > 256:
                # uncollected finished sessions age out (a router
                # always collects; this bounds a buggy caller)
                for old_rid in [
                    r for r, rw in self._migrated.items()
                    if rw.group.done.is_set()
                ][:64]:
                    self._migrated.pop(old_rid, None)
            self._cv.notify_all()
        try:
            self._on_loop(lambda: [
                self._write_page(p, payload) for p, payload in imports
            ])
        except BaseException:
            self.abort_splice(row.rid)
            raise
        return row.rid

    def activate(self, rid: int) -> None:
        """CUTOVER: the parked spliced row starts serving here.  Full
        prompt pages it carried are published to the prefix cache
        only now — after their payloads landed (registering sooner
        would let a concurrent admission pin an unwritten page)."""
        self._on_loop(lambda: self._activate(rid))

    def _activate(self, rid: int) -> None:
        from dcos_commons_tpu.serve.migration import MigrationError

        with self._cv:
            row = self._spliced.pop(rid, None)
            if row is None:
                raise MigrationError(f"no spliced session {rid}")
            row.frozen = False
            self._register_pages_locked(row)
            self._migrated_in += 1
            if row.out:
                # its decode HERE starts now (the span's start; with
                # no admit_t it stays out of the per-request sums)
                row.first_t = time.monotonic()
                row.first_tick = self._decode_calls
            plen = len(row.tokens)
            if row.fill_pos < plen:
                self._prefilling.append(row)  # resumes chunked prefill
            elif row.out and self._row_finished(
                row, row.out[-1], plen + len(row.out) - 1
            ):
                self._retire_locked(row)
            elif row.out:
                self._install_decode_locked(row)
            else:
                raise MigrationError(
                    f"spliced session {rid} has no resume point"
                )
            if not self._has_work_locked():
                self._last_tick_mono = time.monotonic()
            self._cv.notify_all()

    def abort_splice(self, rid: int) -> None:
        """Undo a splice that never activated: pages and slot return
        to the arena.  No-op when the rid is unknown (already
        activated or never spliced) — abort is best-effort."""
        self._on_loop(lambda: self._abort_splice(rid))

    def _abort_splice(self, rid: int) -> None:
        with self._cv:
            row = self._spliced.pop(rid, None)
            if row is None:
                return
            self._migrated.pop(rid, None)
            self._free.append(row.slot)
            if row.admission is not None:
                self._allocator.retire(row.admission, row.private_pages)
                row.admission = None
                row.private_pages = []
                row.table = None

    def release_migrated(self, rid: int, *, moved_to: str,
                         dest_rid: int) -> None:
        """The protocol's last verb: after cutover, retire the frozen
        source row, free its pages, and answer its blocked client
        with ``SessionMigratedError`` naming the destination (the
        router follows with a collect request)."""
        from dcos_commons_tpu.serve.migration import (
            MigrationError,
            SessionMigratedError,
        )

        with self._cv:
            row = self._find_rid_locked(rid)
            if row is None:
                raise MigrationError(f"no session {rid} to release")
            if not row.frozen:
                raise MigrationError(
                    f"session {rid} is not frozen — release without a "
                    "fence would double-serve"
                )
            if row in self._prefilling:
                self._prefilling.remove(row)
            self._migrated_out += 1
            row.group.error = SessionMigratedError(
                rid, moved_to, dest_rid
            )
            self._retire_locked(row)

    def collect(self, rid: int,
                timeout: Optional[float] = None) -> List[int]:
        """Block until a migrated-in session finishes and return its
        FULL output — the tokens the source already produced plus
        everything decoded here, one seamless reply."""
        from dcos_commons_tpu.serve.migration import MigrationError

        with self._cv:
            row = self._migrated.get(rid)
        if row is None:
            raise MigrationError(
                f"no migrated session {rid} to collect"
            )
        wait_s = timeout if timeout is not None else self._queue_timeout_s
        if not row.group.done.wait(timeout=wait_s):
            raise QueueTimeoutError(
                "migrated session did not finish", kind="stalled"
            )
        with self._cv:
            self._migrated.pop(rid, None)
        if row.group.error is not None:
            raise row.group.error
        return list(row.out)


def read_servestats(path: str) -> dict:
    """Parse a worker's servestats.json; {} when absent/corrupt (a
    worker killed mid-replace leaves the previous snapshot or none)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}
