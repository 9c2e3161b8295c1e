"""Device half of the continuous-batching engine: the jitted
prefill-chunk / decode-step pair over a persistent page arena
(models/decode.py).

TWO programs cover the server's whole life: ``prefill_chunk`` runs
one right-padded prompt chunk through a request's page table (traced
start/true_len/temperature/seed — no recompile per request),
``decode`` advances EVERY row one step with per-row positions,
temperatures, PRNG seeds and page tables (a mixed greedy/sampling
pool shares one dispatch).  The arena is allocated ONCE with static
shapes and threaded through both functions; on non-CPU backends the
cache argument is DONATED so XLA updates it in place instead of
holding two arena-sized buffers live across the call.

Per-row sampling keys: each request carries its own 31-bit seed and
every step folds the row's current position into it
(``fold_in(key(seed), pos)``) — rows never share randomness, a row's
stream does not depend on which slot it landed in or who its pool
neighbors are, and no key is ever reused across steps (the prefill
pick folds ``prompt_len - 1``, the first decode folds ``prompt_len``).
Greedy rows (temperature 0) ignore the keys entirely and argmax —
token-identical to whole-batch ``generate`` on the same prompts
(tests/test_paged_kv.py holds the equivalence under arbitrary
admission orders).

Neither call blocks on a result its caller does not need yet (JAX
dispatch is asynchronous, the device runs its queue in order, and the
arena is threaded from call to call as a donated value, so the host
may queue one program behind the one that runs):

* ``prefill_chunk(..., final=False)`` dispatches the chunk and
  returns ``None`` with no fetch; ``final=True`` (the default, and
  what a prompt's last chunk asks) fetches and returns the sampled
  first token.
* ``decode(..., carry=mask)`` dispatches this step, taking each
  ``carry`` row's input token from the PREVIOUS step's output where it
  lies, on the device, then fetches and returns the previous step's
  tokens (an EMPTY array when none was outstanding): the wait for
  step N is spent while the device runs step N + 1.
  ``resolve_decode()`` fetches the outstanding step without
  dispatching another.  With ``carry=None`` the call is synchronous
  and returns its own step's tokens (the gang driver, whose ticks
  broadcast host arrays).

Both entry points close a host span on the profiler's clock
(``pool.prefill_chunk`` / ``pool.decode``) with an inner ``.fetch``
around the blocking ``device_get`` where there is one: in a profile
the part of a call before its fetch is the host dispatching the
program, the fetch is the host waiting for the device (for a step
that mostly finished while the host prepared the next).  Outside a
profiler session a ``TraceAnnotation`` is a flag test.

The gang driver reuses the class unchanged: ``put`` lifts host
arrays to global (broadcast_one_to_all hands every rank identical
numpy), ``constrain_out`` pins token outputs replicated so rank 0
can bulk-fetch them, and ``cache_sharding`` lays the arena's KV heads
(dim 3) over the tp axis when divisible.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Optional

import numpy as np


class PagedPoolModel:
    """Owns the page arena and the two compiled entry points
    (models/decode.py ``init_paged_kv_cache`` / ``paged_prefill_chunk``
    / ``paged_decode_step``).

    ONE prefill-chunk program (chunk width ``chunk_tokens`` static;
    start position, true length, page table, temperature and seed all
    traced — a request resuming after a prefix-cache hit is the same
    program as one starting cold) and ONE decode program (per-row
    positions/temps/seeds/page tables traced) cover every request the
    server ever admits.  With ``riders`` (the caller's loop decodes,
    and will hand a tick's decode step to the tick's chunk) and a
    family whose chunk can carry one (models/decode.py
    ``chunk_carries_riders``), the chunk program IS a chunk and a
    decode step in one, ``chunk_riders`` says so, and a chunk with
    nothing to carry takes idle riders: all-zero tables, which read
    and write the trash page as the decode program's idle rows do.
    Still two programs.  The arena holds ``pages`` usable pages plus
    the TRASH page (physical page 0): padding and inactive-row writes
    land there, so ``warm()`` — which runs both programs over
    all-zero tables — never dirties a real page.

    Not thread-safe by itself: exactly one thread (the engine loop, or
    a gang rank's tick executor) may call ``prefill_chunk``/``decode``
    — both advance ``self.cache``.
    """

    def __init__(
        self,
        config,
        params,
        slots: int,
        max_len: int,
        page_tokens: int,
        pages: int,
        chunk_tokens: int,
        kv_dtype: str = "native",
        cache_sharding: Optional[Any] = None,
        put: Optional[Callable] = None,
        constrain_out: Optional[Callable] = None,
        riders: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        from dcos_commons_tpu.models.decode import (
            arena_lanes,
            chunk_carries_riders,
            init_paged_kv_cache,
            paged_decode_step,
            paged_prefill_chunk,
            sample_token,
        )
        from dcos_commons_tpu.serve.paging import RowLayout
        from dcos_commons_tpu.utils import compile_cache
        from dcos_commons_tpu.utils.stored_program import StoredProgram

        self._jax = jax
        self._span = jax.profiler.TraceAnnotation
        self.config = config
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.page_tokens = page_tokens
        self.pages = pages
        self.chunk_tokens = chunk_tokens
        # what a table entry stands for is the attention's to say
        # (serve/paging.py RowLayout); the engine is given the same
        # layout, and the two programs below read it off ``config``
        eva = config.attention == "eva"
        n_conv = config.n_layers_of("conv")
        sliding = config.n_layers_of("sliding") > 0
        self.layout = RowLayout(
            page_tokens, config.window_size if eva else 0,
            config.chunk_size if eva else 0,
            # a row's share of ``cache["conv_state"]``
            state_bytes_per_row=n_conv * (config.conv_l_cache - 1)
            * config.d_model * jnp.dtype(config.dtype).itemsize,
            # window layers among full ones: a ring a slot, sized by
            # the window and this pool's chunk
            sliding_window=config.sliding_window if sliding else 0,
        ).with_chunk(chunk_tokens)
        ring_pages = self.layout.ring_pages
        self.pages_per_row = self.layout.table_len(max_len)
        self._put = put if put is not None else (lambda x: x)
        con = constrain_out if constrain_out is not None else (lambda x: x)

        init = functools.partial(
            init_paged_kv_cache, config, pages + 1, page_tokens,
            kv_dtype, slots, arena_lanes(config),
            slots * ring_pages + 1 if ring_pages else 0,
        )
        if cache_sharding is not None:
            if n_conv or ring_pages:
                raise ValueError(
                    "a sharded arena has no layout for conv state or for "
                    "the window layers' rings: a pattern with conv or "
                    "window attention layers is served on one device "
                    "(the serving gang lays ONE arena over its tp mesh)"
                )
            self.cache = jax.jit(init, out_shardings=cache_sharding)()
        else:
            self.cache = jax.jit(init)()

        self.chunk_riders = bool(riders) and chunk_carries_riders(config)

        # the mesh the arena is laid over is the ambient mesh while a
        # decode step is traced, alone or as a chunk's riders: its
        # attention kernel is chosen by it (models/decode.py
        # decode_attention_kernel)
        arena_mesh = (
            functools.partial(
                jax.sharding.use_abstract_mesh,
                cache_sharding.mesh.abstract_mesh,
            )
            if cache_sharding is not None else contextlib.nullcontext
        )

        def pick_rows(logits, temps, seeds, pos):
            def pick_row(lg, temp, seed, p):
                key = jax.random.fold_in(jax.random.key(seed), p)
                return sample_token(lg, temp, key)

            with jax.named_scope("sample"):
                return jax.vmap(pick_row)(logits, temps, seeds, pos)

        def _prefill(params, cache, counted, tokens, table, start,
                     true_len, temp, seed, slot, *step):
            # ``step``: what ``_decode`` takes after the cache, where
            # the chunk carries riders
            riders, mesh = None, contextlib.nullcontext
            if step:
                prev, carry, tok, pos, temps, seeds, tables = step
                riders = (jnp.where(carry, prev, tok), pos, tables)
                mesh = arena_mesh
            with mesh():
                logits, cache, counts, *rode = paged_prefill_chunk(
                    config, params, cache, tokens, table, start, true_len,
                    slot, riders, ring_pages,
                )
            if counts is not None:
                # this chunk's mixtures, and the chunk itself, on top
                # of the chunks nobody has fetched yet
                counted = counted + jnp.append(counts, 1)
            # the chunk's last real position is start + true_len - 1
            # == prompt_len - 1 on the final chunk: however a prompt
            # was chunked, its first token is sampled under one key
            with jax.named_scope("sample"):
                key = jax.random.fold_in(
                    jax.random.key(seed), start + true_len - 1
                )
                first = sample_token(logits[0], temp, key)
            chunk = (con(first), con(counted))
            if not step:
                return chunk, cache
            # the riders' tokens, as ``_decode`` hands a step's over
            nxt = pick_rows(rode[0], temps, seeds, pos)
            return (chunk, (con(nxt), con(jnp.zeros(2, jnp.int32)))), cache

        def _decode(params, cache, prev, carry, tok, pos, temps, seeds,
                    tables):
            # a row that rode the previous step reads its token where
            # that step left it: the host has not seen it yet
            tok = jnp.where(carry, prev, tok)
            with arena_mesh():
                logits, cache, counts = paged_decode_step(
                    config, params, cache, tok, pos, tables, ring_pages,
                )
            if counts is None:
                counts = jnp.zeros(2, jnp.int32)
            nxt = pick_rows(logits, temps, seeds, pos)
            return (con(nxt), con(counts)), cache

        # each is called as the compiled executable itself, which a
        # warm start loads from the store beside the compile cache
        # (utils/stored_program.py).  A pool laid over a mesh compiles
        # as ever and stores nothing: what ``put`` and
        # ``constrain_out`` do is in no key, and no sharded executable
        # has been shown to round-trip
        on_mesh = any(
            x is not None for x in (cache_sharding, put, constrain_out)
        )
        program = functools.partial(
            StoredProgram,
            donate_argnums=() if jax.default_backend() == "cpu" else (1,),
            # what the two close over that no argument's shape shows
            closed_over=dict(
                config=config, kv_dtype=kv_dtype, riders=self.chunk_riders,
            ),
            directory=None if on_mesh else compile_cache.programs_dir(),
        )
        self._prefill_c = program(_prefill)
        self._decode_c = program(_decode)
        self._jnp = jnp
        # the tokens of a step dispatched ahead that nobody has fetched
        # yet, as the device holds them: what a carried row reads.
        # Where there is none the program is still handed tokens, of
        # the same kind (a device value), so that it is traced once
        self._outstanding = None
        self._no_tokens = jax.jit(
            lambda: con(jnp.zeros(slots, jnp.int32))
        )()
        self._no_carry = np.zeros(slots, np.bool_)
        # a decode step that computes for nobody, by ``decode``'s names
        self._idle_step = dict(
            tok=np.zeros(slots, np.int32), pos=np.zeros(slots, np.int32),
            temps=np.zeros(slots, np.float32),
            seeds=np.zeros(slots, np.int32),
            tables=np.zeros((slots, self.pages_per_row), np.int32),
        )
        # what the decode steps' mixtures counted on the device, summed
        # as each step's tokens are fetched (the same ``device_get``):
        # live (token, expert) assignments, and expert groups that held
        # at least one, over the expert layers
        self._moe_counts = np.zeros(2, np.int64)
        # the same of the prefill chunks, and the chunks counted: the
        # device adds them up from chunk to chunk (few chunks are
        # fetched) and a prompt's last chunk brings the sum with its
        # token, after which the device starts from zero again
        self._moe_prefill_counts = np.zeros(3, np.int64)
        self._no_chunks = jax.jit(
            lambda: con(jnp.zeros(3, jnp.int32))
        )()
        self._chunks_unfetched = self._no_chunks

    def prefill_chunk(
        self, tokens: np.ndarray, slot: int, table: np.ndarray,
        start: int, true_len: int, temp: float, seed: int,
        final: bool = True, riders: Optional[dict] = None,
    ):
        """Run one [1, chunk_tokens] prompt chunk at virtual positions
        [start, start + true_len) through ``table``.  On a prompt's
        ``final`` chunk, returns the token sampled at its last real
        position; any other chunk is dispatched and NOT fetched
        (returns None): the host goes on while the device writes.
        ``slot`` is the engine's row id: where a pattern with conv
        layers keeps the row's state (models/decode.py); any other
        model's math needs only the table.

        ``riders`` (only where ``chunk_riders``): a decode step by
        ``decode``'s own names, ``tok pos temps seeds tables carry``,
        that rides in the chunk's program.  The call is then that
        ``decode(..., carry=)`` too: the step's tokens stay on the
        device, and what comes back is ``(the chunk's token or None,
        the PREVIOUS step's tokens)``."""
        with self._span("pool.prefill_chunk"):
            if riders is not None and not self.chunk_riders:
                raise ValueError(
                    "this pool's chunk program carries no riders"
                )
            step, previous = (), None
            if riders is not None:
                previous = self._outstanding
                step = self._step_args(previous, **riders)
            elif self.chunk_riders:
                step = self._step_args(
                    None, carry=self._no_carry, **self._idle_step
                )
            out, self.cache = self._prefill_c(
                self.params, self.cache, self._chunks_unfetched,
                self._put(np.asarray(tokens, np.int32)),
                self._put(np.asarray(table, np.int32)),
                np.int32(start), np.int32(true_len),
                np.float32(temp), np.int32(seed), np.int32(slot),
                *step,
            )
            (first, counted), rode = out if step else (out, None)
            if riders is not None:
                self._outstanding = rode
            if not final:
                # nobody reads this chunk's sample, and its writes
                # need no fence: whatever reads these pages later (a
                # chunk or decode step sharing them, export_page) is
                # a later program on the same in-order queue
                self._chunks_unfetched = counted
                first = None
            else:
                self._chunks_unfetched = self._no_chunks
                with self._span("pool.prefill_chunk.fetch"):
                    first, counted = self._jax.device_get((first, counted))
                self._moe_prefill_counts += np.asarray(counted, np.int64)
                first = int(first)
            if riders is None:
                return first
            return first, self._fetch(previous)

    def _step_args(self, previous, carry, tok, pos, temps, seeds, tables):
        """A decode step as either program takes it after the cache:
        the outstanding step's tokens (a device value) first."""
        return (
            self._no_tokens if previous is None else previous[0],
            self._put(np.asarray(carry, np.bool_)),
            self._put(np.asarray(tok, np.int32)),
            self._put(np.asarray(pos, np.int32)),
            self._put(np.asarray(temps, np.float32)),
            self._put(np.asarray(seeds, np.int32)),
            self._put(np.asarray(tables, np.int32)),
        )

    def decode(
        self, tok: np.ndarray, pos: np.ndarray,
        temps: np.ndarray, seeds: np.ndarray,
        tables: np.ndarray, n_active: Optional[int] = None,
        carry: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Dispatch one decode step over the whole pool through
        per-row page tables (inactive rows' outputs are discarded by
        the engine).  ``n_active`` is the engine's bookkeeping rider
        (the gang driver stamps it into the broadcast head); the
        computation always covers every slot — static shapes.

        ``carry=None``: synchronous.  Returns THIS step's next tokens
        [slots] after ONE bulk device fetch — per-element reads are a
        transfer each.

        ``carry`` a bool [slots]: a row it marks takes its input token
        from the previous step's output on the device (``tok`` there
        is ignored), so this step is queued before anybody has read
        that output.  Then the PREVIOUS step's tokens are fetched,
        while the device runs this one, and returned; an empty array
        when no step was outstanding.  This step's stay on the device
        for the next call, or ``resolve_decode``."""
        with self._span("pool.decode"):
            ahead = carry is not None
            previous = self._outstanding
            if not ahead and previous is not None:
                raise RuntimeError(
                    "synchronous decode with a step outstanding: "
                    "resolve_decode() first"
                )
            nxt, self.cache = self._decode_c(
                self.params, self.cache, *self._step_args(
                    previous, carry if ahead else self._no_carry,
                    tok, pos, temps, seeds, tables,
                ),
            )
            self._outstanding = nxt if ahead else None
            return self._fetch(previous if ahead else nxt)

    def resolve_decode(self) -> np.ndarray:
        """Fetch the outstanding step's tokens without dispatching
        another; an empty array when nothing is outstanding."""
        with self._span("pool.decode"):
            previous, self._outstanding = self._outstanding, None
            return self._fetch(previous)

    def loop_counters(self) -> dict:
        """Cumulative sums for the engine's ``loop`` (``/stats``), one
        step behind the dispatch like the tokens they came with; the
        prefill chunks' arrive with their prompt's last chunk, so
        ``moe_prefill_chunks_counted`` (not ``prefill_calls``) is what
        the two sums beside it are sums over."""
        return {
            "moe_assignments_sum": int(self._moe_counts[0]),
            "moe_groups_touched_sum": int(self._moe_counts[1]),
            "moe_prefill_assignments_sum": int(self._moe_prefill_counts[0]),
            "moe_prefill_groups_touched_sum": int(
                self._moe_prefill_counts[1]
            ),
            "moe_prefill_chunks_counted": int(self._moe_prefill_counts[2]),
        }

    def _fetch(self, step) -> np.ndarray:
        """One step's (tokens, counts) in ONE ``device_get``."""
        if step is None:
            return np.zeros(0, np.int32)
        try:
            with self._span("pool.decode.fetch"):
                tokens, counts = self._jax.device_get(step)
            self._moe_counts += np.asarray(counts, np.int64)
            return np.asarray(tokens)
        except BaseException:
            # an asynchronous dispatch's error surfaces here: whatever
            # was queued behind it is lost with it
            self._outstanding = None
            raise

    def export_page(self, page: int) -> dict:
        """Snapshot one physical page as host numpy, every cache key
        included (int8 arenas ship their per-vector scales too — a
        page without its scales decodes to garbage).  A page is
        ``page_tokens`` ENTRIES of every layer, whatever they are: a
        row's exact K/V, or (an EVA row's summary region) its chunk
        summaries — which, is the table entry's to say, not the
        page's.  Single-caller
        contract like ``prefill_chunk``/``decode``: only the engine
        loop may call this (serve/engine.py routes it through the
        page-I/O queue), since it reads ``self.cache`` mid-stream.
        Refused where a page is not all there is to know of its
        positions (``RowLayout.carries_state``)."""
        if self.layout.carries_state:
            raise ValueError(self.layout.carries_state)
        return {
            key: np.asarray(self._jax.device_get(arr[:, page]))
            for key, arr in self.cache.items()
        }

    def import_page(self, page: int, payload: dict) -> None:
        """Splice one exported page into physical page ``page`` of
        THIS arena.  Keys must match this pool's cache layout (both
        ends run the same model/kv_dtype — the migration geometry
        check upstream guarantees page_tokens; dtype mismatch raises
        here).  Same single-caller contract as ``export_page``, and
        the same refusal."""
        if self.layout.carries_state:
            raise ValueError(self.layout.carries_state)
        if set(payload) != set(self.cache):
            raise ValueError(
                f"page payload keys {sorted(payload)} do not match "
                f"arena keys {sorted(self.cache)} (kv_dtype mismatch?)"
            )
        for key, arr in self.cache.items():
            self.cache[key] = arr.at[:, page].set(
                self._jnp.asarray(payload[key], arr.dtype)
            )

    def warm(self, ahead: bool = True) -> None:
        """Load or compile, and execute, both entry points before
        readiness, in every way the engine loop will call them, so
        that nothing is traced under traffic: a chunk unfetched and
        fetched, a decode
        step dispatched behind one still unread (the carried tokens
        that step's own output, a device value) and the resolve; where
        the chunk carries riders, a step riding a chunk behind a
        decode step and a decode step behind a rider chunk.
        ``ahead=False`` warms the synchronous calls alone: the gang
        driver's, whose ticks are resolved as they return.  All
        tables are zero, so every write lands in the trash page and
        every gather is masked — warmup leaves no residue a real
        request could attend to."""
        chunk = dict(
            tokens=np.zeros((1, self.chunk_tokens), np.int32), slot=0,
            table=np.zeros(self.pages_per_row, np.int32),
            start=0, true_len=self.chunk_tokens, temp=0.0, seed=0,
        )
        for final in (False, True) if ahead else (True,):
            self.prefill_chunk(**chunk, final=final)
        step = self._idle_step
        if not ahead:
            self.decode(**step)
            return
        for carry in (self._no_carry, ~self._no_carry):
            self.decode(**step, carry=carry)
            if self.chunk_riders:
                self.prefill_chunk(
                    **chunk, final=bool(carry[0]),
                    riders=dict(step, carry=~carry),
                )
        self.resolve_decode()
