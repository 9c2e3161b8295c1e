"""The tick's decode step rides the tick's prefill chunk (ISSUE 40):
where the device half's chunk program carries a decode step, a tick
that has a chunk makes ONE device call, and everything the loop one
call ahead holds (tests/test_engine_one_ahead.py) it still holds.

Against the chain fake behind ``testing.chain_model.ChunkRiders``,
which logs ("chunk", slot, start, fetched), ("ride", step) for a step
that rode a chunk, ("dispatch", step) for a decode call of its own and
("resolve", step).
"""

import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.serve.migration import MigrationError
from dcos_commons_tpu.testing.chain_model import (
    ChainModel,
    ChunkRiders,
    OneAhead,
    V,
    chain_next,
    chain_oracle,
    settled_stats,
    swarm,
)
from dcos_commons_tpu.trace import TraceRecorder


def _engine(half, slots, pages, max_len=32, prompt_len=24, chunk=5,
            **kw):
    ahead = half.engine_kwargs() if isinstance(half, OneAhead) else {}
    return PagedEngine(
        half.prefill_chunk, half.decode, slots, max_len, prompt_len,
        page_tokens=4, pages=pages, chunk_tokens=chunk,
        prefix_cache=False, **{**ahead, **kw},
    )


def _ticked(engine, half):
    """Mark every scheduling round in the fake's log."""
    work_tick = engine._work_tick

    def marked():
        half.log.append(("tick",))
        work_tick()

    engine._work_tick = marked
    return engine


def _ticks(log):
    """The device calls of each tick that made any, by kind."""
    ticks = [[]]
    for entry in log:
        if entry[0] == "tick":
            ticks.append([])
        elif entry[0] != "resolve":
            ticks[-1].append(entry[0])
    return [t for t in ticks if t]


# the short prompt is one chunk, the long one three: both rows are
# admitted in one tick, and the long prompt prefills while the short
# row decodes
SHORT, LONG, N = [7, 7], list(range(1, 14)), 9


def test_a_tick_with_a_chunk_is_one_device_call():
    half = ChunkRiders(ChainModel(slots=2))
    tracer = TraceRecorder(capacity=256)
    engine = _ticked(
        _engine(half, slots=2, pages=16, tracer=tracer), half
    )
    try:
        assert engine.submit([SHORT, LONG], N) == [
            chain_oracle(SHORT, N), chain_oracle(LONG, N)
        ]
        loop = settled_stats(engine)["loop"]
    finally:
        engine.stop()
    log = [e for e in half.log if e[0] not in ("resolve", "tick")]
    # tick 1: the short prompt's chunk found nobody decoding; the long
    # prompt's first chunk carried the short row's first step.  Ticks
    # 2 and 3: one call each, the chunk with the step in it.  Only
    # then, with nothing left to prefill, a decode call of its own
    assert log[:7] == [
        ("chunk", 0, 0, True), ("chunk", 1, 0, False), ("ride", 0),
        ("chunk", 1, 5, False), ("ride", 1),
        ("chunk", 1, 10, True), ("ride", 2),
    ]
    assert {e[0] for e in log[7:]} == {"dispatch"}
    assert _ticks(half.log)[:4] == [
        ["chunk", "chunk", "ride"], ["chunk", "ride"], ["chunk", "ride"],
        ["dispatch"],
    ]
    assert loop["prefill_calls"] == 4
    assert loop["prefill_rider_calls"] == 3
    # a rider step is a decode step in every sum
    assert loop["decode_calls"] == half.steps
    assert loop["decode_ahead_calls"] == half.steps - 1
    assert loop["decode_rows_sum"] == 2 * (N - 1)
    assert loop["ahead_discarded_rows"] == 0
    # the long row's first token came out of tick 3's program: it
    # joined the step after, as a fresh row does
    assert half.log.index(("ride", 2)) < half.log.index(("dispatch", 3))
    ticks = [s for s in tracer.snapshot() if s.name == "engine.tick"]
    assert sum(s.attrs["riders"] for s in ticks) == 3
    assert all(s.attrs["riders"] <= s.attrs["chunks"] for s in ticks)


def test_a_step_is_resolved_by_the_call_after_it_whichever_it_is():
    half = ChunkRiders(ChainModel(slots=2))
    engine = _engine(half, slots=2, pages=16)
    try:
        engine.submit([SHORT, LONG], N)
        settled_stats(engine)
    finally:
        engine.stop()
    sent = [e for e in half.log if e[0] in ("ride", "dispatch")]
    resolved = [e[1] for e in half.log if e[0] == "resolve"]
    assert [e[1] for e in sent] == resolved == list(range(half.steps))
    for kind, k in sent[1:]:
        # step k went out before step k - 1 was read
        assert half.log.index((kind, k)) < half.log.index(("resolve", k - 1))
    assert half._outstanding is None and not engine._inflight


def test_two_rows_prefilling_in_one_tick_send_one_step():
    """The tick's first chunk call carries the step; the second row's
    chunk of the same tick carries nobody."""
    half = ChunkRiders(ChainModel(slots=3))
    engine = _ticked(_engine(half, slots=3, pages=24), half)
    try:
        rows = [SHORT, LONG, list(range(20, 31))]
        assert engine.submit(rows, N) == [chain_oracle(r, N) for r in rows]
        loop = settled_stats(engine)["loop"]
    finally:
        engine.stop()
    ticks = _ticks(half.log)
    assert ticks[0] == ["chunk", "chunk", "ride", "chunk"]
    assert ticks[1] == ticks[2] == ["chunk", "ride", "chunk"]
    assert all(t.count("ride") <= 1 and "dispatch" not in t
               for t in ticks if "ride" in t)
    assert loop["prefill_calls"] == 7 and loop["prefill_rider_calls"] == 3
    assert loop["decode_calls"] == half.steps


def test_depth_0_never_passes_riders():
    """Without ``resolve_decode_fn`` the loop is the synchronous one,
    whatever the device half's chunks could carry."""
    model = ChainModel(slots=2)
    engine = _engine(model, slots=2, pages=16, chunk_riders=True)
    try:
        # ChainModel.prefill_chunk takes no ``riders``: one would raise
        assert engine.submit([SHORT, LONG], N) == [
            chain_oracle(SHORT, N), chain_oracle(LONG, N)
        ]
        loop = engine.stats()["loop"]
    finally:
        engine.stop()
    assert loop["prefill_rider_calls"] == 0
    assert loop["decode_calls"] == model.decode_calls


def test_a_device_half_whose_chunks_carry_nothing_is_the_loop_of_depth_1():
    half = OneAhead(ChainModel(slots=2))
    engine = _engine(half, slots=2, pages=16)
    try:
        engine.submit([SHORT, LONG], N)
        loop = settled_stats(engine)["loop"]
    finally:
        engine.stop()
    assert loop["prefill_rider_calls"] == 0
    assert not [e for e in half.log if e[0] == "ride"]


@pytest.mark.parametrize("n,eos", [
    (1, None),     # the chunk's own token is the row's last
    (2, None),     # the first rider step is the row's last by ``n``
    (N, "third"),  # ended by ``eos`` with the next step riding a chunk
])
def test_endings_while_another_row_prefills(n, eos):
    """The decoding row ends by ``n`` or by ``eos`` in ticks that have
    a chunk: what it read is the oracle's, the sample of a step queued
    behind its end is dropped, and its pages come home."""
    long = list(range(1, 22))  # five chunks
    if eos is not None:
        eos = chain_oracle(SHORT, N)[2]
    want = [chain_oracle(SHORT, n, eos), chain_oracle(long, n, eos)]
    half = ChunkRiders(ChainModel(slots=2))
    engine = _engine(half, slots=2, pages=16)
    try:
        assert engine.submit([SHORT, long], n, eos_id=eos) == want
        stats = settled_stats(engine)
    finally:
        engine.stop()
    # a row cut by ``eos`` at a decode step that was not also its last
    # by ``n`` had its next step queued already
    cuts = sum(2 <= len(out) < n and out[-1] == eos for out in want)
    assert stats["loop"]["ahead_discarded_rows"] == cuts == (eos is not None)
    assert stats["kv_pages_free"] == 16 and stats["active_slots"] == 0
    assert half._outstanding is None and not engine._inflight
    engine._allocator.check_invariants()
    rides = [e for e in half.log if e[0] == "ride"]
    assert len(rides) == {1: 0, 2: 1}.get(n, 3)


def test_max_len_ends_a_row_whose_step_rode_a_chunk():
    half = ChunkRiders(ChainModel(slots=2))
    engine = _engine(half, slots=2, pages=16, max_len=12, prompt_len=10)
    try:
        short, long = [3] * 8, list(range(1, 11))
        # 8 + n would pass max_len 12: the row stops at position 12
        got = engine.submit([short, long], 2)
        assert got == [chain_oracle(short, 2), chain_oracle(long, 2)]
        got = engine.submit([short], 4)
        assert got == [chain_oracle(short, 4)]
        stats = settled_stats(engine)
    finally:
        engine.stop()
    assert stats["kv_pages_free"] == 16
    assert not engine._inflight


def _while_prefilling(engine, half, long_chunks=6):
    """Start a row that decodes for long and a prompt of
    ``long_chunks`` chunks behind it; returns their threads and
    results once the first rider step has gone out."""
    results = {}

    def client(key, prompt, n):
        try:
            results[key] = engine.submit([prompt], n)
        except BaseException as e:  # noqa: BLE001 — asserted on
            results[key] = e

    threads = [
        threading.Thread(target=client, args=("a", SHORT, 24)),
        threading.Thread(
            target=client,
            args=("b", list(range(1, 5 * long_chunks)), 4),
        ),
    ]
    threads[0].start()
    deadline = time.monotonic() + 10
    while half.steps < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    threads[1].start()
    while not any(e[0] == "ride" for e in half.log):
        assert time.monotonic() < deadline, "no step ever rode a chunk"
        time.sleep(0.0005)
    return threads, results


class _Slow(ChainModel):
    """Each call takes a moment: verbs from other threads land while
    rows prefill and decode.  A step's rows are not held to their
    tables: a fenced row rides a zero table at its own position."""

    def prefill_chunk(self, *args):
        time.sleep(0.003)
        return super().prefill_chunk(*args)

    def decode(self, tok, pos, temps, seeds, tables, n_active):
        time.sleep(0.002)
        self.decode_calls += 1
        return np.asarray(
            [chain_next(int(t), int(p)) for t, p in zip(tok, pos)],
            np.int32,
        )


def test_a_frozen_row_rides_no_chunk_and_loses_no_sample():
    """A fence dropped while steps ride chunks: the outstanding step
    is resolved and applied first (``_drain``), the frozen row stands
    still with a zero table in every rider step, and after the fence
    lifts the client reads the oracle."""
    half = ChunkRiders(_Slow(slots=2))
    engine = _engine(
        half, slots=2, pages=24, max_len=64, prompt_len=40,
        read_page=lambda page: {}, write_page=lambda page, payload: None,
    )
    try:
        threads, results = _while_prefilling(engine, half)
        rid = next(
            s["rid"] for s in engine.sessions() if s["state"] == "decode"
        )
        fences = 0
        while threads[0].is_alive() and fences < 4:
            try:
                engine.freeze(rid)
            except MigrationError:
                break
            with engine._cv:
                row = engine._find_rid_locked(rid)
                stood = (list(row.out), int(engine._pos[row.slot]))
                # the verb ran behind a drain: nothing is in flight
                # for the row, and ``pos`` is what ``out`` says
                assert all(step[row.slot] is None
                           for step in engine._inflight)
                assert stood[1] == len(SHORT) + len(stood[0]) - 1
            time.sleep(0.02)  # several ticks, some with chunks
            with engine._cv:
                assert (list(row.out), int(engine._pos[row.slot])) == stood
            engine.unfreeze(rid)
            fences += 1
            time.sleep(0.005)
        for t in threads:
            t.join(timeout=20)
        assert fences >= 1
        assert results["a"] == [chain_oracle(SHORT, 24)]
        assert results["b"] == [chain_oracle(list(range(1, 30)), 4)]
        stats = settled_stats(engine)
        assert stats["loop"]["ahead_discarded_rows"] == 0
        assert stats["loop"]["prefill_rider_calls"] >= 1
        engine._allocator.check_invariants()
    finally:
        engine.stop()


def test_an_abandoned_row_leaves_the_rider_steps():
    """A client that gives up while its row's steps ride another
    row's chunks: the row retires, its pages come home, and the row
    that was prefilling is served exactly."""
    half = ChunkRiders(_Slow(slots=2))
    engine = _engine(half, slots=2, pages=24, max_len=64, prompt_len=40)
    try:
        threads, results = _while_prefilling(engine, half, long_chunks=7)
        with engine._cv:
            row = next(r for r in engine._rows if r is not None)
            row.group.abandoned = True
        threads[1].join(timeout=20)
        assert results["b"] == [chain_oracle(list(range(1, 35)), 4)]
        stats = settled_stats(engine)
        assert stats["active_slots"] == 0
        assert stats["kv_pages_free"] == 24
        assert half._outstanding is None and not engine._inflight
        engine._allocator.check_invariants()
        # and serves on
        assert engine.submit([[9, 9, 1]], 5) == [chain_oracle([9, 9, 1], 5)]
    finally:
        engine.stop()


class _ChunkFailsAt(ChainModel):
    """Raises from its ``at``-th chunk call, once."""

    def __init__(self, at, **kw):
        super().__init__(**kw)
        self.at = at

    def prefill_chunk(self, *args):
        if self.prefills == self.at:
            self.at = -1
            raise RuntimeError("device fell over")
        return super().prefill_chunk(*args)


def test_a_failing_rider_chunk_fans_out_and_leaves_no_handle_behind():
    half = ChunkRiders(_ChunkFailsAt(3, slots=2))
    engine = _engine(half, slots=2, pages=16)
    try:
        with pytest.raises(RuntimeError, match="fell over"):
            engine.submit([SHORT, LONG], N)
        stats = settled_stats(engine)
        assert half._outstanding is None and not engine._inflight
        assert stats["active_slots"] == 0 and stats["kv_pages_free"] == 16
        assert engine.submit([LONG], 7) == [chain_oracle(LONG, 7)]
    finally:
        engine.stop()


def test_chunk_riders_property_any_request_mix_matches_oracle():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.given(
        st.lists(
            st.tuples(
                st.lists(
                    st.lists(st.integers(0, V - 1), min_size=1,
                             max_size=9),
                    min_size=1, max_size=3,
                ),
                st.integers(1, 8),
                st.one_of(st.none(), st.integers(0, V - 1)),
            ),
            min_size=1, max_size=6,
        ),
        st.integers(1, 4),   # slots
        st.integers(3, 10),  # pages (>= one worst-case request: 3)
        st.integers(1, 6),   # chunk width
    )
    @hypothesis.settings(
        max_examples=40, deadline=None,
        suppress_health_check=[hypothesis.HealthCheck.too_slow],
    )
    def run(jobs, slots, pages, chunk):
        max_len = 12
        jobs = [
            (rows, min(n, max_len - max(len(r) for r in rows)), eos)
            for rows, n, eos in jobs
        ]
        jobs = [j for j in jobs if j[1] >= 1]
        if not jobs:
            return
        served = {}
        for kind in (OneAhead, ChunkRiders):
            half = kind(ChainModel())
            engine = _engine(
                half, slots=slots, pages=pages, max_len=max_len,
                prompt_len=9, chunk=chunk,
            )
            try:
                served[kind] = swarm(engine, jobs)
                stats = settled_stats(engine)
                assert stats["active_slots"] == 0
                assert stats["queue_depth"] == 0
                assert stats["kv_pages_free"] == pages
                assert stats["kv_pages_reserved"] == 0
                engine._allocator.check_invariants()
                loop = stats["loop"]
                assert loop["decode_calls"] == half.steps
                rides = sum(e[0] == "ride" for e in half.log)
                assert loop["prefill_rider_calls"] <= rides
                assert rides <= loop["prefill_calls"]
                assert half._outstanding is None and not engine._inflight
            finally:
                engine.stop()
        # the same prompts give the same outputs with and without
        # riders: the oracle's
        assert served[OneAhead] == served[ChunkRiders] == [
            [chain_oracle(r, n, eos) for r in rows]
            for rows, n, eos in jobs
        ]

    run()


# -- through the real pool ---------------------------------------------------


@pytest.fixture(scope="module")
def eva():
    import jax

    from dcos_commons_tpu.models import init_params
    from test_eva_serving import _config

    config = _config()
    return config, init_params(config, jax.random.key(3))


def _real(config, params, riders, slots=3, pages=60):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    pool = PagedPoolModel(
        config, params, slots, 160, 4, pages, 8, riders=riders
    )
    pool.warm()
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, slots, 160, 112,
        page_tokens=4, pages=pages, chunk_tokens=8, prefix_cache=False,
        layout=pool.layout, queue_timeout_s=120,
        resolve_decode_fn=pool.resolve_decode,
        chunk_riders=pool.chunk_riders,
    )
    return pool, engine


def test_real_pool_same_prompts_and_seeds_same_outputs(eva, monkeypatch):
    """Greedy and sampled rows through the real pool at toy size: the
    chunk that carries the step samples what the two programs sample,
    under the same keys."""
    from dcos_commons_tpu.serve import engine as engine_mod

    config, params = eva
    monkeypatch.setattr(
        engine_mod.os, "urandom", lambda n: (7654321).to_bytes(n, "little")
    )
    rng = np.random.default_rng(40)
    prompts = [
        [int(t) for t in rng.integers(0, 320, n)] for n in (5, 50, 21, 70)
    ]
    temps = (0.0, 0.9, 1.2, 0.0)
    outs, loops = {}, {}
    for riders in (False, True):
        pool, engine = _real(config, params, riders)
        try:
            results = [None] * len(prompts)

            def client(i):
                results[i] = engine.submit(
                    [prompts[i]], 20, temperature=temps[i]
                )[0]

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
                time.sleep(0.01)
            for t in threads:
                t.join(timeout=120)
            outs[riders] = results
            loops[riders] = settled_stats(engine)["loop"]
            assert pool._prefill_c._cache_size() == 1
            assert pool._decode_c._cache_size() == 1
        finally:
            engine.stop()
    assert outs[True] == outs[False]
    assert all(len(out) == 20 for out in outs[True])
    assert loops[False]["prefill_rider_calls"] == 0
    assert loops[True]["prefill_rider_calls"] > 0


def test_a_pool_without_riders_refuses_them(eva):
    config, params = eva
    pool, engine = _real(config, params, riders=False)
    engine.stop()
    assert not pool.chunk_riders
    with pytest.raises(ValueError, match="carries no riders"):
        pool.prefill_chunk(
            np.zeros((1, 8), np.int32), slot=0,
            table=np.zeros(pool.pages_per_row, np.int32), start=0,
            true_len=8, temp=0.0, seed=0, riders={},
        )


def test_pool_rider_chunk_leaves_its_step_outstanding(eva):
    """The protocol, call by call: a rider chunk is ``decode(carry=)``
    as far as the outstanding step goes."""
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config, params = eva
    pool = PagedPoolModel(config, params, 2, 160, 4, 20, 8, riders=True)
    chunk = dict(
        tokens=np.zeros((1, 8), np.int32), slot=0,
        table=np.zeros(pool.pages_per_row, np.int32), start=0,
        true_len=8, temp=0.0, seed=0,
    )
    step = dict(
        tok=np.zeros(2, np.int32), pos=np.zeros(2, np.int32),
        temps=np.zeros(2, np.float32), seeds=np.zeros(2, np.int32),
        tables=np.zeros((2, pool.pages_per_row), np.int32),
    )
    carry = np.zeros(2, bool)
    # a chunk alone: what it returned before
    assert pool.prefill_chunk(**chunk, final=False) is None
    assert isinstance(pool.prefill_chunk(**chunk), int)
    assert pool.resolve_decode().size == 0
    # a rider chunk with nothing outstanding resolves nothing
    first, previous = pool.prefill_chunk(
        **chunk, final=False, riders=dict(step, carry=carry)
    )
    assert first is None and previous.size == 0
    # its step is outstanding: the next call, of either kind, reads it
    rode = pool.decode(**step, carry=~carry)
    assert rode.shape == (2,)
    first, previous = pool.prefill_chunk(
        **chunk, riders=dict(step, carry=~carry)
    )
    assert isinstance(first, int) and previous.shape == (2,)
    with pytest.raises(RuntimeError, match="outstanding"):
        pool.decode(**step)
    assert pool.resolve_decode().shape == (2,)
    assert pool.resolve_decode().size == 0
    # idle riders never touch the outstanding step
    pool.decode(**step, carry=carry)
    assert pool.prefill_chunk(**chunk, final=False) is None
    assert pool.resolve_decode().shape == (2,)
    assert pool._prefill_c._cache_size() == 1
