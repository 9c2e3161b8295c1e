"""Continuous batching: the engine's scheduling machinery with decode
ROWS as the binding resource.

ENGINE properties against the deterministic chain model
(dcos_commons_tpu/testing/chain_model.py, no jax): FIFO admission,
row reuse, early per-row retirement, queue timeout, occupancy
accounting, error fan-out — none may change any row's token chain no
matter how requests arrive, because each row's next token depends
only on that row's own (token, position) state.  A hypothesis sweep
drives arbitrary request mixes through a thread swarm.

The geometry here is full residency (a page budget that holds every
row at MAX_LEN) and a prefill chunk as long as the longest prompt, so
a prompt is admitted in one call and a request only ever waits for a
row; tests/test_paged_kv.py holds the same engine under page
pressure, chunked prefill and the real model.
"""

import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine, QueueTimeoutError
from dcos_commons_tpu.testing.chain_model import (
    ChainModel,
    V as _V,
    chain_oracle as _chain_oracle,
    swarm as _swarm,
)


@pytest.fixture(scope="module", autouse=True)
def _racecheck_probes():
    """Dynamic race probes (SDKLINT_RACECHECK=1): watch every attribute
    the static pass reports as cross-thread shared on the engine loop's
    classes; the session fixture fails the run on any unordered write
    pair.  No-op in the fast tier."""
    from conftest import racecheck_watch_guard

    yield from racecheck_watch_guard(PagedEngine)


def _engine(model, slots, max_len=64, prompt_len=32, **kw):
    return PagedEngine(
        model.prefill_chunk, model.decode, slots, max_len, prompt_len,
        page_tokens=model.page_tokens,
        pages=slots * -(-max_len // model.page_tokens),
        chunk_tokens=prompt_len, prefix_cache=False, **kw
    )


def test_engine_rows_reproduce_oracle_under_concurrency():
    model = ChainModel(slots=3)
    engine = _engine(model, slots=3)
    try:
        jobs = [
            ([[1, 2, 3]], 8, None),
            ([[4], [5, 6]], 5, None),
            ([[7, 8, 9, 10]], 1, None),   # retires at admission
            ([[2, 2]], 8, None),
        ]
        results = _swarm(engine, jobs)
        for (rows, n, eos), result in zip(jobs, results):
            assert result == [_chain_oracle(r, n, eos) for r in rows]
        assert model.max_active >= 2  # rows really shared ticks
        stats = engine.stats()
        assert stats["active_slots"] == 0
        assert stats["free_slots"] == 3
        assert stats["requests_completed"] == len(jobs)
        assert stats["tokens_out"] == sum(
            len(r) for result in results for r in result
        )
    finally:
        engine.stop()


def test_engine_eos_retires_row_early():
    model = ChainModel(slots=2)
    engine = _engine(model, slots=2)
    try:
        prompt = [3, 1]
        full = _chain_oracle(prompt, 10)
        eos = full[4]
        got = engine.submit([prompt], 10, eos_id=eos)[0]
        assert got == full[:5]  # cut at (and including) the eos token
        assert engine.stats()["active_slots"] == 0
    finally:
        engine.stop()


def test_engine_slot_exhaustion_queues_and_completes():
    """More concurrent requests than slots: the overflow WAITS for a
    retirement (no error, no corruption) and every chain still
    matches the oracle."""
    model = ChainModel(slots=2)
    engine = _engine(model, slots=2)
    try:
        jobs = [([[i + 1]], 6, None) for i in range(7)]
        results = _swarm(engine, jobs)
        for (rows, n, eos), result in zip(jobs, results):
            assert result == [_chain_oracle(rows[0], n, eos)]
        assert model.max_active <= 2  # never more rows than slots
    finally:
        engine.stop()


def test_engine_queue_timeout_is_distinguishable_overload():
    """A wedged pool raises QueueTimeoutError (-> HTTP 503), and the
    timed-out request leaves the queue (abandoned work never reaches
    the chip)."""
    gate = threading.Event()  # never set: decode wedges
    model = ChainModel(slots=1, step_gate=gate)
    engine = _engine(model, slots=1, queue_timeout_s=0.3)
    try:
        # one long-running occupant wedges the only slot
        occupant = threading.Thread(
            target=lambda: pytest.raises(
                Exception, engine.submit, [[9]], 8
            ),
            daemon=True,
        )
        occupant.start()
        time.sleep(0.1)  # let it admit
        t0 = time.monotonic()
        with pytest.raises(QueueTimeoutError) as exc:
            engine.submit([[5]], 4)
        assert time.monotonic() - t0 < 5.0
        assert isinstance(exc.value, RuntimeError)  # 503 mapping basis
        assert exc.value.kind == "kv-slot"  # pages are ample: a ROW
        # BOTH requests overran: the wedged occupant times out too
        # (its slot is retired as abandoned at the next tick)
        deadline = time.monotonic() + 5
        while (engine.stats()["requests_timed_out"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert engine.stats()["requests_timed_out"] == 2
        assert engine.stats()["queue_depth"] == 0  # removed itself
    finally:
        gate.set()
        engine.stop()


def test_engine_occupancy_accounting_mid_flight():
    """KV occupancy tracks live positions per tick: with the decode
    gated, stats between ticks show the admitted rows' prompt+output
    positions and drop back to zero at retirement."""
    gate = threading.Event()
    model = ChainModel(slots=2, step_gate=gate)
    engine = _engine(model, slots=2, max_len=64, prompt_len=32)
    try:
        # ONE submit carrying both rows: they enter the queue
        # atomically, so the first admission pass seats them together
        # (separate clients could race the first gated tick)
        result = [None]

        def client():
            result[0] = engine.submit([[1, 2, 3], [4, 5]], 3)

        swarm = threading.Thread(target=client)
        swarm.start()

        def wait_stats(pred, what):
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                s = engine.stats()
                if pred(s):
                    return s
                time.sleep(0.01)
            raise AssertionError(f"{what}: {engine.stats()}")

        # both admitted (first token each), blocked before tick 1:
        # live = prompt positions (3 + 2)
        s = wait_stats(
            lambda s: s["active_slots"] == 2, "both rows admitted"
        )
        assert s["kv_live_tokens"] == 5
        # occupancy is PHYSICAL: one page a row of the 2 x 16
        assert s["kv_occupancy"] == round(2 / (2 * 16.0), 4)
        gate.set()  # tick 1: each row +1 position
        s = wait_stats(
            lambda s: s["kv_live_tokens"] == 7, "tick 1 accounted"
        )
        gate.set()  # tick 2: rows hit n=3 and retire
        s = wait_stats(
            lambda s: s["active_slots"] == 0, "rows retired"
        )
        assert s["kv_live_tokens"] == 0
        assert s["free_slots"] == 2
        swarm.join(timeout=10)
        assert not swarm.is_alive()
        assert result[0] == [
            _chain_oracle([1, 2, 3], 3), _chain_oracle([4, 5], 3),
        ]
    finally:
        gate.set()
        engine.stop()


def test_engine_prefill_failure_signals_group_and_frees_slot():
    """A prefill failure must surface to ITS OWN group immediately
    (not leave the client waiting out the full timeout) and return
    the popped slot to the pool (review finding: transient device
    errors must not drain the pool)."""
    model = ChainModel(slots=2)
    boom = RuntimeError("prefill exploded")
    model.prefill_chunk = lambda *a, **kw: (_ for _ in ()).throw(boom)
    engine = _engine(model, slots=2, queue_timeout_s=30)
    try:
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="prefill exploded"):
            engine.submit([[1, 2]], 4)
        assert time.monotonic() - t0 < 5.0  # error, not timeout
        stats = engine.stats()
        assert stats["free_slots"] == 2 and stats["active_slots"] == 0
    finally:
        engine.stop()


def test_engine_slow_healthy_generation_is_not_cut_off():
    """The timeout bounds saturation (no slot) and stalls (no new
    token for a window) — NOT total duration: a generation slower
    than the window that keeps producing completes."""
    model = ChainModel(slots=1)
    orig = model.decode

    def slow_decode(*args):
        time.sleep(0.15)  # half a timeout window per tick: slow, but
        return orig(*args)  # a token lands inside every window

    model.decode = slow_decode
    engine = _engine(model, slots=1, queue_timeout_s=0.3)
    try:
        # 6 tokens x 0.15s/tick ~= 0.9s total, 3x the window — but a
        # token lands every window, so the request must complete
        got = engine.submit([[4, 2]], 6)[0]
        assert got == _chain_oracle([4, 2], 6)
        assert engine.stats()["requests_timed_out"] == 0
    finally:
        engine.stop()


def test_engine_model_failure_fans_out():
    model = ChainModel(slots=2, fail=RuntimeError("chip gone"))
    engine = _engine(model, slots=2)
    try:
        with pytest.raises(RuntimeError, match="chip gone"):
            engine.submit([[1, 2]], 4)
        # the pool is clean afterwards: slots freed, nothing active
        stats = engine.stats()
        assert stats["active_slots"] == 0 and stats["free_slots"] == 2
    finally:
        engine.stop()


def test_engine_survives_malformed_decode_output():
    """A decode_fn returning the wrong shape (gang payload bug) blows
    up in BOOKKEEPING, not in the guarded model call — the loop must
    fan the error out fast and keep serving, not die silently and
    hang every later client for the full timeout."""
    model = ChainModel(slots=2)
    bad = [True]
    orig = model.decode

    def decode(*args):
        if bad[0]:
            return np.zeros(0, np.int32)  # too short: IndexError later
        return orig(*args)

    model.decode = decode
    engine = _engine(model, slots=2, queue_timeout_s=30)
    try:
        t0 = time.monotonic()
        with pytest.raises(IndexError):
            engine.submit([[1, 2]], 4)
        assert time.monotonic() - t0 < 5.0  # fast fan-out, no timeout
        # the loop survived: a well-formed request still serves
        bad[0] = False
        assert engine.submit([[3]], 4)[0] == _chain_oracle([3], 4)
    finally:
        engine.stop()


def test_engine_rejects_caller_errors():
    model = ChainModel(slots=1)
    engine = _engine(model, slots=1, max_len=16, prompt_len=8)
    try:
        with pytest.raises(ValueError):
            engine.submit([], 4)
        with pytest.raises(ValueError):
            engine.submit([[]], 4)
        with pytest.raises(ValueError):
            engine.submit([[1] * 9], 4)       # prompt > prompt_len
        with pytest.raises(ValueError):
            engine.submit([[1] * 8], 0)       # n < 1
        with pytest.raises(ValueError):
            engine.submit([[1] * 8], 9)       # prompt + n > max_len
    finally:
        engine.stop()


def test_engine_property_any_request_mix_matches_oracle():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.given(
        st.lists(
            st.tuples(
                st.lists(
                    st.lists(st.integers(0, _V - 1), min_size=1,
                             max_size=6),
                    min_size=1, max_size=3,
                ),
                st.integers(1, 8),
                st.one_of(st.none(), st.integers(0, _V - 1)),
            ),
            min_size=1, max_size=6,
        ),
        st.integers(1, 4),
    )
    @hypothesis.settings(
        max_examples=40, deadline=None,
        suppress_health_check=[hypothesis.HealthCheck.too_slow],
    )
    def run(jobs, slots):
        model = ChainModel(slots=slots)
        engine = _engine(
            model, slots=slots, max_len=16, prompt_len=6
        )
        try:
            results = _swarm(engine, jobs)
            for (rows, n, eos), result in zip(jobs, results):
                assert result == [
                    _chain_oracle(r, n, eos) for r in rows
                ]
            stats = engine.stats()
            assert stats["active_slots"] == 0
            assert stats["free_slots"] == slots
            assert stats["queue_depth"] == 0
        finally:
            engine.stop()

    run()


def test_one_engine_one_class():
    """There is one serving engine: ``PagedEngine`` derives from
    nothing and nothing in the package derives from it, and the
    saturation error it raises is its own (re-exported by ``serve``)."""
    import dcos_commons_tpu.serve as serve
    from dcos_commons_tpu.serve import engine as engine_module

    assert PagedEngine.__bases__ == (object,)
    assert [
        cls for cls in PagedEngine.__subclasses__()
        if cls.__module__.startswith("dcos_commons_tpu")
    ] == []  # a test may derive a probe; the package may not
    assert serve.QueueTimeoutError is QueueTimeoutError
    assert QueueTimeoutError.__module__ == engine_module.__name__
    assert [
        name for name in vars(engine_module)
        if name.endswith("Engine") and name != "PagedEngine"
    ] == []
