"""The engine's own timeline (ISSUE 24): the loop thread's phases, the
per-request sums and spans, and the profiler's view of both.

Against a fake two-callable pool on the CPU, like ``test_paged_kv``:
the counters partition the loop thread's life, a request's three
intervals add up to its time in the engine, rows that did not end
normally stay out of the sums and leave no span open, and a
``jax.profiler`` capture shows the program's own host spans under
names the benchmark's outside spans do not use.
"""

import glob
import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import (
    PHASES,
    PagedEngine,
    QueueTimeoutError,
)
from dcos_commons_tpu.serve.migration import (
    InProcessTransport,
    SessionMigratedError,
    migrate_session,
)
from dcos_commons_tpu.trace import NULL_TRACER, TraceRecorder, to_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = 4  # page tokens
SUMS = ("queue_wait_s_sum", "prefill_s_sum", "decode_s_sum")


class FakePool:
    """Two callables that take a little time, like a device would: a
    token is a function of its predecessor and position.  ``gate``
    (an Event) holds every decode until set; ``fail`` makes the next
    decode raise."""

    def __init__(self, step_s=0.002, gate=None):
        self.step_s = step_s
        self.gate = gate
        self.fail = False
        self.pages = {}

    def prefill_chunk(self, padded, slot, table, start, true_len,
                      temp, seed):
        time.sleep(self.step_s)
        return int(padded[0, :true_len].sum() + start) % 97

    def decode(self, tok, pos, temps, seeds, *rest):
        if self.gate is not None:
            assert self.gate.wait(10), "decode never released"
        time.sleep(self.step_s)
        if self.fail:
            raise RuntimeError("device fell over")
        return ((tok * 7 + pos * 3 + 1) % 97).astype(np.int32)

    def read_page(self, page):
        return dict(self.pages.get(page, {}))

    def write_page(self, page, payload):
        self.pages[page] = dict(payload)


def paged(pool, tracer=NULL_TRACER, slots=3, **kw):
    kw.setdefault("queue_timeout_s", 30)
    return PagedEngine(
        pool.prefill_chunk, pool.decode, slots, 64, 48,
        page_tokens=P, pages=48, chunk_tokens=8, prefix_cache=True,
        read_page=pool.read_page, write_page=pool.write_page,
        tracer=tracer, **kw,
    )


def swarm(engine, jobs, parents=None):
    """Submit every (prompt, n) at once, each from its own thread;
    returns each call's seconds around ``submit``."""
    took, errors = [None] * len(jobs), []

    def client(i):
        prompt, n = jobs[i]
        t0 = time.monotonic()
        try:
            engine.submit(
                [prompt], n,
                trace_parent=parents[i] if parents else None,
            )
        except Exception as e:  # noqa: BLE001 — surfaced via assert
            errors.append(e)
        took[i] = time.monotonic() - t0

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert not errors, errors
    return took


JOBS = [
    (list(range(1, 8)), 6), ([3] * 20, 4), ([9, 9], 9), ([4] * 30, 3),
    ([5] * 11, 12), ([7], 2),
]


def by_rid(tracer):
    spans = {}
    for span in tracer.snapshot():
        if span.name.startswith("engine.") and "rid" in span.attrs:
            spans.setdefault(span.attrs["rid"], {})[span.name] = span
    return spans


def all_closed(made):
    """Every span a recorder ever minted was closed."""
    return all(span.end_s is not None for span in made)


@pytest.fixture
def minted(monkeypatch):
    """Every ``Span`` a recorder mints while the test runs."""
    from dcos_commons_tpu.trace import recorder as recorder_module

    made = []

    class Counted(recorder_module.Span):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            made.append(self)

    monkeypatch.setattr(recorder_module, "Span", Counted)
    return made


# -- the phases ----------------------------------------------------------


class Lived(PagedEngine):
    """The engine with its loop thread's life stamped by the thread
    itself (a busy test host starts and joins threads late)."""

    def _loop(self):
        self.born = time.monotonic()
        try:
            super()._loop()
        finally:
            self.died = time.monotonic()


@pytest.mark.parametrize("chunk", [8, 48], ids=["paged", "one-chunk"])
def test_the_phases_partition_the_loop_threads_life(chunk):
    """Chunked prefill under a page budget, and the prompt in one
    chunk with every row resident: the loop's two extremes."""
    pool = FakePool()
    engine = Lived(
        pool.prefill_chunk, pool.decode, 3, 64, 48,
        page_tokens=P, pages=48, chunk_tokens=chunk,
    )
    swarm(engine, JOBS)
    time.sleep(0.15)  # parked: waiting is a phase like any other
    swarm(engine, JOBS[:2])
    engine.stop()
    life = engine.died - engine.born
    loop = engine.stats()["loop"]
    assert set(loop["phase_s"]) == set(PHASES)
    assert sum(loop["phase_s"].values()) == pytest.approx(life, rel=0.01)
    assert loop["decode_calls"] > 0 and loop["prefill_calls"] > 0
    # what no ``with`` block covers is charged too, to ``other``
    assert loop["phase_s"]["other"] > 0
    # the fake's sleeps are inside the two calls, and nowhere else
    calls = loop["phase_s"]["prefill_call"] + loop["phase_s"]["decode_call"]
    slept = pool.step_s * (loop["decode_calls"] + loop["prefill_calls"])
    assert calls >= slept
    assert loop["phase_s"]["wait"] >= 0.15


def test_counters_are_cumulative_and_ride_stats_and_servestats(tmp_path):
    import json

    path = str(tmp_path / "servestats.json")
    engine = paged(FakePool(), stats_path=path, stats_every_s=0.0)
    try:
        swarm(engine, JOBS[:3])
        first = engine.stats()["loop"]
        swarm(engine, JOBS[3:])
        second = engine.stats()["loop"]
    finally:
        engine.stop()
    assert second["requests_timed"] == len(JOBS)
    assert first["requests_timed"] == 3
    for key in ("decode_calls", "prefill_calls", "decode_tokens_sum",
                *SUMS):
        assert second[key] > first[key] > 0, key
    assert second["decode_tokens_sum"] == sum(n - 1 for _p, n in JOBS)
    # 30 prompt tokens at 8 a chunk: the long prompt alone took four
    assert second["prefill_calls"] >= sum(
        -(-len(p) // 8) for p, _n in JOBS
    )
    with open(path) as f:
        mirrored = json.load(f)["loop"]
    assert set(mirrored) == set(second)


# -- a request's three intervals -----------------------------------------


def test_queue_prefill_decode_add_up_to_retire_minus_arrival(minted):
    tracer = TraceRecorder(capacity=512, service="serve")
    engine = paged(FakePool(), tracer=tracer, slots=2)  # rows run short
    try:
        took = swarm(engine, JOBS)
        loop = engine.stats()["loop"]
    finally:
        engine.stop()
    spans = by_rid(tracer)
    assert len(spans) == len(JOBS)
    total = 0.0
    for rid, mine in spans.items():
        queue, prefill, decode = (
            mine[f"engine.{part}"] for part in ("queue", "prefill", "decode")
        )
        # contiguous: admission ends the wait, the first token the prefill
        assert queue.end_s == prefill.start_s
        assert prefill.end_s == decode.start_s
        assert (queue.duration_s + prefill.duration_s + decode.duration_s
                == pytest.approx(decode.end_s - queue.start_s, abs=1e-9))
        assert decode.attrs["end"] == "max_tokens"
        assert decode.attrs["ticks"] == decode.attrs["tokens"] - 1
        total += decode.end_s - queue.start_s
    # the sums are those same intervals...
    assert sum(loop[key] for key in SUMS) == pytest.approx(total, abs=1e-4)
    assert loop["requests_timed"] == len(JOBS)
    # ...and the engine's whole share of what each caller waited
    assert total <= sum(took)
    assert total >= sum(took) - 0.25 * len(JOBS)  # a caller's wake-up
    # with two rows for six requests somebody queued for a row
    assert loop["queue_wait_s_sum"] > 0.002
    assert all_closed(minted)


def test_spans_of_one_request_share_its_trace_and_nest_under_it(minted):
    tracer = TraceRecorder(capacity=512, service="serve")
    engine = paged(FakePool(), tracer=tracer)
    try:
        parents = [
            tracer.span("request", track="req", rows=1) for _ in JOBS
        ]
        swarm(engine, JOBS, parents)
        for parent in parents:
            parent.end()
    finally:
        engine.stop()
    spans = by_rid(tracer)
    requests = {s.span_id: s for s in tracer.snapshot() if s.name == "request"}
    assert len(requests) == len(JOBS) == len(spans)
    seen = set()
    for mine in spans.values():
        assert set(mine) == {"engine.queue", "engine.prefill", "engine.decode"}
        (trace_id,) = {s.trace_id for s in mine.values()}
        (parent_id,) = {s.parent_id for s in mine.values()}
        request = requests[parent_id]
        assert request.trace_id == trace_id
        assert all(s.track == "req" for s in mine.values())
        assert request.start_s <= mine["engine.queue"].start_s
        assert mine["engine.decode"].end_s <= request.end_s
        seen.add(trace_id)
    assert len(seen) == len(JOBS)  # one trace a request
    long_prompt = next(
        m["engine.prefill"] for m in spans.values()
        if m["engine.prefill"].attrs["prompt_tokens"] == 30
    )
    assert long_prompt.attrs["chunks"] == 4
    assert long_prompt.attrs["cached_pages"] == 0
    # a tick a span on its own lane, saying what rode it
    ticks = [s for s in tracer.snapshot() if s.name == "engine.tick"]
    assert ticks and all(s.track == "loop" for s in ticks)
    assert sum(s.attrs["chunks"] for s in ticks) >= 12
    assert max(s.attrs["rows"] for s in ticks) == 3
    text = to_text(tracer, service="serve")
    assert "engine.prefill" in text and "engine.tick" in text
    assert all_closed(minted)


def test_a_prefix_cache_hit_shows_in_the_prefill_span():
    tracer = TraceRecorder(capacity=64)
    engine = paged(FakePool(), tracer=tracer)
    try:
        engine.submit([[6] * 13], 2)
        engine.submit([[6] * 13], 2)
    finally:
        engine.stop()
    first, second = (
        mine["engine.prefill"] for _rid, mine in sorted(by_rid(tracer).items())
    )
    assert first.attrs["cached_pages"] == 0
    assert second.attrs["cached_pages"] == 3  # 12 of the 13 tokens
    assert second.attrs["chunks"] == 1


# -- rows that did not end normally --------------------------------------


def test_an_abandoned_row_is_left_out_and_leaks_no_span(minted):
    tracer = TraceRecorder(capacity=128)
    gate = threading.Event()
    pool = FakePool(gate=gate)
    engine = paged(pool, tracer=tracer, queue_timeout_s=0.2)
    try:
        with tracer.span("request", track="req") as request:
            with pytest.raises(QueueTimeoutError):
                engine.submit([[1, 2, 3]], 8, trace_parent=request)
        gate.set()  # the held tick comes back to a dead request
        deadline = time.monotonic() + 5
        while engine.stats()["active_slots"] and time.monotonic() < deadline:
            time.sleep(0.01)
        stats = engine.stats()
        assert stats["active_slots"] == 0
        assert stats["loop"]["requests_timed"] == 0
        assert all(stats["loop"][key] == 0 for key in SUMS)
        # a sound request afterwards is timed as ever
        engine.submit([[4, 5]], 3)
        assert engine.stats()["loop"]["requests_timed"] == 1
    finally:
        gate.set()
        engine.stop()
    ends = sorted(
        s.attrs["end"] for s in tracer.snapshot() if s.name == "engine.decode"
    )
    assert ends == ["abandoned", "max_tokens"]
    assert all_closed(minted)


def test_failed_rows_are_left_out_and_leak_no_span(minted):
    tracer = TraceRecorder(capacity=128)
    pool = FakePool()
    engine = paged(pool, tracer=tracer)
    try:
        pool.fail = True
        with tracer.span("request", track="req") as request:
            with pytest.raises(RuntimeError, match="fell over"):
                engine.submit([[1, 2, 3]], 8, trace_parent=request)
        assert engine.stats()["loop"]["requests_timed"] == 0
        pool.fail = False
        engine.submit([[1, 2, 3]], 4)  # the loop lives on
        loop = engine.stats()["loop"]
        assert loop["requests_timed"] == 1
        assert loop["decode_tokens_sum"] == 3
    finally:
        engine.stop()
    assert all_closed(minted)


def test_migrated_rows_are_left_out_on_both_pods(minted):
    src_tracer, dst_tracer = TraceRecorder(128), TraceRecorder(128)
    src = paged(FakePool(step_s=0.004), tracer=src_tracer)
    dst = paged(FakePool(step_s=0.004), tracer=dst_tracer)
    try:
        result = {}

        def client():
            try:
                result["r"] = src.submit([list(range(1, 14))], 30)
            except BaseException as e:  # noqa: BLE001 — the assertion target
                result["r"] = e

        thread = threading.Thread(target=client)
        thread.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            sessions = src.sessions()
            if sessions and sessions[0]["state"] == "decode" \
                    and src.stats()["tokens_out"] >= 5:
                break
            time.sleep(0.005)
        record = migrate_session(
            src, dst, sessions[0]["rid"], dest_name="dst",
            transport=InProcessTransport(),
        )
        assert record.ok
        thread.join(timeout=15)
        assert isinstance(result["r"], SessionMigratedError)
        assert len(dst.collect(result["r"].dest_rid, timeout=20)) == 30
        for engine in (src, dst):
            loop = engine.stats()["loop"]
            assert loop["requests_timed"] == 0
            assert all(loop[key] == 0 for key in SUMS)
        # each pod still says what it did with the session
        (left,) = [s for s in src_tracer.snapshot()
                   if s.name == "engine.decode"]
        assert left.attrs["end"] == "migrated"
        (arrived,) = [s for s in dst_tracer.snapshot()
                      if s.name == "engine.decode"]
        assert arrived.attrs["end"] == "max_tokens"
        # a request of the destination's own is timed beside it
        dst.submit([[2, 2]], 3)
        assert dst.stats()["loop"]["requests_timed"] == 1
    finally:
        src.stop()
        dst.stop()
    assert all_closed(minted)


# -- the ring off ---------------------------------------------------------


def worker_module():
    spec = importlib.util.spec_from_file_location(
        "serve_worker_under_test",
        os.path.join(REPO, "frameworks", "jax", "serve_worker.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_with_the_ring_off_submit_allocates_no_span(minted):
    tracer = TraceRecorder(capacity=0)
    del minted[:]  # the recorder's own shared no-op span
    engine = paged(FakePool(), tracer=tracer)
    try:
        with tracer.span("request", track="req") as request:
            engine.submit([[1, 2, 3, 4, 5]], 4, trace_parent=request)
        loop = engine.stats()["loop"]
    finally:
        engine.stop()
    assert minted == [] and tracer.snapshot() == []
    assert loop["requests_timed"] == 1  # the counters are always on
    body, kind = worker_module().trace_reply(tracer, "fmt=chrome")
    assert b"recorder off" in body and b"SERVE_TRACE_CAPACITY" in body
    assert kind == "text/plain"


def test_trace_reply_renders_the_ring_as_text_and_chrome():
    import json

    tracer = TraceRecorder(capacity=64)
    engine = paged(FakePool(), tracer=tracer)
    try:
        with tracer.span("request", track="req") as request:
            engine.submit([[1, 2, 3]], 3, trace_parent=request)
    finally:
        engine.stop()
    reply = worker_module().trace_reply
    text, kind = reply(tracer)
    assert kind == "text/plain"
    for name in ("request", "engine.queue", "engine.prefill",
                 "engine.decode", "engine.tick"):
        assert name.encode() in text
    chrome, kind = reply(tracer, "fmt=chrome")
    assert kind == "application/json"
    events = json.loads(chrome)["traceEvents"]
    assert {e["tid"] for e in events} == {"req", "loop"}
    assert len({e["args"]["trace_id"] for e in events
                if e["tid"] == "req"}) == 1


# -- the profiler's view --------------------------------------------------


def test_a_profiler_capture_shows_the_programs_own_host_spans(tmp_path):
    """The real device half at toy size, on the CPU: the engine's
    phases and the pool's calls are host spans of the capture, and
    none of them is named like the benchmark's four outside spans
    (``perfbench/harness/trace_reduce.py`` matches those names on any
    host line)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from dcos_commons_tpu.models import TransformerConfig, init_params
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config = TransformerConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=48, max_seq=64, dtype=jnp.float32, remat=False,
    )
    pool = PagedPoolModel(
        config, init_params(config, jax.random.key(0)), slots=2,
        max_len=32, page_tokens=4, pages=16, chunk_tokens=8,
    )
    pool.warm()
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, 2, 32, 24,
        page_tokens=4, pages=16, chunk_tokens=8,
        annotate=jax.profiler.TraceAnnotation,
    )
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            engine.submit([list(range(1, 12))], 4)
            time.sleep(0.05)  # the loop parks inside the session
            engine.submit([list(range(1, 12))], 4)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.stop()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    program = {n for n in names if n.startswith(("engine.", "pool."))}
    # every phase but the stats write (this engine has no stats
    # file) and ``other``, which is what lies between the spans
    assert {"engine." + phase for phase in PHASES
            if phase not in ("stats", "other")} <= program
    assert "engine.other" not in program
    assert {"pool.prefill_chunk", "pool.prefill_chunk.fetch",
            "pool.decode", "pool.decode.fetch"} <= program
    assert not names & {"decode", "prefill_chunk", "decode:fetch",
                        "prefill_chunk:fetch"}
