"""How a traced run ends (ISSUE 34): the wait for ``/trace/stop`` is
reckoned from the device programs the traced span held, a stop that
takes long is waited for, one that hangs fails the run with a message
that says what was traced and how long it waited, and the worker
entry's ``/trace/stop`` writes the profiler session's bytes where the
reduction reads them."""

import glob
import json
import math
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_bench_harness_run import REPO  # noqa: E402
from test_bench_worker import worker_entry  # noqa: E402

sys.path.insert(0, REPO)

from perfbench import run  # noqa: E402
from perfbench.harness.deploy import http_json  # noqa: E402

# the wait run.py gave every control call before ISSUE 34, and the
# factor the tests scale a second by
OLD_LIMIT_S, SCALE = 120.0, 0.004


class FakeDeployment:
    """A sandbox that names a control server of the test's own."""

    def __init__(self, tmp_path, stop):
        self.calls = []
        calls = self.calls

        class Control(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                calls.append(self.path)
                stop()
                data = json.dumps({"ok": True}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Control)
        self.server.daemon_threads = True
        threading.Thread(target=self.server.serve_forever, daemon=True).start()
        self.dir = str(tmp_path)
        with open(os.path.join(self.dir, "perfbench_control.json"), "w") as f:
            json.dump({"port": self.server.server_address[1]}, f)

    def sandbox(self, task):
        assert task == run.TASK
        return self.dir

    def close(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.fixture
def scaled(monkeypatch):
    """A second of the limit's reckoning lasts SCALE seconds."""
    monkeypatch.setattr(run, "STOP_FLOOR_S", run.STOP_FLOOR_S * SCALE)
    monkeypatch.setattr(run, "STOP_S_PER_PROGRAM",
                        run.STOP_S_PER_PROGRAM * SCALE)


def test_a_stop_that_outlasts_the_old_limit_is_waited_for(tmp_path, scaled):
    """1,500 programs in the traced seconds: the stop takes a quarter
    longer than the old fixed wait, and the run goes on and says how
    long."""
    took = 1.25 * OLD_LIMIT_S * SCALE
    assert run.stop_limit_s(1500, 4.0) > took + 0.1
    deployment = FakeDeployment(tmp_path, lambda: time.sleep(took))
    try:
        stopped = run.stop_trace(deployment, 1500, 4.0)
    finally:
        deployment.close()
    assert deployment.calls == ["/trace/stop"]
    assert took <= stopped["stop_s"] < run.stop_limit_s(1500, 4.0)
    assert (stopped["programs"], stopped["traced_s"]) == (1500, 4.0)
    assert stopped["profile_bytes"] is None  # this stand-in says none


@pytest.mark.parametrize("programs, named", [
    (650, "about 650 device programs traced in 4.1 s"),
    (None, "uncounted device programs traced in 4.1 s"),
], ids=["counted", "uncounted"])
def test_a_stop_that_never_returns_fails_the_run_inside_its_limit(
        tmp_path, scaled, monkeypatch, programs, named):
    monkeypatch.setattr(run, "UNCOUNTED_PROGRAMS_PER_S", 100.0)
    hung = threading.Event()
    deployment = FakeDeployment(tmp_path, lambda: hung.wait(30))
    limit = run.stop_limit_s(programs, 4.1)
    began = time.monotonic()
    try:
        with pytest.raises(run.RunFailure) as failed:
            run.stop_trace(deployment, programs, 4.1)
        waited = time.monotonic() - began
    finally:
        hung.set()
        deployment.close()
    assert limit <= waited < limit + 2.0
    message = str(failed.value)
    assert "/trace/stop gave no answer in" in message and named in message
    assert f"the limit for them is {limit:.1f} s" in message
    assert "timed out" in message


def test_a_worker_that_is_gone_fails_the_run_at_once(tmp_path, scaled):
    deployment = FakeDeployment(tmp_path, lambda: None)
    deployment.close()
    with pytest.raises(run.RunFailure) as failed:
        run.stop_trace(deployment, 650, 4.0)
    assert "about 650 device programs" in str(failed.value)


def samples_of(rows):
    return [
        {"_t": t, "loop": {"decode_calls": d, "prefill_calls": p}}
        for t, d, p in rows
    ]


# `/stats` as the poller recorded it around the traced span of a run of
# `lfm2-24b.chat` on the chip (PR 33, call u1, traced seed 2147507091:
# the span began at 90.372 and its 4.11 s of profile held 572 decode
# steps and 77 chunks): the harness's clock, `loop.decode_calls`,
# `loop.prefill_calls`
RECORDED = samples_of([
    (88.641, 3954, 643), (89.643, 4090, 658), (90.647, 4202, 675),
    (91.709, 4319, 688), (92.712, 4448, 709), (93.714, 4584, 738),
    (94.717, 4783, 749), (95.721, 4816, 775), (96.724, 4914, 794),
])
T0, T1 = 90.372, 94.572


def test_the_traced_programs_are_the_pollers_rate_times_the_span():
    # what the poller has when the span ends: the sample of 93.714 is
    # its newest; from the last before the span, 89.643, the count grew
    # by 574 in 4.071 s, so 4.2 s held about 593 (the trace: 649; the
    # last second's steps were short ones)
    had = [s for s in RECORDED if s["_t"] <= T1]
    assert had[-1]["_t"] == 93.714
    assert run.traced_programs(had, T0, T1) == 593
    # samples from before the span's last do not count
    early = samples_of([(60.0, 0, 0), (75.0, 10, 0)])
    assert run.traced_programs(early + had, T0, T1) == 593
    # no sample before the span: from the first there is
    assert run.traced_programs(had[2:], T0, T1) == math.ceil(
        (5322 - 4877) / (93.714 - 90.647) * (T1 - T0))
    # the wait for them
    assert run.stop_limit_s(593, T1 - T0) == pytest.approx(
        run.STOP_FLOOR_S + 593 * run.STOP_S_PER_PROGRAM)


@pytest.mark.parametrize("samples", [
    [], RECORDED[:1],
    [{"_t": 100.0, "loop": {}}, {"_t": 104.0}],           # no counters
    samples_of([(100.0, 5, 5), (100.0, 9, 9)]),           # no time between
], ids=["none", "one", "no-counters", "one-instant"])
def test_a_span_that_cannot_be_counted_reads_none(samples):
    assert run.traced_programs(samples, 101.0, 105.0) is None


def test_the_limit_follows_the_programs_and_no_tick_outruns_it():
    limit = run.stop_limit_s
    assert limit(0, 4.0) == run.STOP_FLOOR_S
    assert limit(1500, 4.0) - limit(650, 4.0) == pytest.approx(
        850 * run.STOP_S_PER_PROGRAM)
    # an uncounted span is given the most programs it could have held
    assert limit(None, 4.0) == limit(
        int(run.UNCOUNTED_PROGRAMS_PER_S * 4.0), 4.0)
    assert limit(None, 4.0) > limit(1500, 4.0)


@pytest.fixture
def control_server(tmp_path, monkeypatch):
    """The worker entry's control server, started in a directory of the
    test's own; yields (entry, url)."""
    entry = worker_entry()
    monkeypatch.chdir(tmp_path)
    server = entry._control_server()
    try:
        with open(tmp_path / entry.CONTROL_FILE) as f:
            port = json.load(f)["port"]
        yield entry, f"http://127.0.0.1:{port}"
    finally:
        server.shutdown()
        server.server_close()


def test_the_entrys_trace_is_the_profilers_own_session_written_raw(
        control_server, tmp_path, monkeypatch):
    """Step 1 of ISSUE 34 kept no option (none shortened a stop, PERF.md
    section 6): the session is the profiler's default one, and what its
    stop returns is written as it comes, with no export beside it."""
    from jax._src.lib import _profiler

    entry, url = control_server
    sessions = []

    class Session:
        def __init__(self, *options):
            sessions.append(options)

        def stop(self):
            return b"an XSpace"

    monkeypatch.setattr(_profiler, "ProfilerSession", Session)
    there = str(tmp_path / "there")
    assert http_json(url + "/trace/start", {"dir": there})[1]["ok"]
    reply = http_json(url + "/trace/stop", {})[1]
    assert reply["ok"] and reply["profile_bytes"] == 9
    assert sessions == [()]
    # where the reduction looks, and nothing else there
    written = glob.glob(os.path.join(
        there, "plugins", "profile", "*", "*.xplane.pb"))
    assert written == [os.path.join(there, entry.PROFILE_FILE)]
    with open(written[0], "rb") as f:
        assert f.read() == b"an XSpace"
    assert os.listdir(os.path.dirname(written[0])) == ["worker.xplane.pb"]


def test_the_entrys_profile_holds_the_spans_the_reduction_reads(
        control_server, tmp_path):
    """On the CPU, through the real profiler: what ``/trace/stop``
    writes is loaded by ``trace_reduce.load_xplane`` and holds every
    host span it looks for."""
    import jax

    from perfbench.harness import trace_reduce

    _entry, url = control_server
    there = str(tmp_path / "trace")
    http_json(url + "/trace/start", {"dir": there})
    try:
        for outer in trace_reduce.OUTER:
            with jax.profiler.TraceAnnotation(outer):
                with jax.profiler.TraceAnnotation(outer + ":fetch"):
                    time.sleep(0.001)
    finally:
        reply = http_json(url + "/trace/stop", {})[1]
    assert reply["profile_bytes"] > 0
    host = trace_reduce.load_xplane(there)["host"]
    assert sorted(name for name, _s, _d in host) == sorted(
        trace_reduce.OUTER + trace_reduce.INNER)
