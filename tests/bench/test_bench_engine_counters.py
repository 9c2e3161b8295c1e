"""The readers of the engine's own counters (``/stats`` key ``loop``),
on hand-made runs; ``tools/loop_gaps.py`` on hand-made plain lists;
and the toy cell's rehearsal, which has to print all seven metrics
from the program's real counters."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toyroot  # noqa: E402
from perfbench.harness import counters  # noqa: E402
from perfbench.harness.manifest import Manifest  # noqa: E402
from perfbench.tools import loop_gaps  # noqa: E402
from test_bench_harness_run import run_cell  # noqa: E402

PHASES = ("wait", "prefill_call", "decode_prep", "decode_call",
          "decode_apply", "stats", "other")
SEVEN = {
    "engine_tick_period_ms.chat": 50.0,           # 10 s / 200 calls
    "engine_tpot_mean_ms.chat": 51.0,             # 153 s / 3000 tokens
    "engine_prefill_chunks_per_tick.chat": 0.95,  # 190 / 200
    "engine_queue_wait_mean_s.chat": 0.03,        # 0.6 s / 20 requests
    "engine_prefill_mean_s.chat": 0.35,           # 7 s / 20
    "engine_host_ms_per_tick.chat": 1.4,          # 4 phases x 70 ms / 200
    "engine_loop_wait_share.chat": 0.005,         # 0.05 s / 10 s
}


def loop_at(k: float) -> dict:
    """The counters after ``k`` units of steady serving."""
    seconds = dict.fromkeys(PHASES, 0.007 * k + 1.0)
    seconds.update(wait=0.005 * k + 9.0, prefill_call=0.4 * k,
                   decode_call=0.5 * k)
    return {
        "decode_calls": 20 * k + 7,
        "prefill_calls": 19 * k + 3, "phase_s": seconds,
        "queue_wait_s_sum": 0.06 * k + 2.0, "prefill_s_sum": 0.7 * k,
        "decode_s_sum": 15.3 * k + 40.0, "decode_tokens_sum": 300 * k + 11,
        "requests_timed": 2 * k + 5,
    }


def run_of(samples) -> dict:
    return {"window": [100.0, 151.0], "trace_window": None,
            "stats_samples": samples}


def sample(t: float, loop=None) -> dict:
    out = {"_t": t, "t": 5000.0 + t, "queue_depth": 0, "active_slots": 21,
           "model": {"d_model": 4096}}
    if loop is not None:
        out["loop"] = loop
    return out


@pytest.fixture(scope="module")
def bench():
    return Manifest(REPO)


@pytest.fixture(scope="module")
def steady():
    # before the window, then one a second inside it, then after it
    return run_of(
        [sample(99.0, loop_at(0))]
        + [sample(110.0 + k, loop_at(k)) for k in range(11)]
        + [sample(152.0, loop_at(40))]
    )


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_each_reader_windows_the_counters_by_difference(bench, steady, name):
    value = bench.reader("per_layer", name)(steady)
    assert value == pytest.approx(SEVEN[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_a_program_without_the_counters_reads_none(bench, name):
    read = bench.reader("per_layer", name)
    parent = run_of([sample(110.0 + k) for k in range(11)])
    assert read(parent) is None
    assert read(run_of([])) is None
    # one sample is no difference; nor is a window in which nothing ran
    assert read(run_of([sample(110.0, loop_at(3))])) is None
    stalled = run_of([sample(110.0 + k, loop_at(3)) for k in range(5)])
    value = read(stalled)
    assert value is None or value == 0.0  # only the wait share has a number


def test_the_seven_are_entered_for_the_chat_cell(bench):
    entered = {m["name"]: m for m in bench.data["per_layer"]}
    # appended together, in this order; a later PR appends after them
    first = list(entered).index(next(iter(SEVEN)))
    assert list(entered)[first:first + 7] == list(SEVEN)
    for name in SEVEN:
        metric = entered[name]
        assert metric["source"] == "program_counter"
        assert metric["layer"] == "engine host loop"
        assert metric["moves"] == "norm_lat_p50_s"
        # its own cell first; a later cell that reports the metric it
        # moves appends itself
        assert metric["workloads"][0] == "mixtral8x7b.chat"
        assert metric["better"] == "lower"


def test_delta_reads_the_traced_span_alone_when_asked():
    run = run_of([sample(110.0 + k, loop_at(k)) for k in range(11)])
    run["trace_window"] = [112.5, 116.5]
    assert counters.delta(run, "loop", "decode_calls") == 200
    assert counters.delta(run, "loop", "decode_calls", traced_only=True) == 60
    assert counters.elapsed_s(run, "loop", traced_only=True) == 3.0
    phases = counters.delta(run, "loop", "phase_s", traced_only=True)
    assert phases["decode_call"] == pytest.approx(1.5)
    assert counters.delta(run, "loop", "no_such_counter") is None
    assert counters.delta(run, "model", "d_model", "deeper") is None
    assert counters.ratio(1.0, 0) is None and counters.ratio(None, 2) is None
    # the program's own stamp of the snapshot, where it has one
    for s in run["stats_samples"]:
        del s["t"]
    assert counters.elapsed_s(run, "loop") == 10.0


# -- loop_gaps on plain lists ---------------------------------------------

MS = 1e6  # ns


def span(name, start_ms, end_ms):
    return [name, start_ms * MS, (end_ms - start_ms) * MS]


def one_tick(at):
    """A tick of 50 ms from ``at``: 2 ms of host work under no span
    (the program's ``other``), a prefill call of 20 (the device busy
    for 16 of them), 1 ms of host work, a decode call of 26 (busy for
    23), 1 ms to apply."""
    host = [
        span("engine.prefill_call", at + 2, at + 22),
        span("pool.prefill_chunk", at + 2.5, at + 21.5),
        span("pool.prefill_chunk.fetch", at + 4, at + 21.5),
        span("engine.decode_prep", at + 22, at + 23),
        span("engine.decode_call", at + 23, at + 49),
        span("pool.decode", at + 23, at + 49),
        span("pool.decode.fetch", at + 25, at + 49),
        span("engine.decode_apply", at + 49, at + 50),
    ]
    ops = [[(at + 5) * MS, 16 * MS], [(at + 25.5) * MS, 23 * MS]]
    return host, ops


def test_self_intervals_give_each_instant_to_the_innermost_span():
    host, _ops = one_tick(0)
    own = loop_gaps.self_intervals(host)
    total = {name: sum(e - s for s, e in iv) / MS for name, iv in own.items()}
    assert total["engine.prefill_call"] == pytest.approx(1.0)  # 0.5 + 0.5
    assert total["pool.prefill_chunk"] == pytest.approx(1.5)   # its dispatch
    assert total["pool.prefill_chunk.fetch"] == pytest.approx(17.5)
    assert "engine.decode_call" not in total  # pool.decode covers it whole
    assert total["pool.decode"] == pytest.approx(2.0)
    assert sum(total.values()) == pytest.approx(48.0)


def test_idle_time_is_put_down_to_the_span_over_it():
    host, ops = [], []
    for k in range(4):
        h, o = one_tick(50 * k)
        host += h
        ops += o
    out = loop_gaps.attribute(
        {"devices": {"/device:TPU:0": ops}, "host": {"loop": host}}
    )
    # the window runs from the first operation to the last one's end
    assert out["window_s"] == pytest.approx((198.5 - 5) / 1e3)
    idle = dict(out["idle_by_span"])
    assert out["idle_s"] == pytest.approx(sum(idle.values()))
    assert out["idle_s"] == pytest.approx((193.5 - 4 * 39) / 1e3)
    # before the chunk runs: its fetch has waited 1 ms; after: 0.5 ms
    assert idle["pool.prefill_chunk.fetch"] == pytest.approx(
        (3 * 1.0 + 4 * 0.5) / 1e3)
    assert idle["pool.decode.fetch"] == pytest.approx(
        (4 * 0.5 + 3 * 0.5) / 1e3)
    assert idle["engine.decode_prep"] == pytest.approx(4 / 1e3)
    assert idle["engine.decode_apply"] == pytest.approx(3 / 1e3)
    # a tick's first 2 ms, once a device operation has come before
    assert idle[loop_gaps.OUTSIDE] == pytest.approx(3 * 2 / 1e3)
    assert out["idle_by_span"] == sorted(
        out["idle_by_span"], key=lambda kv: -kv[1])


def test_idle_outside_every_span_and_an_empty_trace():
    out = loop_gaps.attribute({
        "devices": {"/device:TPU:0": [[0.0, 1 * MS], [9 * MS, 1 * MS]]},
        "host": {"loop": [span("engine.stats", 2, 4)]},
    })
    idle = dict(out["idle_by_span"])
    assert idle["engine.stats"] == pytest.approx(2e-3)
    assert idle[loop_gaps.OUTSIDE] == pytest.approx(6e-3)
    empty = loop_gaps.attribute({"devices": {}, "host": {}})
    assert empty["idle_s"] == 0.0 and empty["idle_by_span"] == []


# -- the rehearsal ----------------------------------------------------------


def test_the_toy_cells_rehearsal_prints_all_seven(tmp_path):
    """Scheduler -> agent -> the real worker on the CPU: the seven are
    read from the program's own ``/stats``, not from a hand-made run."""
    proc = run_cell(toyroot.build(str(tmp_path)), "--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = json.loads(proc.stdout.splitlines()[-1])["metrics"]
    assert set(SEVEN) <= set(metrics)
    for name in SEVEN:
        assert metrics[name]["value"] >= 0.0, name
    period = metrics["engine_tick_period_ms.chat"]["value"]
    assert 0.0 < period < 1000.0
    assert 0.0 <= metrics["engine_loop_wait_share.chat"]["value"] <= 1.0
    assert metrics["engine_tpot_mean_ms.chat"]["value"] > 0.0
