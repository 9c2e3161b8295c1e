"""The reader of the loop's one-call-ahead counter (``/stats`` ->
``loop.decode_ahead_calls``, ISSUE 31), on hand-made runs, and its
entry in ``BENCHMARK.json``."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toyroot  # noqa: E402
from toyroot import bench_roots  # noqa: E402,F401
from perfbench.harness.manifest import Manifest  # noqa: E402

NAME = "engine_decode_ahead_share.chat"


def run_of(loops) -> dict:
    return {
        "window": [100.0, 151.0], "trace_window": None,
        "stats_samples": [
            {"_t": 110.0 + k, "t": 5110.0 + k, "loop": loop}
            for k, loop in enumerate(loops)
        ],
    }


@pytest.fixture(scope="module")
def bench():
    return Manifest(REPO)


def test_the_share_is_the_windows_difference_of_both_counters(bench):
    read = bench.reader("per_layer", NAME)
    run = run_of([
        {"decode_calls": 100 + 50 * k, "decode_ahead_calls": 10 + 47 * k}
        for k in range(6)
    ])
    assert read(run) == pytest.approx(47 / 50)
    # before and after the window: not counted
    run["stats_samples"].insert(
        0, {"_t": 99.0, "t": 5099.0,
            "loop": {"decode_calls": 0, "decode_ahead_calls": 0}})
    assert read(run) == pytest.approx(47 / 50)


@pytest.mark.parametrize("loops", [
    [],                                                    # no sample
    [{"decode_calls": 7}, {"decode_calls": 90}],           # the parent
    [{"decode_calls": 7, "decode_ahead_calls": 5}],        # one sample
    [{"decode_calls": 7, "decode_ahead_calls": 5}] * 3,    # nothing ran
], ids=["no-samples", "no-such-counter", "one-sample", "stalled"])
def test_a_program_without_the_counter_reads_none(bench, loops):
    assert bench.reader("per_layer", NAME)(run_of(loops)) is None


def test_a_synchronous_loop_reads_zero(bench):
    run = run_of([
        {"decode_calls": 10 * k, "decode_ahead_calls": 0} for k in range(4)
    ])
    assert bench.reader("per_layer", NAME)(run) == 0.0


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_the_entry(bench_roots, where):
    metric = dict(toyroot.named(
        Manifest(bench_roots[where]).data["per_layer"], NAME))
    # the three cells of today report it; a later cell appends itself
    assert set(metric.pop("workloads")) >= set(toyroot.CELLS)
    assert metric == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "engine host loop",
        "moves": "norm_lat_p50_s",
    }
