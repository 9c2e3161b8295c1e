"""The reader of the loop's chunk-rider counter (``/stats`` ->
``loop.prefill_rider_calls``, ISSUE 40), on hand-made runs, and its
entry in ``BENCHMARK.json`` (ISSUE 43): ``evabyte.docqa`` alone among
the cells of today, since the mixtures' chunks carry no rider."""

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

NAME = "engine_chunk_rider_share.chat"


def run_of(loops) -> dict:
    return {
        "window": [100.0, 151.0], "trace_window": None,
        "stats_samples": [
            {"_t": 110.0 + k, "t": 5110.0 + k, "loop": loop}
            for k, loop in enumerate(loops)
        ],
    }


@pytest.fixture(scope="module")
def read():
    return Manifest(REPO).reader("per_layer", NAME)


def test_the_share_is_the_windows_difference_of_both_counters(read):
    run = run_of([
        {"prefill_calls": 30 + 20 * k, "prefill_rider_calls": 4 + 19 * k}
        for k in range(6)
    ])
    assert read(run) == pytest.approx(19 / 20)
    # before the window: not counted
    run["stats_samples"].insert(
        0, {"_t": 99.0, "t": 5099.0,
            "loop": {"prefill_calls": 0, "prefill_rider_calls": 0}})
    assert read(run) == pytest.approx(19 / 20)


@pytest.mark.parametrize("loops", [
    [],                                                       # no sample
    [{"prefill_calls": 7}, {"prefill_calls": 90}],            # the parent
    [{"prefill_calls": 7, "prefill_rider_calls": 5}],         # one sample
    [{"prefill_calls": 7, "prefill_rider_calls": 5}] * 3,     # no chunk ran
], ids=["no-samples", "no-such-counter", "one-sample", "stalled"])
def test_a_program_without_the_counter_reads_none(read, loops):
    assert read(run_of(loops)) is None


def test_a_pool_whose_chunks_carry_nothing_reads_zero(read):
    run = run_of([
        {"prefill_calls": 10 * k, "prefill_rider_calls": 0} for k in range(4)
    ])
    assert read(run) == 0.0


def test_the_engines_own_counters_feed_the_reader(read):
    # two /stats snapshots of a real loop over the chain model's device
    # half: one before anything ran, one after a long prompt prefilled
    # in three chunks while a short one's row decoded
    from dcos_commons_tpu.serve.engine import PagedEngine
    from dcos_commons_tpu.testing.chain_model import (
        ChainModel,
        ChunkRiders,
        settled_stats,
    )

    half = ChunkRiders(ChainModel(slots=2))
    engine = PagedEngine(
        half.prefill_chunk, half.decode, 2, 32, 24, page_tokens=4,
        pages=16, chunk_tokens=5, prefix_cache=False, **half.engine_kwargs(),
    )
    try:
        before = engine.stats()["loop"]
        engine.submit([[7, 7], list(range(1, 14))], 9)
        after = settled_stats(engine)["loop"]
    finally:
        engine.stop()
    assert before["prefill_rider_calls"] == 0
    assert read(run_of([before, after])) == pytest.approx(3 / 4)


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_the_entry(bench_roots, where):
    bench = Manifest(bench_roots[where])
    metric = dict(toyroot.named(bench.data["per_layer"], NAME))
    assert set(metric.pop("workloads")) & set(toyroot.CELLS) == {
        "evabyte.docqa"}
    assert metric == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "engine host loop",
        "moves": "norm_lat_p50_s",
    }
    assert NAME in {
        m["name"] for m in bench.metrics("per_layer", "evabyte.docqa")
    }
