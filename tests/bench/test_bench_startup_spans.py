"""The eight readers of the worker's own start-up account (``/stats``
-> ``startup``, ISSUE 41), on a recorded ``final_stats``, and one
rehearsal run of the harness on the CPU in which the real scheduler,
agent and worker produce what they read, a first start (which compiles)
and a second on the same compile cache (which loads the pool's two
stored programs).  The eight are entered in ``BENCHMARK.json`` for
every cell (ISSUE 43; ``ENTRIES`` is what was appended), so the toy
root's cell reports them as every cell does.  The rehearsal keeps a
compile cache of its own under the test's temporary directory: what
the checkout's ``.jax_cache/programs/`` holds from an earlier run must
not decide whether the first start compiles."""

import json
import os
import subprocess
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

# what a worker of this repo answered on the CPU at a toy size (the
# builder's drive of svc_serve.yml through the scheduler, PR 41)
RECORDED = {
    "warm_s": 1.27,
    "startup": {
        "trace_id": "9b2d237600000003", "span_id": "9b2d237600000008",
        "scheduler_started": 1790870141.73332, "launched": 1790870142.072071,
        "phase_s": {
            "launch": 0.00126, "imports": 2.378905, "backend_up": 0.01931,
            "weights": 1.107649, "build": 0.144415, "warm": 1.273736,
            "ready": 0.000328,
        },
        "phase_end": {
            "launch": 1790870142.073331, "imports": 1790870144.452236,
            "backend_up": 1790870144.471545, "weights": 1790870145.579194,
            "build": 1790870145.723609, "warm": 1790870146.997345,
            "ready": 1790870146.997673,
        },
        "start_to_ready_s": 4.925603,
        "warm": {
            "_prefill": {"trace_s": 0.069531, "lower_s": 0.104993,
                         "compile_s": 0.612589, "cache_read_s": 0.0},
            "_decode": {"trace_s": 0.067023, "lower_s": 0.102278,
                        "compile_s": 0.2836, "cache_read_s": 0.0},
            "other": {"trace_s": 0.001, "lower_s": 0.002,
                      "compile_s": 0.004, "cache_read_s": 0.003},
        },
        "compiles_after_ready": 0, "compile_after_ready_s_sum": 0.0,
    },
}

# metric -> (its layer, what it reads of RECORDED)
METRICS = {
    "sched_start_to_launch_s": (
        "plan/offer cycle + agent launch", 1790870142.072071 - 1790870141.73332),
    "agent_launch_to_process_s": ("plan/offer cycle + agent launch", 0.00126),
    "worker_imports_s": ("worker start-up", 2.378905),
    "worker_backend_up_s": ("worker start-up", 0.01931),
    "worker_weights_s": ("worker start-up", 1.107649),
    "worker_build_s": ("worker start-up", 0.144415),
    "worker_warm_trace_lower_s": (
        "worker start-up",
        0.069531 + 0.104993 + 0.067023 + 0.102278 + 0.001 + 0.002),
    "worker_warm_compile_s": ("worker start-up", 0.612589 + 0.2836 + 0.004),
}


@pytest.fixture(scope="module")
def bench():
    return Manifest(REPO)


@pytest.mark.parametrize("name", METRICS)
def test_the_reader_on_a_recorded_final_stats(bench, name):
    read = bench.reader("per_layer", name)
    assert read({"final_stats": RECORDED}) == pytest.approx(METRICS[name][1])


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("final_stats", [
    {"warm_s": 3.5},                        # the parent: no such key
    {"warm_s": 3.5, "startup": None},
    {"warm_s": 3.5, "startup": {}},
], ids=["no-key", "null", "empty"])
def test_a_program_without_the_account_reads_none(bench, name, final_stats):
    assert bench.reader("per_layer", name)({"final_stats": final_stats}) is None


def test_a_launch_without_its_context_leaves_two_out_and_reads_six(bench):
    bare = json.loads(json.dumps(RECORDED))
    bare["startup"]["phase_s"]["launch"] = None
    bare["startup"]["launched"] = bare["startup"]["scheduler_started"] = None
    values = {
        name: bench.reader("per_layer", name)({"final_stats": bare})
        for name in METRICS
    }
    assert values["sched_start_to_launch_s"] is None
    assert values["agent_launch_to_process_s"] is None
    assert all(
        values[name] == pytest.approx(METRICS[name][1])
        for name in list(METRICS)[2:]
    )


def test_the_phases_and_the_warm_up_add_up(bench):
    """The recorded account is whole: seven phases sum to
    ``start_to_ready_s``, and the warm-up's two metrics stay inside
    ``warm_s``."""
    startup = RECORDED["startup"]
    assert sum(startup["phase_s"].values()) == pytest.approx(
        startup["start_to_ready_s"], abs=1e-6
    )
    run = {"final_stats": RECORDED}
    parts = sum(bench.reader("per_layer", name)(run) for name in (
        "worker_warm_trace_lower_s", "worker_warm_compile_s"
    ))
    assert parts <= RECORDED["warm_s"] + 0.005


def entry(name, cells):
    return {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_span", "layer": METRICS[name][0],
        "moves": "setup_s", "workloads": list(cells),
    }


# what ISSUE 43 appended to `per_layer`, for every cell of its day
ENTRIES = [entry(name, toyroot.CELLS) for name in METRICS]


@pytest.mark.parametrize("metric", ENTRIES, ids=list(METRICS))
def test_the_entry_is_the_one_that_was_to_be_appended(bench, metric):
    """Found by its name; a later cell may have appended itself to its
    ``workloads``.  A reader's file stands under the entry's name."""
    entered = dict(toyroot.named(bench.data["per_layer"], metric["name"]))
    assert set(entered.pop("workloads")) >= set(metric["workloads"])
    assert entered == {k: v for k, v in metric.items() if k != "workloads"}
    assert os.path.isfile(os.path.join(
        REPO, "perfbench", "layer_metrics", metric["name"] + ".py"
    ))


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_all_eight_are_entered(bench_roots, where):
    entered = {m["name"] for m in Manifest(bench_roots[where]).data["per_layer"]}
    assert set(METRICS) <= entered


@pytest.mark.parametrize("cell", toyroot.CELLS)
def test_every_cell_reports_them(bench, cell):
    names = {m["name"] for m in bench.metrics("per_layer", cell)}
    assert set(METRICS) <= names


# -- through the harness, on the CPU -----------------------------------


def start_toy_cell(root, cache, keep, seed):
    """One traced rehearsal of the toy cell on the compile cache
    ``cache``: its result line and the record it kept."""
    env = dict(os.environ, BENCH_RUN="3", JAX_COMPILATION_CACHE_DIR=cache)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "toy.open", "--seed", str(seed), "--seconds",
         "3", "--trace", "1", "--root", root, "--rehearse-cpu",
         "--keep", keep],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(os.path.join(keep, f"toy.open.{seed}.run.json")) as f:
        kept = json.load(f)
    return json.loads(proc.stdout.splitlines()[-1]), kept


@pytest.fixture(scope="module")
def starts(tmp_path_factory):
    """Two starts of the toy cell, whose entry in the throw-away root
    lists the eight as every cell's does, on ONE compile cache that was
    empty before the first: (result line, kept record) of each."""
    root = toyroot.build(str(tmp_path_factory.mktemp("startup_bench")))
    cache = str(tmp_path_factory.mktemp("startup_cache"))
    keep = str(tmp_path_factory.mktemp("startup_kept"))
    return [
        start_toy_cell(root, cache, keep, seed)
        for seed in (2**31 + 41, 2**31 + 42)
    ]


@pytest.fixture(scope="module")
def rehearsal(starts):
    """The first start: nothing stored, both programs compile."""
    return starts[0]


def test_a_traced_run_prints_all_eight(rehearsal):
    result, _kept = rehearsal
    for name in METRICS:
        assert result["metrics"][name]["unit"] == "s", name
        # the process's OS start has a clock tick's resolution (10 ms):
        # a fork within one of the hand-off may read just under 0
        assert result["metrics"][name]["value"] >= -0.02, name
    # and what it printed before
    assert "worker_warm_s" in result["metrics"]
    assert "deploy_plan_s" in result["metrics"]


def test_the_account_a_run_keeps_is_whole_and_close_to_the_outside_one(
        rehearsal):
    result, kept = rehearsal
    startup = kept["final_stats"]["startup"]
    assert all(s is not None for s in startup["phase_s"].values())
    assert sum(startup["phase_s"].values()) == pytest.approx(
        startup["start_to_ready_s"], abs=1e-6
    )
    assert startup["compiles_after_ready"] == 0
    value = {k: v["value"] for k, v in result["metrics"].items()}
    # the scheduler's start and launch -> ready lie inside what
    # `deploy_plan_s` times from outside; what is left over is ready ->
    # COMPLETE (the readiness poll, the status' cycle, the plan poll)
    inside = value["sched_start_to_launch_s"] + startup["start_to_ready_s"]
    assert inside <= value["deploy_plan_s"] + 0.05
    assert value["deploy_plan_s"] - inside < 3.0
    # the warm-up's two parts against the worker's own `warm_s`
    parts = value["worker_warm_trace_lower_s"] + value["worker_warm_compile_s"]
    assert 0 < parts <= value["worker_warm_s"] + 0.01


def test_a_second_start_on_the_same_cache_loads_both_programs(starts):
    """Since PR 42 a warm start loads the pool's two programs from
    ``<compile cache>/programs/``: the two metrics of the host's
    tracing, lowering and compiling read 0 and the warm-up is what is
    left (the loads and the programs' first runs)."""
    (_first, first_kept), (result, kept) = starts
    assert {
        program["source"]
        for name, program in first_kept["final_stats"]["startup"]["warm"].items()
        if name != "other"
    } == {"compiled"}
    warm = kept["final_stats"]["startup"]["warm"]
    assert {warm[name]["source"] for name in ("_prefill", "_decode")} == {
        "stored"}
    assert kept["final_stats"]["startup"]["programs"] == {
        "stored": 2, "compiled": 0}
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert value["worker_warm_trace_lower_s"] == 0
    assert 0 < value["worker_warm_s"]
    assert result["correct"] is True
