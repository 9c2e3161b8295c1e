"""The eight readers of the worker's own start-up account (``/stats``
-> ``startup``, ISSUE 41), on a recorded ``final_stats``, and one
rehearsal run of the harness on the CPU in which the real scheduler,
agent and worker produce what they read.  The eight have no entry in
``BENCHMARK.json`` yet (``PERF.md`` 7 ah: an entry at the end of
``per_layer`` fails ``test_bench_lfm2_family.py``, one in the middle
reads as a change to what was there), so the readers are loaded by
their files' names, as an entry's would be, and the entries a
``benchmark`` PR is to append are held here, ``ENTRIES``, and entered
in the throw-away root of the rehearsal."""

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


# what a `benchmark` PR is to append to `per_layer`, `evabyte.docqa`
# first (the cell ISSUE 41 names)
ENTRIES = [entry(name, ["evabyte.docqa"]) for name in METRICS]


@pytest.mark.parametrize("metric", ENTRIES, ids=list(METRICS))
def test_the_entry_to_append_fits_the_manifest(bench, metric):
    """As `test_bench_manifest.py` holds an entry that is there: a
    layer the manifest names, an end-to-end metric that the entry's
    cells report, a reader's file under the entry's name."""
    data = bench.data
    assert metric["layer"] in {m["layer"] for m in data["per_layer"]}
    moved = {m["name"]: m for m in data["end_to_end"]}[metric["moves"]]
    cells = [w["name"] for w in data["workloads"]]
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    assert os.path.isfile(os.path.join(
        REPO, "perfbench", "layer_metrics", metric["name"] + ".py"
    ))


def test_none_of_the_eight_is_entered_yet(bench):
    """The `benchmark` PR that appends ``ENTRIES`` turns this one and
    the next around."""
    entered = {m["name"] for m in bench.data["per_layer"]}
    assert not set(METRICS) & entered


@pytest.mark.parametrize(
    "cell", ["evabyte.docqa", "mixtral8x7b.chat", "lfm2-24b.chat"])
def test_no_cell_reports_them_yet(bench, cell):
    names = {m["name"] for m in bench.metrics("per_layer", cell)}
    assert not set(METRICS) & names


# -- through the harness, on the CPU -----------------------------------


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the toy cell with the eight entered for
    it in the throw-away root's manifest, its record kept."""
    root = toyroot.build(str(tmp_path_factory.mktemp("startup_bench")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["per_layer"] += [entry(name, ["toy.open"]) for name in METRICS]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    keep = str(tmp_path_factory.mktemp("startup_kept"))
    env = dict(os.environ, BENCH_RUN="3")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "toy.open", "--seed", str(2**31 + 41), "--seconds",
         "3", "--trace", "1", "--root", root, "--rehearse-cpu",
         "--keep", keep],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(os.path.join(keep, f"toy.open.{2**31 + 41}.run.json")) as f:
        kept = json.load(f)
    return json.loads(proc.stdout.splitlines()[-1]), kept


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
