"""A whole run of the harness on the CPU, at toy size, in its
rehearsal mode: scheduler -> agent -> worker, traffic, teardown, the
reference check and the result line.  The cell it runs, its
configuration, its mix and one per-layer metric were added to a copy
of the benchmark by files and entries alone."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toyroot  # noqa: E402


def run_cell(root, *extra, cell="toy.open", trace="1"):
    env = dict(os.environ, BENCH_RUN="7")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 9), "--seconds", "5",
         "--trace", trace, "--root", root, *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
    )
    return proc


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return toyroot.build(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def traced_run(toy):
    return run_cell(toy, "--rehearse-cpu")


def test_a_cell_added_by_files_alone_runs_and_is_correct(traced_run):
    assert traced_run.returncode == 0, traced_run.stderr[-3000:]
    lines = traced_run.stdout.splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] == 20 and result["failed"] == 0
    # the metric added by one reader file and one entry is reported
    assert result["metrics"]["toy_judged_requests"]["value"] == 20.0
    # a metric that an existing reader serves under a new suffix is
    # found (decode_tick_ms.toy reads the trace: nothing in a rehearsal)
    assert "engine_active_slots_mean.chat" in result["metrics"]
    assert result["metrics"]["deploy_plan_s"]["value"] > 0


def test_rehearsal_says_cpu_and_writes_no_device_metric(traced_run):
    result = json.loads(traced_run.stdout.splitlines()[-1])
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        traced = {m["name"] for m in json.load(f)["per_layer"]
                  if m["source"] == "device_trace"}
    assert not traced & set(result["metrics"])
    assert "CPU REHEARSAL" in traced_run.stdout


def test_every_number_compared_is_printed_beside_its_limit(traced_run):
    printed = [line for line in traced_run.stdout.splitlines()
               if line.startswith("correct: ")]
    assert {line.split()[1] for line in printed} == set(toyroot.TOY_LIMITS)
    assert all("(limit " in line for line in printed)
    assert "compilations inside ramp and window: 0" in traced_run.stdout


def test_the_measurement_path_refuses_to_run_without_a_tpu(toy):
    proc = run_cell(toy, trace="0")  # tests run with JAX_PLATFORMS=cpu
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{")
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_an_unknown_device_kind_is_an_error():
    sys.path.insert(0, REPO)
    from perfbench import run

    peaks = {"TPU v5 lite": {}}
    run.check_device({"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
                     1, peaks)
    for device, chips in (
        ({"platform": "tpu", "kind": "TPU v9", "count": 1}, 1),
        ({"platform": "cpu", "kind": "cpu", "count": 1}, 1),
        ({"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, 4),
    ):
        with pytest.raises(run.RunFailure):
            run.check_device(device, chips, peaks)


def test_outside_a_checkout_it_fails_and_prints_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "mixtral8x7b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=60,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "not inside a tpu-service-sdk checkout" in proc.stderr
