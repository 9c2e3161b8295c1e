"""A whole run of the harness on the CPU, at toy size, in its
rehearsal mode: scheduler -> agent -> worker, traffic, teardown, the
reference check and the result line.  The cell it runs, its
configuration, that configuration's FAMILY (the four files the harness
finds by its name), its mix and two per-layer metrics were added to a
copy of the benchmark by files and entries alone."""

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
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True
    assert result["attempted"] == 20 and result["failed"] == 0
    # the metric added by one reader file and one entry is reported
    assert result["metrics"]["toy_judged_requests"]["value"] == 20.0
    # a metric that an existing reader serves under a new suffix is
    # found (decode_tick_ms.toy reads the trace: nothing in a rehearsal)
    assert "engine_active_slots_mean.chat" in result["metrics"]
    assert result["metrics"]["deploy_plan_s"]["value"] > 0


def test_every_hook_the_run_went_through_was_the_toy_familys(traced_run):
    """One marker a hook, each made by the toy family's own file; and
    the toy configuration has no key but ``vocab_size`` that the first
    family or a size-reading harness could have read."""
    assert not (set(toyroot.TOY_FAMILY_MODEL) - {"vocab_size"}) & {
        "hidden_size", "head_dim", "num_hidden_layers", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "num_local_experts",
    }
    out = traced_run.stdout
    # program_env: its own key beside the sizes, which the worker's
    # /stats confirmed or the run would have stopped
    sizing = [line for line in out.splitlines()
              if line.startswith("family toy_family: sizing env ")]
    env = json.loads(sizing[0].split("sizing env ", 1)[1])
    assert env["TASKCFG_ALL_TOY_FAMILY"] == "env-marker"
    assert (env["D_MODEL"], env["TASKCFG_ALL_N_EXPERTS"]) == ("64", "4")
    assert env["KV_PAGES"] == "64"  # the mix's sizes, merged over it
    # weight_specs: the toy reference refuses weights whose final norm
    # is not the toy family's, and the served tokens agree with it
    assert "reference of family toy_family on cpu" in out
    result = json.loads(out.splitlines()[-1])
    assert result["correct"] is True
    # reference: only under the toy family's margin (+1000) is a
    # position steadier than the cell's 999, and `steady_max_gap` reads
    steady = [line for line in out.splitlines() if "of them steady" in line]
    read, of_them = [int(w) for w in steady[0].split() if w.isdigit()][-2:]
    assert read == of_them > 0
    assert result["compared"]["steady_max_gap"][0] == 0.0
    # needs: a key that only the toy family's needs.py returns
    assert result["metrics"]["toy_needs_marker"]["value"] == 26.0


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
    # once: as the last lines of stderr, and last in the result's line
    limits = toyroot.TOY_CELL["correct_limits"]
    printed = [line for line in traced_run.stderr.splitlines()
               if line.startswith("correct: ")]
    assert printed == traced_run.stderr.splitlines()[-len(limits):]
    assert [line.split()[1] for line in printed] == list(limits)
    assert all("(limit " in line for line in printed)
    assert "correct: " not in traced_run.stdout
    result = json.loads(traced_run.stdout.splitlines()[-1])
    assert result["compared"] == {k: [0.0, v] for k, v in limits.items()}
    assert "compilations inside ramp and window: 0" in traced_run.stdout


def test_a_traced_run_says_what_it_traced_and_what_the_stop_took(traced_run):
    """ISSUE 34: the wait for ``/trace/stop`` is reckoned from the
    device programs the poller saw in the traced seconds."""
    import re

    sys.path.insert(0, REPO)
    from perfbench import run

    line, = [line for line in traced_run.stdout.splitlines()
             if line.startswith("traced ")]
    found = re.fullmatch(
        r"traced ([\d.]+)s, about (\d+) device programs; /trace/stop took "
        r"([\d.]+)s of the ([\d.]+)s it may and wrote (\d+) bytes", line)
    traced_s, programs, stop_s, limit, written = (
        float(g) for g in found.groups())
    assert 1.0 <= traced_s < 1.5  # the toy mix's trace_s
    assert programs > 0 and stop_s < limit and written > 0
    assert limit == pytest.approx(
        run.stop_limit_s(int(programs), traced_s), abs=0.06)


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
