"""BENCHMARK.json against the contract it is written to, and every
name in it against the file the harness finds by that name."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)

from perfbench.harness.manifest import Manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes():
    data = load()
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= data["run_seconds"] <= 51
    assert isinstance(data["run_seconds"], int)
    assert 1 <= len(data["command"]) <= 32
    for path in data["paths"]:
        assert PATH.match(path) and not path.startswith("/")
        assert ".." not in path.split("/")
        assert os.path.isdir(os.path.join(REPO, path))
    assert data["command"][1].startswith(data["paths"][0] + "/")


def entries(kind):
    return [pytest.param(e, id=e["name"]) for e in load()[kind]]


@pytest.mark.parametrize("metric", entries("end_to_end") + entries("per_layer"))
def test_metric_entry(metric):
    data = load()
    cells = {w["name"] for w in data["workloads"]}
    end_to_end = {m["name"]: m for m in data["end_to_end"]}
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", [])) <= cells
    if metric["name"] in end_to_end:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        folder = "end_to_end"
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        moved = end_to_end[metric["moves"]]
        # it is read only in cells that report the metric it moves
        assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
        folder = "layer_metrics"
    # a reader file of its own, or its quantity's (the name less its suffix)
    assert any(
        os.path.isfile(os.path.join(REPO, "perfbench", folder, name + ".py"))
        for name in (metric["name"], metric["name"].rsplit(".", 1)[0])
    )
    kind = "end_to_end" if folder == "end_to_end" else "per_layer"
    assert callable(Manifest(REPO).reader(kind, metric["name"]))


@pytest.mark.parametrize("cell", entries("workloads"))
def test_cell_entry(cell):
    data = load()
    bench = Manifest(REPO)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in data["configs"]}
    mix = bench.traffic(cell["traffic"])
    params = bench.cell_params(cell["name"])
    assert mix["loop"] == "open"
    assert params["rate_rps"] > 0
    numbers = {"max_gap", "mean_gap", "mismatch_share", "wide_gap_share"}
    assert params["correct_limits"]
    assert set(params["correct_limits"]) <= numbers | {
        "steady_" + n for n in numbers
    }
    reported = [m["name"] for m in bench.metrics("end_to_end", cell["name"])]
    assert "setup_s" in reported and len(reported) >= 2
    assert bench.metrics("per_layer", cell["name"])
    pairs = [(w["config"], w["traffic"]) for w in data["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


@pytest.mark.parametrize("config", entries("configs"))
def test_config_entry(config):
    data = load()
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"].startswith("perfbench/") and PATH.match(config["file"])
    assert [c["file"] for c in data["configs"]].count(config["file"]) == 1
    assert config["name"] in {w["config"] for w in data["workloads"]}
    with open(os.path.join(REPO, config["file"])) as f:
        model = json.load(f)
    widths = ("size", "_dim", "_rank", "per_tok")
    for key in config["reduced"]:
        assert NAME.match(key)
        assert not any(w in key for w in widths), f"{key} is a width"
        # what was changed states what was published
        assert key in model["published"] and model["published"][key] != model[key]
    assert set(model["published"]) == set(config["reduced"])
    # published widths (Mistral 7B v0.3 / Mixtral 8x7B v0.1 config.json)
    assert (model["hidden_size"], model["intermediate_size"]) == (4096, 14336)
    assert (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"]) == (32, 8, 128)
    if model["model_type"] == "mixtral":
        assert (model["num_local_experts"], model["num_experts_per_tok"],
                model["vocab_size"]) == (8, 2, 32000)
    else:
        assert model["vocab_size"] == 32768


def test_names_are_unique_and_four_chip_cells_are_few():
    data = load()
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in data[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in data["end_to_end"] + data["per_layer"]]
    assert len(metrics) == len(set(metrics))
    four = sum(w["chips"] == 4 for w in data["workloads"])
    assert four <= max(1, len(data["workloads"]) // 4)
    layers = {m["layer"] for m in data["per_layer"]}
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_every_file_under_paths_is_named_from_allowed_characters():
    for path in load()["paths"]:
        for folder, _dirs, files in os.walk(os.path.join(REPO, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                relative = os.path.relpath(os.path.join(folder, name), REPO)
                assert PATH.match(relative), relative


def test_the_benchmark_imports_neither_bench_nor_chip_smoke():
    for folder, _dirs, files in os.walk(os.path.join(REPO, "perfbench")):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                text = f.read()
            assert not re.search(
                r"^\s*(import|from)\s+(bench|chip_smoke)\b", text, re.M
            ), name
