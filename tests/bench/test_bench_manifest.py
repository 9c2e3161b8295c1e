"""BENCHMARK.json against the contract it is written to, and every
name in it against the file the harness finds by that name.  The entry
tests hold for a configuration of ANY family: they run over the real
file's entries and over those that ``toyroot.py`` appends, whose
configuration is of a second family and shares no size's name with the
first but ``vocab_size``, and a second time over the root in which a
fourth configuration, cell and per-layer metric are appended
(``toyroot.appended``), as a `model_config` PR appends them.  What only one configuration promises (its
published widths) is a test keyed to that configuration's name; a later
PR brings such a test in a file of its own."""

import json
import os
import re
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

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def roots(tmp_path_factory, bench_roots):
    """Where each manifest's files lie: the repo, the throw-away root
    with the toy family, configuration, mix, cell and metrics, and the
    one with a fourth of each kind appended."""
    return dict(
        bench_roots,
        toy=toyroot.build(str(tmp_path_factory.mktemp("entries"))),
    )


def manifest_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
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
    """(which root, entry): every entry of the real file, every entry
    that the toy root adds to it, and every entry of the root with a
    fourth of each kind appended (the real ones again, as they stand
    there, and the three copies)."""
    real = load()[kind]
    names = {e["name"] for e in real}
    added = [e for e in toyroot.manifest()[kind] if e["name"] not in names]
    return [pytest.param("repo", e, id=e["name"]) for e in real] + [
        pytest.param("toy", e, id="toyroot-" + e["name"]) for e in added
    ] + [
        pytest.param("appended", e, id="appended-" + e["name"])
        for e in toyroot.appended()[kind]
    ]


@pytest.mark.parametrize(
    "where,metric", entries("end_to_end") + entries("per_layer"))
def test_metric_entry(roots, where, metric):
    root = roots[where]
    data = manifest_of(root)
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
        os.path.isfile(os.path.join(root, "perfbench", folder, name + ".py"))
        for name in (metric["name"], metric["name"].rsplit(".", 1)[0])
    )
    kind = "end_to_end" if folder == "end_to_end" else "per_layer"
    assert callable(Manifest(root).reader(kind, metric["name"]))


@pytest.mark.parametrize("where,cell", entries("workloads"))
def test_cell_entry(roots, where, cell):
    data = manifest_of(roots[where])
    bench = Manifest(roots[where])
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


@pytest.mark.parametrize("where,config", entries("configs"))
def test_config_entry(roots, where, config):
    root = roots[where]
    data = manifest_of(root)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert PATH.match(config["file"]) and any(
        config["file"].startswith(path + "/") for path in data["paths"])
    assert [c["file"] for c in data["configs"]].count(config["file"]) == 1
    assert config["name"] in {w["config"] for w in data["workloads"]}
    with open(os.path.join(root, config["file"])) as f:
        model = json.load(f)
    widths = ("size", "_dim", "_rank", "per_tok")
    published = model.get("published", {})
    for key in config["reduced"]:
        assert NAME.match(key)
        assert not any(w in key for w in widths), f"{key} is a width"
        # what was changed states what was published
        assert key in published and published[key] != model[key]
    assert set(published) == set(config["reduced"])
    # its family is a directory that holds the four hooks, and the one
    # size the harness itself reads is there; every other key of the
    # file is its family's to name
    family = Manifest(root).family(config["name"])
    assert family.name == model["family"] and NAME.match(family.name)
    assert isinstance(model["vocab_size"], int) and model["vocab_size"] > 0


# What one configuration promises of its own: the widths its source
# published, by the names ITS family reads.  Keyed by the
# configuration's name; one that a later PR adds is held to its widths
# by a test in a file of that PR's.
PUBLISHED_WIDTHS = {
    # mistralai/Mixtral-8x7B-v0.1 config.json
    "mixtral-8x7b-v0.1": {
        "family": "gqa_decoder", "model_type": "mixtral",
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "head_dim": 128, "num_local_experts": 8, "num_experts_per_tok": 2,
        "vocab_size": 32000,
    },
}


@pytest.mark.parametrize("name", sorted(PUBLISHED_WIDTHS))
def test_a_configuration_keeps_its_published_widths(name):
    model = Manifest(REPO).config(name)
    assert {k: model[k] for k in PUBLISHED_WIDTHS[name]} == (
        PUBLISHED_WIDTHS[name])


def test_the_toy_configuration_shares_no_size_with_the_first_family():
    """So the entry tests above, run over it, prove that they read no
    family's names: a test that did would stop on a KeyError."""
    shared = set(toyroot.TOY_FAMILY_MODEL) & set(
        PUBLISHED_WIDTHS["mixtral-8x7b-v0.1"])
    assert shared == {"family", "vocab_size"}
    assert toyroot.TOY_FAMILY_MODEL["family"] != "gqa_decoder"


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
