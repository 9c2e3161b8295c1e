"""A throw-away benchmark root for the tests: the real BENCHMARK.json
and the real files under ``perfbench/``, plus a SECOND FAMILY (its four
files), a toy configuration of that family, a toy mix, a cell of the
two, two more per-layer metrics with readers of their own and one
(``decode_tick_ms.toy``) that an existing reader serves under a new
suffix, all ADDED as new files and new entries.  No file that is there
is edited, which is what a later PR is held to.

``build_appended`` makes the plainer root that guards the lists
themselves: the real manifest with ONE more configuration, cell and
per-layer metric at the end of each list (``appended``), copies of the
last of each under new names.  The entry tests of every file here run
against it too (``ROOTS``), so a test that finds an entry by its place,
counts a list or holds a ``workloads`` list to today's cells fails
here before it stops a later PR."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def family(name="gqa_decoder", root=REPO):
    """The four hooks of a family under ``root``, as the harness loads
    them."""
    sys.path.insert(0, REPO)
    from perfbench.harness.manifest import load_family

    return load_family(os.path.join(root, "perfbench", "families", name))


TOY_MODEL = {
    "model_type": "mixtral", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "num_local_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
}
# The toy cell's configuration, of the family ``toy_family``: no key of
# it but ``vocab_size`` is one that the first family reads, so a hook of
# that family, or a harness that read a size itself, stops on a KeyError.
TOY_FAMILY_NAMES = {
    "width": "hidden_size", "ffn": "intermediate_size",
    "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
    "head": "head_dim", "depth": "num_hidden_layers",
    "experts": "num_local_experts", "chosen": "num_experts_per_tok",
}
TOY_FAMILY_MODEL = {"family": "toy_family", **{
    theirs: TOY_MODEL[ours] for theirs, ours in TOY_FAMILY_NAMES.items()
}, **{k: TOY_MODEL[k] for k in ("vocab_size", "rope_theta", "rms_norm_eps")}}
# The program serves one architecture, so the second family's files
# borrow the first's arithmetic under their own names; each hook leaves
# a marker that only its own file makes.
TOY_FAMILY_BORROWS = '''
import functools
import os

from perfbench.harness.manifest import load_family

NAMES = {names!r}


@functools.lru_cache(maxsize=None)
def first():
    return load_family(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "gqa_decoder"))


def renamed(model):
    return {{NAMES.get(k, k): v for k, v in model.items()}}
'''.format(names=TOY_FAMILY_NAMES)
TOY_FAMILY = {
    "program_env.py": '''

def program_env(model, config_path):
    assert os.path.isfile(config_path)
    env = first().program_env(renamed(model), config_path)
    return dict(env, TASKCFG_ALL_TOY_FAMILY="env-marker")
''',
    # the marker: a final norm of ones exactly, where the first family
    # draws it around one
    "weight_specs.py": '''

def weight_specs(model):
    return [
        (path, shape, kind, 0.0 if path == ("final_norm",) else scale, dtype)
        for path, shape, kind, scale, dtype
        in first().weight_specs(renamed(model))
    ]
''',
    # it refuses weights without that marker, and its steadiness margin
    # means something of its own: every position is 1000 steadier
    "reference.py": '''

def logits(model, weights, tokens, rows=None, lower=None, margins=False):
    if not bool((weights["final_norm"] == 1).all()):
        raise SystemExit("these weights are not the toy family's")
    out, margin = first().reference.logits(
        renamed(model), weights, tokens, rows, lower, margins=True)
    return (out, margin + 1000.0) if margins else out
''',
    "needs.py": '''

def decode_tick(model, live_rows, live_tokens):
    needs = first().needs.decode_tick(renamed(model), live_rows, live_tokens)
    return dict(needs, toy_family=26.0)


def prefill_chunk(model, chunk_tokens, context_tokens):
    return first().needs.prefill_chunk(
        renamed(model), chunk_tokens, context_tokens)
''',
}
TOY_SIZING = {
    "MAX_LEN": 96, "MAX_NEW_TOKENS": 16, "SERVE_SLOTS": 4,
    "SERVE_BATCH": 1, "KV_PAGES": 64,
}
TOY_OPEN = {
    "why": "toy open loop for the tests", "loop": "open",
    "prompt_tokens": {"kind": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 4, "max": 72},
    "output_tokens": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
    "pairing_seed": 7, "order_seed": 11, "ramp_s": 1, "tail_s": 5, "drain_limit_s": 60,
    "request_timeout_s": 120, "clients": 16, "trace_after_s": 0.5,
    "trace_s": 1, "check_draw": 2, "check_tokens": 4,
    "sizing_env": TOY_SIZING,
}
TOY_LIMITS = {"max_gap": 1e-3, "mean_gap": 1e-4, "mismatch_share": 0.02}
# the cell's own: a position is steady only by the toy family's margin
# (the first family's margins are all far under 999)
TOY_CELL = {
    "rate_rps": 4.0, "routing_margin": 999.0,
    "correct_limits": dict(TOY_LIMITS, steady_max_gap=1e-3),
}
TOY_READER = '''"""A per-layer metric added by a file alone."""


def read(run):
    return float(len(run["judged"]))
'''
TOY_NEEDS_READER = '''"""What the needs of the run's family say beyond bytes and flops."""
from perfbench.harness.readers import family_needs


def read(run):
    return family_needs(run).decode_tick(run["model"], 1, 1).get("toy_family")
'''


def copy_bench(root: str) -> str:
    """The real files under ``perfbench/``, copied into ``root``."""
    shutil.copytree(
        os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    return os.path.join(root, "perfbench")


def build(root: str) -> str:
    """Make the throw-away root under ``root``; returns it."""
    bench = copy_bench(root)

    def put(relative, payload):
        path = os.path.join(bench, relative)
        assert not os.path.exists(path), f"{relative} would be edited"
        with open(path, "w") as f:
            if isinstance(payload, str):
                f.write(payload)
            else:
                json.dump(payload, f)

    os.mkdir(os.path.join(bench, "families", "toy_family"))
    for name, body in TOY_FAMILY.items():
        put("families/toy_family/" + name, TOY_FAMILY_BORROWS + body)
    put("configs/toy-moe.json", TOY_FAMILY_MODEL)
    put("traffic/toy-open.json", TOY_OPEN)
    put("cells/toy.open.json", TOY_CELL)
    put("layer_metrics/toy_judged_requests.py", TOY_READER)
    put("layer_metrics/toy_needs_marker.py", TOY_NEEDS_READER)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest(), f)
    return root


def manifest() -> dict:
    """The real ``BENCHMARK.json`` with the toy entries appended."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "toy-moe", "source": "tests", "reduced": [],
        "file": "perfbench/configs/toy-moe.json", "why": "toy",
    })
    manifest["workloads"].append(
        {"name": "toy.open", "config": "toy-moe", "traffic": "toy-open",
         "chips": 1, "why": "toy"}
    )
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append("toy.open")
    manifest["per_layer"] += [
        {"name": "toy_judged_requests", "unit": "requests",
         "better": "higher", "source": "program_counter",
         "layer": "load generator", "moves": "setup_s",
         "workloads": ["toy.open"]},
        {"name": "toy_needs_marker", "unit": "marks",
         "better": "higher", "source": "program_counter",
         "layer": "kernel", "moves": "setup_s", "workloads": ["toy.open"]},
        # a quantity split by a new suffix: read by decode_tick_ms.py
        {"name": "decode_tick_ms.toy", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "device step",
         "moves": "setup_s", "workloads": ["toy.open"]},
    ]
    return manifest


# which root an entry test reads: the repo's own manifest, and the one
# with a fourth of each kind appended
ROOTS = ["repo", "appended"]
AGAIN = "-again"
# the cells of today (PR 43), for a test of which of THEM an entry lists:
# a later cell may append itself to any ``workloads``
CELLS = ["mixtral8x7b.chat", "evabyte.docqa", "lfm2-24b.chat"]


def named(entries: list, name: str) -> dict:
    """The entry of a manifest's list by its name, never by its place."""
    return next(e for e in entries if e["name"] == name)


def again(name: str) -> str:
    """``lfm2-24b.chat`` -> ``lfm2-24b-again.chat``: the suffix (what a
    quantity is split by, the mix's half of a cell's name) stays."""
    stem, dot, suffix = name.partition(".")
    return stem + AGAIN + dot + suffix


def appended(manifest: dict = None) -> dict:
    """``manifest`` (the real ``BENCHMARK.json``) as a `model_config`
    PR leaves it: copies of the last configuration, the last cell and
    the last per-layer metric under new names at the END of their
    lists, and the new cell's name appended to every ``workloads`` list
    that holds the cell it copies.  Nothing that was there moves."""
    if manifest is None:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    config, cell, metric = (
        dict(manifest[kind][-1])
        for kind in ("configs", "workloads", "per_layer")
    )
    copied = cell["name"]
    config["name"] = again(config["name"])
    config["file"] = "{}{}.json".format(config["file"][:-len(".json")], AGAIN)
    cell["name"], cell["config"] = again(copied), config["name"]
    # the copy of the last metric lists what the last lists, once that
    # has the new cell
    for listed in manifest["end_to_end"] + manifest["per_layer"]:
        if copied in listed.get("workloads", []):
            listed["workloads"].append(cell["name"])
    metric["name"] = again(metric["name"])
    metric["workloads"] = list(manifest["per_layer"][-1]["workloads"])
    manifest["configs"].append(config)
    manifest["workloads"].append(cell)
    manifest["per_layer"].append(metric)
    return manifest


def build_appended(root: str) -> str:
    """The throw-away root of ``appended()``: the real files, and the
    three copies' files beside them."""
    copy_bench(root)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    manifest = appended()
    for old, new in (
        (real["configs"][-1]["file"], manifest["configs"][-1]["file"]),
        ("perfbench/cells/" + real["workloads"][-1]["name"] + ".json",
         "perfbench/cells/" + manifest["workloads"][-1]["name"] + ".json"),
        ("perfbench/layer_metrics/" + real["per_layer"][-1]["name"] + ".py",
         "perfbench/layer_metrics/" + manifest["per_layer"][-1]["name"]
         + ".py"),
    ):
        assert not os.path.exists(os.path.join(root, new)), new
        shutil.copy(os.path.join(root, old), os.path.join(root, new))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


@pytest.fixture(scope="module")
def bench_roots(tmp_path_factory):
    """Where an entry test reads ``BENCHMARK.json``, by the names of
    ``ROOTS``: the repo, and the throw-away root in which a fourth
    configuration, cell and per-layer metric are appended.  A test file
    imports it by name (a ``conftest.py`` here would hide ``tests/``'s
    own from the tests that import that one as a module)."""
    return {
        "repo": REPO,
        "appended": build_appended(str(tmp_path_factory.mktemp("appended"))),
    }
