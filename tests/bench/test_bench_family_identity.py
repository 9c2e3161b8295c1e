"""The first family is the parent's code, moved: for the same seed it
builds the same weights bit for bit, sends the same env, states the same
needs and computes the same reference logits as the code did where it
lay before (commit 93e5eac; ``recorded_parent.json`` was written by that
code, on the CPU at toy size, before the move).  And a configuration
whose family cannot be found stops a run before anything is deployed."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import toyroot  # noqa: E402
from perfbench.harness.manifest import (  # noqa: E402
    FAMILY_HOOKS,
    Manifest,
    family_of,
)

with open(os.path.join(HERE, "recorded_parent.json")) as f:
    PARENT = json.load(f)
TOY = PARENT["toy_model"]
MODELS = {"mixture": TOY, "dense": dict(TOY, num_local_experts=0)}


def real_config():
    bench = Manifest(REPO)
    return bench, bench.config("mixtral-8x7b-v0.1")


def test_the_toy_configuration_recorded_is_the_one_the_tests_use():
    assert TOY == toyroot.TOY_MODEL


@pytest.mark.parametrize("recorded", sorted(PARENT["weights_sha256"]))
def test_weights_are_the_parents_bit_for_bit(recorded):
    import jax
    import jax.numpy as jnp

    from perfbench.harness.weights import make_weights

    kind, seed = recorded.split(".")
    model = MODELS[kind.replace("_bf16", "")]
    dtype = jnp.bfloat16 if kind.endswith("_bf16") else jnp.float32
    specs = toyroot.family().weight_specs(model)
    tree = make_weights(specs, int(seed), dtype)
    # a bf16 leaf was recorded by the bytes of its float32 widening
    got = {
        "/".join(str(k.key) for k in path): hashlib.sha256(
            np.asarray(leaf.astype(jnp.float32)).tobytes()
        ).hexdigest()
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }
    assert got == PARENT["weights_sha256"][recorded]
    assert int(seed) in (5, 2**31 + 77)  # one of them over 31 bits


def test_the_env_sent_for_the_real_file_is_the_parents():
    from perfbench import run

    bench, model = real_config()
    path = bench.config_path("mixtral-8x7b-v0.1")
    family = bench.family("mixtral-8x7b-v0.1")
    assert family.name == "gqa_decoder"
    sizes = run.sizing_env(family, model, path, bench.traffic("chat-steady"))
    assert sizes == PARENT["sizing_env"]
    env = run.deployment_env(sizes, path, 7, "/w")
    assert {k: v for k, v in env.items() if k in sizes} == sizes
    # the file's path is all the worker entry is sent of the family
    assert set(env) - set(sizes) == {
        "JAX_FRAMEWORK_DIR", "TASKCFG_ALL_PERFBENCH_CONFIG_FILE",
        "TASKCFG_ALL_PERFBENCH_SEED"}
    assert family_of(env["TASKCFG_ALL_PERFBENCH_CONFIG_FILE"]) is family
    # this family's own rule, kept: the program derives the head's size
    with pytest.raises(run.RunFailure, match="derives head_dim"):
        run.sizing_env(family, dict(model, head_dim=64), path,
                       bench.traffic("chat-steady"))


@pytest.mark.parametrize("call", sorted(PARENT["needs"]))
def test_needs_of_the_real_file_are_the_parents(call):
    _bench, model = real_config()
    name, numbers = call.rstrip(")").split("(")
    a, b = (float(x) for x in numbers.split(","))
    needs = getattr(toyroot.family().needs, name)(model, a, b)
    assert needs == PARENT["needs"][call]


@pytest.mark.parametrize("kind", ["mixture", "dense"])
def test_reference_logits_are_the_parents(kind):
    import jax.numpy as jnp

    from perfbench.harness.weights import make_weights

    recorded, model = PARENT["reference"], MODELS[kind]
    family = toyroot.family()
    weights = make_weights(
        family.weight_specs(model), recorded["seed"], jnp.float32
    )
    logits, margins = family.reference.logits(
        model, weights, np.asarray(recorded["tokens"]),
        rows=recorded["rows"], margins=True,
    )
    want = recorded[kind]
    assert np.allclose(logits, want["logits"], rtol=0, atol=1e-5)
    assert np.allclose(
        np.asarray(margins, np.float64),
        [float(m) for m in want["margins"]], rtol=0, atol=1e-5,
    )
    lower = family.reference.logits(
        model, weights, np.asarray(recorded["tokens"]),
        rows=recorded["rows"], lower="int8",
    )
    assert np.allclose(lower, want["int8_logits"], rtol=0, atol=1e-5)
    assert not np.allclose(lower, want["logits"], rtol=0, atol=1e-3)


def test_the_roofline_readers_reach_the_family_through_the_runs_record():
    from perfbench.harness.roofline import least_seconds

    bench, model = real_config()
    peak = bench.peaks()["TPU v5 lite"]
    a, b = 100.0, 200.0
    run = {
        "model": model, "peaks": peak, "window": [a, b],
        "trace_window": [a, b],
        "config_file": bench.config_path("mixtral-8x7b-v0.1"),
        "stats_samples": [
            {"_t": 150.0, "active_slots": 64, "kv_live_tokens": 512}],
        "outcomes": [{"prompt_tokens": 1024}], "judged": [0],
        "final_stats": {"prefill_chunk_tokens": 64},
        "trace": {"programs": {"jit__decode": {"median_ms": 20.0},
                               "jit__prefill": {"median_ms": 12.5}}},
    }
    for name, program_ms, needs in (
        ("decode_step_roofline.chat", 20.0,
         PARENT["needs"]["decode_tick(64, 512)"]),
        ("prefill_chunk_roofline.chat", 12.5,
         PARENT["needs"]["prefill_chunk(64, 512)"]),
    ):
        share = bench.reader("per_layer", name)(run)
        least = least_seconds(needs, peak)[0]
        assert share == pytest.approx(100 * least / (program_ms * 1e-3))
        assert 0 < share < 100


# ---- failing early: one bad configuration a case, added to a toy root

def half_family(bench_dir):
    """A family with three of the four files, and one whose needs.py
    lacks a function."""
    for name, files in (("half", ["program_env.py", "weight_specs.py",
                                  "reference.py"]),
                        ("no_chunk", list(FAMILY_HOOKS))):
        os.mkdir(os.path.join(bench_dir, "families", name))
        for file in files:
            with open(os.path.join(bench_dir, "families", "toy_family",
                                   file)) as f:
                text = f.read()
            if name == "no_chunk" and file == "needs.py":
                text = text.replace("def prefill_chunk", "def prefill_part")
            with open(os.path.join(bench_dir, "families", name, file),
                      "w") as f:
                f.write(text)


BAD = {
    "no-family-key": (None, ["configs/bad-no-family-key.json",
                             'states no "family"']),
    "no-directory": ("latent_attention",
                     ["no directory", "families/latent_attention"]),
    "a-file-missing": ("half", ["no file", "families/half/needs.py"]),
    "a-function-missing": ("no_chunk", ["families/no_chunk/needs.py",
                                        "no function prefill_chunk"]),
}


@pytest.fixture(scope="module")
def bad_root(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("bad")))
    half_family(os.path.join(root, "perfbench"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for case, (family, _said) in BAD.items():
        model = dict(toyroot.TOY_FAMILY_MODEL, family=family)
        if family is None:
            del model["family"]
        file = f"perfbench/configs/bad-{case}.json"
        with open(os.path.join(root, file), "w") as f:
            json.dump(model, f)
        manifest["configs"].append(dict(
            manifest["configs"][-1], name="bad-" + case, file=file))
        manifest["workloads"].append(dict(
            manifest["workloads"][-1], name="bad." + case,
            config="bad-" + case))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("case", sorted(BAD))
def test_a_family_that_cannot_be_found_stops_the_run_before_deploy(
        bad_root, case):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "bad." + case, "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu", "--root", bad_root],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=60,
    )
    assert proc.returncode != 0
    for said in BAD[case][1]:
        assert said in proc.stderr, proc.stderr
    # the families present are named (a later PR's are there as well)
    present = proc.stderr.split("families present: ")[1].split("\n")[0]
    assert {"gqa_decoder", "half", "no_chunk", "toy_family"} <= set(
        present.split(", "))
    # nothing was deployed and no result was printed
    assert not os.path.exists(os.path.join(bad_root, ".perfbench_run"))
    assert "{" not in proc.stdout
