"""The family ``afmoe`` of the benchmark (perfbench/families/afmoe/) and
its cell ``trinity-mini.longdoc``: the reference's two copies, the four
hooks, the plain reference against the program at toy size as ``correct``
compares them, the int8 control, ``needs.py`` by hand at the cell's
sizes, the published widths by their own key names, the two readers this
family brought on a recorded sample, and a whole rehearsal of a toy root's
``afmoe`` cell on the CPU."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import toyroot  # noqa: E402
from toyroot import bench_roots  # noqa: E402,F401
from perfbench.harness import check  # noqa: E402
from perfbench.harness.manifest import Manifest  # noqa: E402

CONFIG = os.path.join(REPO, "perfbench", "configs", "trinity-mini.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "trinity-mini.longdoc"


@pytest.fixture(scope="module")
def afmoe():
    return toyroot.family("afmoe")


@pytest.fixture(scope="module")
def published():
    with open(CONFIG) as f:
        return json.load(f)


def toy_of(published):
    """The published file at toy widths: the same pattern, the same
    routing, every key the family reads; a head of 32 on a hidden size
    of 64 with 4 heads, a window of 32."""
    return dict(
        published, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, num_experts=8,
        num_experts_per_tok=2, vocab_size=128, sliding_window=32,
    )


@pytest.fixture(scope="module")
def toy(published):
    return toy_of(published)


TOY_LIMITS = {"max_gap": 1e-3, "mean_gap": 1e-4, "mismatch_share": 0.02}
TOY_SIZES = dict(MAX_LEN="160", MAX_NEW_TOKENS="48", SERVE_SLOTS="3",
                 KV_PAGES="30", PREFILL_CHUNK_TOKENS="16",
                 KV_PAGE_TOKENS="16")


def test_the_two_copies_of_the_reference_are_one_file(afmoe):
    with open(os.path.join(afmoe.directory, "reference.py")) as f:
        benchmark = f.read()
    with open(os.path.join(
        REPO, "dcos_commons_tpu", "models", "reference", "afmoe.py"
    )) as f:
        program_side = f.read()
    assert benchmark == program_side
    assert "dcos_commons_tpu" not in benchmark.split('"""', 2)[2]


@pytest.fixture(scope="module")
def served(afmoe, toy, tmp_path_factory):
    """(model, weights, requests): three prompts served by the program,
    built from the toy configuration's FILE as the worker builds it and
    given the family's seeded weights, through pool and engine."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import config_from_env, init_params
    from dcos_commons_tpu.serve.engine import PagedEngine
    from dcos_commons_tpu.serve.paging import paged_config_from_env
    from dcos_commons_tpu.serve.pool import PagedPoolModel
    from perfbench.harness.weights import make_weights, tree_differences

    path = str(tmp_path_factory.mktemp("afmoe") / "toy-afmoe.json")
    with open(path, "w") as f:
        json.dump(toy, f)
    env = {k.replace("TASKCFG_ALL_", ""): v
           for k, v in afmoe.program_env(toy, path).items()}
    assert env["MODEL_CONFIG"] == path
    assert (env["D_MODEL"], env["N_EXPERTS"], env["D_FF"]) == ("64", "8", "96")
    env.update(TOY_SIZES)
    config = config_from_env(env, dtype=jnp.float32, remat=False)
    specs = afmoe.weight_specs(toy)
    theirs = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    assert tree_differences(specs, config.dtype, theirs) == []
    weights = make_weights(specs, 2**31 + 5, jnp.float32)
    paged = paged_config_from_env(env)
    assert paged.prefix_cache is False      # a ring holds what a prefix left
    assert paged.layout.ring_pages == 3 and paged.window_arena_pages == 10
    pool = PagedPoolModel(
        config, weights, paged.slots, paged.max_len, paged.page_tokens,
        paged.pages, paged.chunk_tokens,
    )
    assert pool.layout == paged.layout
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, paged.slots, paged.max_len,
        paged.max_len - 48, page_tokens=paged.page_tokens,
        pages=paged.pages, chunk_tokens=paged.chunk_tokens,
        prefix_cache=paged.prefix_cache, layout=pool.layout,
        resolve_decode_fn=pool.resolve_decode,
    )
    rng = np.random.default_rng(5)
    try:
        requests = []
        for plen, new in ((20, 10), (70, 40), (105, 30)):
            prompt = rng.integers(0, 128, plen).tolist()
            requests.append({
                "prompt": prompt, "served": engine.submit([prompt], new)[0],
            })
        stats = engine.stats()
    finally:
        engine.stop()
    return toy, weights, requests, stats


def test_the_reference_agrees_with_the_program_as_correct_compares(
    afmoe, served
):
    model, weights, requests, stats = served
    correct, compared, positions, steady = check.compare(
        afmoe.reference, model, weights, requests, TOY_LIMITS
    )
    assert correct, compared
    # the margin is the mixture's: finite, and over 0 at every position
    assert positions == steady == 80
    # every history page came back, every slot's ring is its own still
    assert stats["kv_pages_free"] == stats["kv_pages_total"] == 30
    assert stats["kv_window_live_tokens"] == 0
    assert stats["loop"]["decode_window_entries_sum"] > 0


def test_the_int8_control_fails_the_same_limits(afmoe, served):
    import jax.numpy as jnp

    model, weights, requests, _stats = served
    gaps = []
    for r in requests:
        exact, margin = check.served_logits(
            afmoe.reference, model, weights, r["prompt"], r["served"]
        )
        assert np.isfinite(margin).all() and (margin >= 0).all()
        lower, _ = check.served_logits(
            afmoe.reference, model, weights, r["prompt"], r["served"],
            lower="int8",
        )
        gaps.append(check.chosen_gaps(exact, np.asarray(jnp.argmax(lower, -1))))
    gaps = np.concatenate(gaps)
    correct, compared = check.judge(
        gaps, np.ones(len(gaps), bool), TOY_LIMITS
    )
    assert not correct, compared


def test_a_program_without_window_layers_refuses_the_file(toy, tmp_path):
    """What the parent of this family's PR did with the same file: its
    ``config_fields_from_file`` raised on ``head_dim`` and on
    ``sliding_attention``, so the task exited during deploy.  Here: the
    names it would not have known are the ones the file is built from."""
    from dcos_commons_tpu.models.transformer import (
        _OPERATORS,
        config_fields_from_file,
    )

    path = tmp_path / "toy.json"
    path.write_text(json.dumps(toy))
    fields = config_fields_from_file(str(path))
    assert fields["d_head"] * fields["n_heads"] != fields["d_model"]
    assert "sliding" in fields["layer_types"]
    assert _OPERATORS["sliding_attention"] == "sliding"


def test_program_env_refuses_what_the_reference_does_not_compute(afmoe, toy):
    with pytest.raises(ValueError, match="afmoe"):
        afmoe.program_env(dict(toy, model_type="mixtral"), CONFIG)
    with pytest.raises(ValueError, match="layer_types"):
        afmoe.program_env(dict(toy, num_hidden_layers=8), CONFIG)
    for key in ("attention_gate", "sandwich_norm", "nope_on_full_attention",
                "qk_norm", "use_expert_bias", "mup_enabled"):
        with pytest.raises(ValueError, match=key):
            afmoe.program_env(dict(toy, **{key: False}), CONFIG)
    with pytest.raises(ValueError, match="num_shared_experts"):
        afmoe.program_env(dict(toy, num_shared_experts=2), CONFIG)


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_trinity_keeps_its_published_widths(published, bench_roots, where):
    """Every width under its published key; what was cut is depth."""
    assert published["hidden_size"] == 2048
    assert (published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"]) == (
        32, 4, 128)
    assert published["intermediate_size"] == 6144
    assert (published["num_experts"], published["moe_intermediate_size"],
            published["num_experts_per_tok"],
            published["num_shared_experts"]) == (128, 1024, 8, 1)
    assert (published["sliding_window"], published["vocab_size"]) == (
        2048, 200192)
    assert (published["route_scale"], published["score_func"]) == (
        2.826, "sigmoid")
    assert (published["num_hidden_layers"], published["num_dense_layers"]) \
        == (5, 1)
    assert published["layer_types"] == (
        ["sliding_attention"] * 4 + ["full_attention"]
    )
    assert sorted(published["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers",
    ]
    # every switch the published config has no key for is listed
    for key in ("sandwich_norm", "attention_gate", "qk_norm",
                "nope_on_full_attention", "use_expert_bias",
                "route_norm_eps", "mup_enabled"):
        assert key in published["assumed"], key
    entry = toyroot.named(
        Manifest(bench_roots[where]).data["configs"], "trinity-mini")
    assert entry["file"] == os.path.relpath(CONFIG, REPO)
    assert sorted(entry["reduced"]) == sorted(published["reduced"])
    assert entry["source"] == published["source"]
    cell = toyroot.named(Manifest(bench_roots[where]).data["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "longdoc-steady", 1)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalog_row_is_kept_key_for_key(published):
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"
        )
    assert published["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in published["reduced"]:
            assert published[key] != value
            # a cut of the published pattern, not another pattern: the
            # published layers 1 and 4-7
            if key == "layer_types":
                assert published[key] == value[1:2] + value[4:8]
        else:
            assert published[key] == value, key


def test_the_weight_tree_has_the_parameters_the_issue_counts(afmoe, published):
    specs = afmoe.weight_specs(published)
    count = sum(int(np.prod(shape)) for _p, shape, *_rest in specs)
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert attention == 27_262_976
    expert = 3 * 2048 * 1024
    matrices = (
        5 * attention + 3 * 2048 * 6144
        + 4 * (129 * expert + 2048 * 128) + 2 * 200192 * 2048
    )
    assert matrices == 4_241_489_920        # ISSUE 44: 8.48 GB in bf16
    norms = 2048 * (1 + 5 * 2 + 5 * 2) + 5 * 2 * 128
    assert count == matrices + norms + 4 * 128
    by_path = {"/".join(p): (shape, dtype) for p, shape, _k, _s, dtype in specs}
    assert by_path["layers/moe/w_gate"] == ((4, 128, 2048, 1024), "served")
    assert by_path["layers/moe/shared_up"] == ((4, 2048, 1024), "served")
    assert by_path["layers/moe/expert_bias"] == ((4, 128), "float32")
    assert by_path["layers/sliding/wq"] == ((4, 2048, 4096), "served")
    assert by_path["layers/attention/wg"] == ((1, 2048, 4096), "served")
    assert by_path["layers/sliding/q_norm"] == ((4, 128), "served")
    assert by_path["lm_head"] == ((2048, 200192), "served")


def test_the_cells_caches_are_the_sizes_the_issue_counts(published):
    """24 rows of 32,768 positions: the full layer's history pool and
    the four window layers' rings, as the program's own geometry has
    them."""
    from dcos_commons_tpu.serve.paging import paged_config_from_env

    with open(os.path.join(
        REPO, "perfbench", "traffic", "longdoc-steady.json"
    )) as f:
        sizes = {k: str(v) for k, v in json.load(f)["sizing_env"].items()}
    paged = paged_config_from_env(dict(sizes, MODEL_CONFIG=CONFIG))
    assert paged.layout.ring_pages == 160 == (2048 + 512) // 16
    assert paged.pages_per_row == 160 + 2048
    assert paged.pages == 49152 == 24 * 2048
    entry = 16 * 4 * 128 * 2 * 2               # a page's K and V, bf16
    assert paged.arena_pages * entry == pytest.approx(1.61e9, rel=0.01)
    assert 4 * paged.window_arena_pages * entry == pytest.approx(
        0.50e9, rel=0.01)
    assert paged.prefix_cache is False


def test_needs_of_one_decode_step_by_hand(afmoe, published):
    """5 live rows of 10,000 positions each: under even routing they
    touch some 35 of 128 experts a layer; the full layer reads every
    position, a window layer 2,048 a row."""
    needs = afmoe.needs
    touched = needs.experts_touched(published, 5)
    assert touched == pytest.approx(128 * (1 - (15 / 16) ** 5))
    assert 35 < touched < 36
    got = needs.decode_tick(published, 5, 50000)
    expert = 3 * 2048 * 1024
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    outside = (
        5 * attention + 3 * 2048 * 6144
        + 4 * (2048 * 128 + 128 + expert) + 200192 * 2048
    )
    assert got["expert_bytes"] == pytest.approx(4 * touched * expert * 2)
    assert got["weight_bytes"] == outside * 2 + got["expert_bytes"]
    entry = 2 * 4 * 128 * 2
    assert got["kv_bytes"] == (50005 + 4 * (5 * 2048 + 5)) * entry
    acts = 5 * 5 * 2048 * 2 * 2
    assert got["bytes"] == got["weight_bytes"] + got["kv_bytes"] + acts
    assert got["flops"] == (
        2 * 5 * (outside - 4 * 128 + 4 * 8 * expert)
        + 4 * 32 * 128 * (50000 + 4 * 5 * 2048)
    )
    # the head is about a quarter of such a step's bytes
    assert 0.23 < 200192 * 2048 * 2 / got["bytes"] < 0.28
    # a row inside its first window reads what it has, no more
    short = needs.decode_tick(published, 5, 5000)
    assert short["kv_bytes"] == (5005 + 4 * (5000 + 5)) * entry


def test_needs_of_one_prefill_chunk_by_hand(afmoe, published):
    needs = afmoe.needs
    got = needs.prefill_chunk(published, 512, 4096)
    assert needs.experts_touched(published, 512) == pytest.approx(128)
    entry = 2 * 4 * 128 * 2
    assert got["kv_bytes"] == (4608 + 4 * (2048 + 512)) * entry
    attended = 512 * (4096 + 256)
    windowed = 512 * 2048
    base = needs.call_needs(published, 512, 0, 0, 0, 0, 128.0)
    assert got["flops"] - base["flops"] == 4 * 32 * 128 * (
        attended + 4 * windowed
    )


def test_needs_of_the_two_kernels_by_hand(afmoe, published):
    got = afmoe.needs.moe_grouped_matmul(published, 40, 30)
    assert got["bytes"] == (
        30 * 3 * 2048 * 1024 + 40 * 3 * (2048 + 1024)
    ) * 2
    assert got["flops"] == 2 * 3 * 2048 * 1024 * 40
    window = afmoe.needs.window_decode_attention(published, 5, 5 * 2048)
    assert window["bytes"] == (
        5 * 2048 * 2 * 4 * 128 + 5 * 2 * 32 * 128
    ) * 2
    assert window["flops"] == 4 * 5 * 2048 * 32 * 128


def _run(samples, **extra):
    return dict({
        "window": [0.0, 100.0], "trace_window": [10.0, 70.0],
        "stats_samples": samples, "mix": {"trace_s": 4},
        "config_file": CONFIG,
    }, **extra)


def _sample(t, calls=None, rows=None, entries=None, **gauges):
    loop = {}
    if calls is not None:
        loop.update(decode_calls=calls, decode_rows_sum=rows)
    if entries is not None:
        loop["decode_window_entries_sum"] = entries
    return dict({"_t": t, "loop": loop}, **gauges)


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_the_cache_reader_on_a_recorded_sample(bench_roots, where):
    read = Manifest(bench_roots[where]).reader(
        "per_layer", "kv_window_entries_per_context_token.chat"
    )
    samples = [
        _sample(20.0, kv_window_live_tokens=4096, context_live_tokens=16384),
        _sample(21.0, kv_window_live_tokens=6144, context_live_tokens=12288),
        _sample(22.0, context_live_tokens=0, kv_window_live_tokens=0),
        _sample(200.0, kv_window_live_tokens=1, context_live_tokens=1),
    ]
    assert read(_run(samples)) == pytest.approx((0.25 + 0.5) / 2)
    # a program from before window layers reports no such gauge, and a
    # model without them reads 0 there: nothing, not a number, not a raise
    assert read(_run([_sample(20.0, context_live_tokens=5)])) is None
    assert read(_run([_sample(
        20.0, context_live_tokens=5, kv_window_live_tokens=0)])) is None


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_the_kernel_reader_on_a_recorded_sample(
        bench_roots, where, afmoe, published):
    read = Manifest(bench_roots[where]).reader(
        "per_layer", "window_decode_attention_roofline.chat"
    )
    peaks = {"hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14}
    with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
        peaks = next(iter(json.load(f).values()))
    trace = {
        "programs": {"jit__decode": {"count": 100, "median_ms": 9.0}},
        "breakdown": {"device_ops": [
            ["jit__decode:paged_decode_attention_window", 0.010],
            ["jit__decode:paged_decode_attention_window.1", 0.030],
            ["jit__decode:paged_decode_attention", 0.2],
            ["jit__prefill:fusion.7", 0.5],
        ]},
    }
    samples = [
        _sample(10.5, 1000, 5000, 5000 * 2048),
        # the recorded span ends a second after trace_s
        _sample(14.5, 1100, 5500, 5500 * 2048),
        _sample(60.0, 4000, 90000, 90000 * 2048),
    ]
    stats = {"model": {
        "layer_plan": [1, 1, 3, 1],
        "layer_types": ["sliding"] * 4 + ["attention"],
    }}
    run = _run(samples, trace=trace, peaks=peaks, model=published,
               final_stats=stats)
    got = read(run)
    # 5 rows of 2,048 entries a call; 40 ms over 100 programs x 4 layers
    from perfbench.harness import roofline

    least, _ = roofline.least_seconds(
        afmoe.needs.window_decode_attention(published, 5.0, 5.0 * 2048),
        peaks,
    )
    assert got == pytest.approx(100.0 * least / (0.040 / 400))
    assert 0 < got < 100
    # the trace lists ten operations: where the leading layer's is not
    # among them, the one listed is the scanned period's and stands for
    # its three layers, not for all four
    period_alone = dict(trace, breakdown={"device_ops": [
        ["jit__decode:paged_decode_attention_window.1", 0.030]]})
    assert read(dict(run, trace=period_alone)) == pytest.approx(
        100.0 * least / (0.030 / 300))
    # a program that states no plan: nothing
    assert read(dict(run, final_stats={"model": {}})) is None
    # nothing to read: no such operation in the trace, no such counter
    # in the program, no trace at all
    none_listed = dict(trace, breakdown={"device_ops": [
        ["jit__decode:paged_decode_attention", 0.2]]})
    assert read(dict(run, trace=none_listed)) is None
    bare = [_sample(10.5, 1000, 5000), _sample(14.5, 1100, 5500)]
    assert read(dict(run, stats_samples=bare)) is None
    assert read(dict(run, trace=None)) is None


def test_the_new_cell_reports_what_the_issue_lists(bench_roots):
    bench = Manifest(bench_roots["repo"])
    per_layer = {m["name"] for m in bench.metrics("per_layer", CELL)}
    assert {
        "kv_window_entries_per_context_token.chat",
        "window_decode_attention_roofline.chat", "decode_tick_ms.chat",
        "prefill_chunk_ms.chat", "prefill_chunk_roofline.chat",
        "moe_experts_touched_per_layer.chat", "deploy_plan_s",
        "worker_warm_s", "engine_tick_period_ms.chat",
    } <= per_layer
    # EVA's, and the two that read nothing here (needs.py says why)
    assert not per_layer & {
        "eva_decode_attention_roofline.chat",
        "kv_entries_per_context_token.chat", "decode_step_roofline.chat",
        "engine_chunk_rider_share.chat",
    }
    assert {m["name"] for m in bench.metrics("end_to_end", CELL)} == {
        "norm_lat_p50_s", "setup_s"}
    params = bench.cell_params(CELL)
    assert params["rate_rps"] <= 0.7 * params["knee_rps"] + 1e-9
    # every limit of the comparison has its reason beside it
    assert set(params["correct_limits"]) == set(params["limits_why"])
    mix = bench.traffic("longdoc-steady")
    assert mix["sizing_env"] == {
        "MAX_LEN": 32768, "MAX_NEW_TOKENS": 1024, "SERVE_SLOTS": 24,
        "SERVE_BATCH": 1, "KV_PAGES": 49152, "PREFILL_CHUNK_TOKENS": 512,
        "KV_PAGE_TOKENS": 16,
    }
    assert (mix["pairing_seed"], mix["order_seed"]) == (20260928, 20261002)
    assert mix["prompt_tokens"] == {
        "kind": "lognormal", "median": 8192, "sigma": 0.6, "min": 4096,
        "max": 30720,
    }


@pytest.fixture(scope="module")
def toy_afmoe_root(tmp_path_factory, published):
    """A toy root with a cell of this family added by files alone: the
    toy configuration, a mix whose prompts pass the toy window, a cell."""
    root = toyroot.build(str(tmp_path_factory.mktemp("afmoe_root")))
    bench = os.path.join(root, "perfbench")
    with open(os.path.join(bench, "configs", "toy-afmoe.json"), "w") as f:
        json.dump(toy_of(published), f)
    mix = dict(
        toyroot.TOY_OPEN,
        prompt_tokens={"kind": "lognormal", "median": 50, "sigma": 0.5,
                       "min": 20, "max": 100},
        # long answers at a high rate: a toy step takes 2 ms, and the
        # gauges are sampled once a second
        output_tokens={"kind": "lognormal", "median": 32, "sigma": 0.3,
                       "min": 16, "max": 48},
        sizing_env={k: int(v) for k, v in TOY_SIZES.items()},
    )
    with open(os.path.join(bench, "traffic", "toy-long.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "cells", "toy-afmoe.long.json"), "w") as f:
        json.dump({"rate_rps": 24.0, "correct_limits": TOY_LIMITS}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "toy-afmoe", "source": "tests", "reduced": [],
        "file": "perfbench/configs/toy-afmoe.json", "why": "toy",
    })
    manifest["workloads"].append({
        "name": "toy-afmoe.long", "config": "toy-afmoe",
        "traffic": "toy-long", "chips": 1, "why": "toy",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("toy-afmoe.long")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_a_rehearsal_of_a_toy_afmoe_cell_ends_correct(toy_afmoe_root):
    from test_bench_harness_run import run_cell

    proc = run_cell(
        toy_afmoe_root, "--rehearse-cpu", cell="toy-afmoe.long", trace="1"
    )
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-3000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 120
    ratio = result["metrics"]["kv_window_entries_per_context_token.chat"]
    assert 0 < ratio["value"] <= 1.0
    assert "moe_experts_touched_per_layer.chat" in result["metrics"]
    # a device metric is never written from a CPU run
    assert "window_decode_attention_roofline.chat" not in result["metrics"]
