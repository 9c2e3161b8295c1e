"""The family ``lfm2_moe`` of the benchmark (perfbench/families/lfm2_moe/)
and its cell ``lfm2-24b.chat``: the plain reference against the program at
toy size through the family's four hooks, the reference's two copies, the
int8 control, ``needs.py`` by hand, the published widths by their own key
names, and the two readers this family brought on a recorded sample."""

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

CONFIG = os.path.join(REPO, "perfbench", "configs", "lfm2-24b-a2b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def lfm2():
    return toyroot.family("lfm2_moe")


@pytest.fixture(scope="module")
def published():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy(published):
    """The published file at toy widths: the same pattern, the same
    routing, every key the family reads."""
    return dict(
        published, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=8,
        num_experts_per_tok=2, vocab_size=128,
    )


TOY_LIMITS = {"max_gap": 1e-3, "mean_gap": 1e-4, "mismatch_share": 0.02}


def test_the_two_copies_of_the_reference_are_one_file(lfm2):
    with open(os.path.join(lfm2.directory, "reference.py")) as f:
        benchmark = f.read()
    with open(os.path.join(
        REPO, "dcos_commons_tpu", "models", "reference", "lfm2_moe.py"
    )) as f:
        program_side = f.read()
    assert benchmark == program_side
    assert "dcos_commons_tpu" not in benchmark.split('"""', 2)[2]


@pytest.fixture(scope="module")
def served(lfm2, toy, tmp_path_factory):
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

    path = str(tmp_path_factory.mktemp("lfm2") / "toy-lfm2.json")
    with open(path, "w") as f:
        json.dump(toy, f)
    env = {k.replace("TASKCFG_ALL_", ""): v
           for k, v in lfm2.program_env(toy, path).items()}
    assert env["MODEL_CONFIG"] == path
    assert (env["D_MODEL"], env["N_EXPERTS"], env["D_FF"]) == ("64", "8", "96")
    env.update(MAX_LEN="160", MAX_NEW_TOKENS="48", SERVE_SLOTS="3",
               KV_PAGES="80", PREFILL_CHUNK_TOKENS="8", KV_PAGE_TOKENS="4")
    config = config_from_env(env, dtype=jnp.float32, remat=False)
    specs = lfm2.weight_specs(toy)
    theirs = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    assert tree_differences(specs, config.dtype, theirs) == []
    weights = make_weights(specs, 2**31 + 5, jnp.float32)
    paged = paged_config_from_env(env)
    assert paged.prefix_cache is False      # rows keep state outside pages
    pool = PagedPoolModel(
        config, weights, paged.slots, paged.max_len, paged.page_tokens,
        paged.pages, paged.chunk_tokens,
    )
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
        for plen, new in ((20, 10), (44, 40), (75, 30)):
            prompt = rng.integers(0, 128, plen).tolist()
            requests.append({
                "prompt": prompt, "served": engine.submit([prompt], new)[0],
            })
    finally:
        engine.stop()
    return toy, weights, requests


def test_the_reference_agrees_with_the_program_as_correct_compares(
    lfm2, served
):
    model, weights, requests = served
    correct, compared, positions, steady = check.compare(
        lfm2.reference, model, weights, requests, TOY_LIMITS
    )
    assert correct, compared
    # the margin is the mixture's: finite, and over 0 at every position
    assert positions == steady == 80


def test_the_int8_control_fails_the_same_limits(lfm2, served):
    import jax.numpy as jnp

    model, weights, requests = served
    gaps = []
    for r in requests:
        exact, margin = check.served_logits(
            lfm2.reference, model, weights, r["prompt"], r["served"]
        )
        assert np.isfinite(margin).all() and (margin >= 0).all()
        lower, _ = check.served_logits(
            lfm2.reference, model, weights, r["prompt"], r["served"],
            lower="int8",
        )
        gaps.append(check.chosen_gaps(exact, np.asarray(jnp.argmax(lower, -1))))
    gaps = np.concatenate(gaps)
    correct, compared = check.judge(
        gaps, np.ones(len(gaps), bool), TOY_LIMITS
    )
    assert not correct, compared


def test_a_program_without_the_pattern_is_refused_by_its_parameter_tree(
    lfm2, toy
):
    """What the parent of this family's PR builds from the same env: a
    grouped-query mixture of one stack.  The worker entry's comparison
    stops it before anything is built."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, init_params
    from perfbench.harness.weights import tree_differences

    old = TransformerConfig(
        vocab=128, d_model=64, n_layers=9, n_heads=4, n_kv_heads=2, d_ff=96,
        n_experts=8, moe_top_k=2, dtype=jnp.float32, remat=False,
    )
    theirs = jax.eval_shape(lambda: init_params(old, jax.random.key(0)))
    differences = tree_differences(
        lfm2.weight_specs(toy), jnp.float32, theirs
    )
    assert any("layers/conv/conv_in" in d for d in differences)
    assert any("layers/moe/expert_bias" in d for d in differences)


def test_program_env_refuses_what_the_program_cannot_build(lfm2, toy):
    with pytest.raises(ValueError, match="lfm2_moe"):
        lfm2.program_env(dict(toy, model_type="mixtral"), CONFIG)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.program_env(dict(toy, num_hidden_layers=8), CONFIG)
    with pytest.raises(ValueError, match="bias"):
        lfm2.program_env(dict(toy, conv_bias=True), CONFIG)


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_lfm2_keeps_its_published_widths(published, bench_roots, where):
    """Every width under its published key; what was cut is depth."""
    assert published["hidden_size"] == 2048
    assert (published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"]) == (
        32, 8, 64)
    assert published["intermediate_size"] == 11776
    assert (published["num_experts"], published["moe_intermediate_size"],
            published["num_experts_per_tok"]) == (64, 1536, 4)
    assert published["vocab_size"] == 65536
    assert published["conv_L_cache"] == 3
    assert published["norm_eps"] == 1e-5
    assert published["rope_parameters"] == {
        "rope_theta": 1000000, "rope_type": "default",
    }
    assert (published["num_hidden_layers"], published["num_dense_layers"]) \
        == (9, 1)
    assert published["layer_types"] == (
        ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    )
    assert sorted(published["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers",
    ]
    entry = toyroot.named(
        Manifest(bench_roots[where]).data["configs"], "lfm2-24b-a2b")
    assert entry["file"] == os.path.relpath(CONFIG, REPO)
    assert sorted(entry["reduced"]) == sorted(published["reduced"])
    assert entry["source"] == published["source"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalog_row_is_kept_key_for_key(published):
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B"
        )
    assert published["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in published["reduced"]:
            assert published[key] != value
            # a cut of the published pattern, not another pattern
            if key == "layer_types":
                assert published[key] == value[1:10]
        else:
            assert published[key] == value, key


def test_the_weight_tree_has_the_parameters_the_issue_counts(lfm2, published):
    specs = lfm2.weight_specs(published)
    count = sum(int(np.prod(shape)) for _p, shape, *_rest in specs)
    experts = 8 * 64 * 3 * 2048 * 1536
    operators = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 7 * (
        4 * 2048 * 2048 + 2048 * 3
    )
    dense = 3 * 2048 * 11776
    routers = 8 * (2048 * 64 + 64)
    norms = 2048 * (1 + 2 + 7 + 1 + 8) + 2 * 2 * 64
    embedding = 65536 * 2048
    assert count == experts + operators + dense + routers + norms + embedding
    assert 5.17e9 < count < 5.19e9          # ISSUE 33: 5.18G, 10.36 GB
    by_path = {"/".join(p): (shape, dtype) for p, shape, _k, _s, dtype in specs}
    assert by_path["layers/moe/w_gate"] == ((8, 64, 2048, 1536), "served")
    assert by_path["layers/moe/expert_bias"] == ((8, 64), "float32")
    assert by_path["layers/conv/conv_w"] == ((7, 2048, 3), "served")
    assert by_path["layers/attention/q_norm"] == ((2, 64), "served")
    assert "lm_head" not in by_path


def test_needs_of_one_decode_step_by_hand(lfm2, published):
    """10 live rows of 500 positions each: under even routing they
    touch some 30 experts a layer, and the tick reads those."""
    needs = lfm2.needs
    touched = needs.experts_touched(published, 10)
    assert touched == pytest.approx(64 * (1 - (15 / 16) ** 10))
    assert 30 < touched < 31
    got = needs.decode_tick(published, 10, 5000)
    expert = 3 * 2048 * 1536
    outside = (
        2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
        + 7 * (4 * 2048 * 2048 + 2048 * 3) + 3 * 2048 * 11776
        + 8 * (2048 * 64 + 64) + 65536 * 2048
    )
    assert got["expert_bytes"] == pytest.approx(8 * touched * expert * 2)
    assert got["weight_bytes"] == outside * 2 + got["expert_bytes"]
    assert got["kv_bytes"] == 2 * 5010 * 2 * 8 * 64 * 2
    state = 7 * 10 * 2 * 2048 * 2 * 2
    acts = 9 * 10 * 2048 * 2 * 2
    assert got["bytes"] == (
        got["weight_bytes"] + got["kv_bytes"] + state + acts
    )
    assert got["flops"] == (
        2 * 10 * (outside - 8 * 64 + 8 * 4 * expert)
        + 2 * 4 * 5000 * 32 * 64
    )
    # the experts are most of a step's bytes (ISSUE 33: 87%), and they
    # follow the rows: 59 of 64 at 40
    assert 0.85 < got["expert_bytes"] / got["bytes"] < 0.89
    more = needs.decode_tick(published, 40, 5000)
    assert more["expert_bytes"] / got["expert_bytes"] == pytest.approx(
        (1 - (15 / 16) ** 40) / (1 - (15 / 16) ** 10)
    )


def test_needs_of_one_prefill_chunk_by_hand(lfm2, published):
    needs = lfm2.needs
    got = needs.prefill_chunk(published, 64, 192)
    touched = needs.experts_touched(published, 64)
    assert 62.9 < touched < 64
    assert got["expert_bytes"] == pytest.approx(
        8 * touched * 3 * 2048 * 1536 * 2
    )
    assert got["kv_bytes"] == 2 * 256 * 2 * 8 * 64 * 2
    # one row's conv state, in and out, a conv layer
    no_rows = needs.call_needs(published, 64, 256, 64 * 224, 0, touched)
    assert got["bytes"] - no_rows["bytes"] == 7 * 2 * 2048 * 2 * 2


def test_needs_of_the_grouped_matmul_by_hand(lfm2, published):
    got = lfm2.needs.moe_grouped_matmul(published, 40, 30)
    assert got["bytes"] == (
        30 * 3 * 2048 * 1536 + 40 * 3 * (2048 + 1536)
    ) * 2
    assert got["flops"] == 2 * 3 * 2048 * 1536 * 40


def _run(samples, **extra):
    return dict({
        "window": [0.0, 100.0], "trace_window": [10.0, 70.0],
        "stats_samples": samples, "mix": {"trace_s": 4},
        "config_file": CONFIG,
    }, **extra)


MODEL_STATS = {
    "n_layers": 9, "n_dense_layers": 1, "n_experts": 64,
    "layer_plan": [1, 4, 2, 0],
}


def _sample(t, calls, assignments, groups, chunks=None):
    loop = {"decode_calls": calls}
    if assignments is not None:
        loop.update(moe_assignments_sum=assignments,
                    moe_groups_touched_sum=groups)
    if chunks is not None:
        # 30 chunks a second, 240 assignments and 60 groups a chunk an
        # expert layer
        loop.update(moe_prefill_chunks_counted=chunks,
                    moe_prefill_assignments_sum=chunks * 8 * 240,
                    moe_prefill_groups_touched_sum=chunks * 8 * 60)
    return {"_t": t, "t": 1000.0 + t, "loop": loop}


def test_the_two_new_readers_on_a_recorded_sample(published):
    bench = Manifest(REPO)
    touched = bench.reader("per_layer", "moe_experts_touched_per_layer.chat")
    share = bench.reader("per_layer", "moe_grouped_matmul_roofline.chat")
    # 100 calls a second, 10 live rows: 40 assignments and 30 groups a
    # call an expert layer
    samples = [
        _sample(t, 100 * t, 100 * t * 8 * 40, 100 * t * 8 * 30)
        for t in range(0, 100, 1)
    ]
    run = _run(samples, final_stats={"model": MODEL_STATS}, model=published,
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert touched(run) == pytest.approx(30.0)
    # six of the period's twelve products are among the trace's ten
    # longest operations, each 0.3 ms a call, 400 programs, 2 trips
    run["trace"] = {
        "programs": {"jit__decode": {"count": 400, "median_ms": 8.0}},
        "breakdown": {"device_ops": [
            [f"jit__decode:gmm.{i} bf16[256,1536]", 400 * 2 * 0.3e-3]
            for i in range(6)
        ] + [["jit__prefill:gmm.3 bf16[256,1536]", 0.5],
             ["jit__decode:fusion.9 f32[64,65536]", 0.1]]},
    }
    needs = Manifest(REPO).family("lfm2-24b-a2b").needs.moe_grouped_matmul(
        published, 40, 30
    )
    want = 100.0 * (needs["bytes"] / 819e9) / (3 * 0.3e-3)
    # the chunks' product is listed too, but this program counts no
    # chunks: the decode steps' products are read alone
    assert share(run) == pytest.approx(want)
    assert 60 < share(run) < 100
    # with the chunks' counters every listed product counts, each at its
    # own program's counts: needed seconds over seconds taken
    counted = dict(run, stats_samples=[
        _sample(t, 100 * t, 100 * t * 8 * 40, 100 * t * 8 * 30, 30 * t)
        for t in range(100)
    ])
    counted["trace"] = dict(run["trace"], programs={
        "jit__decode": {"count": 400}, "jit__prefill": {"count": 120},
    })
    of_chunk = Manifest(REPO).family("lfm2-24b-a2b").needs.moe_grouped_matmul(
        published, 240, 60
    )
    needed = (
        6 * 400 * 2 * needs["bytes"] + 120 * 2 * of_chunk["bytes"]
    ) / 3 / 819e9
    assert share(counted) == pytest.approx(
        100.0 * needed / (6 * 400 * 2 * 0.3e-3 + 0.5)
    )
    # the chunks' products alone among the ten (seen at 4.2/s)
    chunks_only = dict(counted, trace=dict(counted["trace"], breakdown={
        "device_ops": [["jit__prefill:gmm.3 bf16[256,1536]", 0.5]],
    }))
    assert share(chunks_only) == pytest.approx(
        100.0 * (of_chunk["bytes"] / 3 / 819e9) / (0.5 / (120 * 2))
    )
    assert share(dict(run, trace=dict(run["trace"], breakdown={
        "device_ops": [["jit__prefill:gmm.3 bf16[256,1536]", 0.5]],
    }))) is None
    # nothing where the counters, the plan or the operation are absent
    bare = [_sample(t, 100 * t, None, None) for t in range(100)]
    assert touched(dict(run, stats_samples=bare)) is None
    assert share(dict(run, stats_samples=bare)) is None
    assert touched(dict(run, final_stats={"model": {"n_layers": 9}})) is None
    assert share(dict(run, final_stats={"model": {
        "n_layers": 9, "n_experts": 64, "n_dense_layers": 1,
    }})) is None
    no_kernel = dict(run, trace=dict(run["trace"], breakdown={
        "device_ops": [["jit__decode:fusion.9 f32[64,65536]", 0.1]],
    }))
    assert share(no_kernel) is None
    assert share(dict(run, trace=None)) is None
    # an expert layer outside the scanned periods: its products run
    # once a program and cannot be told apart
    outside = dict(MODEL_STATS, layer_plan=[2, 4, 1, 0])
    assert share(dict(run, final_stats={"model": outside})) is None


OWN_METRICS = {
    "moe_experts_touched_per_layer.chat", "moe_grouped_matmul_roofline.chat",
}


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_the_cell_and_its_entries(bench_roots, where):
    """Every entry is found by its NAME: a later PR appends a
    configuration, a cell and metrics of its own (and its cell's name
    to the ``workloads`` of the metrics it reports), and nothing here
    may depend on what stands last or on how many there are."""
    bench = Manifest(bench_roots[where])
    cell = bench.cell("lfm2-24b.chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b", "chat-steady", 1,
    )
    params = bench.cell_params("lfm2-24b.chat")
    # ISSUE 33's fallback, 0.6 of the swept knee; the file says why
    assert params["rate_rps"] == pytest.approx(0.6 * params["knee_rps"])
    assert "4.2/s" in params["rate_why"]
    assert set(params["correct_limits"]) <= {
        "max_gap", "mean_gap", "mismatch_share", "wide_gap_share",
        "steady_max_gap", "steady_mean_gap", "steady_mismatch_share",
        "steady_wide_gap_share",
    }

    def moving_latency(name):
        return {
            m["name"] for m in bench.metrics("per_layer", name)
            if m["moves"] == "norm_lat_p50_s"
        }

    names, mixtral = (
        moving_latency("lfm2-24b.chat"), moving_latency("mixtral8x7b.chat")
    )
    # of the metrics that move the latency, all that Mixtral's cell
    # reports but one (ISSUE 34 appended the cell to
    # engine_decode_ahead_share.chat): decode_step_roofline.chat sets a
    # gauge's rows beside the traced steps, and here the bytes of a
    # step follow its rows (families/lfm2_moe/needs.py); plus its own two
    assert mixtral - names == {"decode_step_roofline.chat"}
    assert names - mixtral == OWN_METRICS
    # the two are this cell's alone among the cells of today: a later
    # cell of the family may join the list, no accepted cell may
    for name in OWN_METRICS:
        metric = toyroot.named(bench.data["per_layer"], name)
        assert set(metric["workloads"]) & set(toyroot.CELLS) == {
            "lfm2-24b.chat"}
        assert metric["moves"] == "norm_lat_p50_s"
    assert [m["name"] for m in bench.metrics("end_to_end", "lfm2-24b.chat")] \
        == ["norm_lat_p50_s", "setup_s"]
    # what moves the set-up is reported here as in Mixtral's cell
    assert {
        m["name"] for m in bench.metrics("per_layer", "lfm2-24b.chat")
    } - names == {
        m["name"] for m in bench.metrics("per_layer", "mixtral8x7b.chat")
    } - mixtral
