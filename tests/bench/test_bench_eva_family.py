"""The second family, ``perfbench/families/eva_decoder/``, and the
configuration, mix, cell and readers that came with it: its reference
against the program at toy size (as the cell's ``correct`` compares
them), the int8 control failing the same limit, its needs by hand, the
published widths of ``evabyte-6.5b`` by its own names, and the two new
readers on a recorded ``/stats`` sample."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toyroot  # noqa: E402
from toyroot import bench_roots  # noqa: E402,F401
from toyroot import family  # noqa: E402

from perfbench.harness import check  # noqa: E402
from perfbench.harness.manifest import Manifest  # noqa: E402

CONFIG = os.path.join(REPO, "perfbench", "configs", "evabyte-6.5b.json")
# the family at toy size: window 32, chunk and page 4
TOY = {
    "family": "eva_decoder", "attention_class": "eva", "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 320,
    "rope_theta": 100000, "rms_norm_eps": 1e-05,
    "norm_add_unit_offset": True, "tie_word_embeddings": False,
    "num_pred_heads": 8, "window_size": 32, "chunk_size": 4,
    "init_std": 0.5,
}
# float32 on both sides: served tokens lie under the reference's best
# by rounding alone (largest seen 1e-5); the int8 control lies 1e-2 and
# more under it
TOY_LIMITS = {"max_gap": 1e-3, "mean_gap": 1e-4, "mismatch_share": 0.02}


@pytest.fixture(scope="module")
def eva():
    return family("eva_decoder")


@pytest.fixture(scope="module")
def published():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_two_copies_of_the_reference_are_one_file(eva):
    with open(os.path.join(eva.directory, "reference.py")) as f:
        benchmark = f.read()
    with open(os.path.join(
        REPO, "dcos_commons_tpu", "models", "reference", "eva.py"
    )) as f:
        program_side = f.read()
    assert benchmark == program_side
    assert "dcos_commons_tpu" not in benchmark.split('"""', 2)[2]


@pytest.fixture(scope="module")
def served(eva, tmp_path_factory):
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

    path = str(tmp_path_factory.mktemp("eva") / "toy-eva.json")
    with open(path, "w") as f:
        json.dump(TOY, f)
    env = {k.replace("TASKCFG_ALL_", ""): v
           for k, v in eva.program_env(TOY, path).items()}
    assert env["MODEL_CONFIG"] == path and env["D_MODEL"] == "64"
    env.update(MAX_LEN="160", MAX_NEW_TOKENS="48", SERVE_SLOTS="3",
               KV_PAGES="80", PREFILL_CHUNK_TOKENS="8", KV_PAGE_TOKENS="4")
    config = config_from_env(env, dtype=jnp.float32, remat=False)
    specs = eva.weight_specs(TOY)
    theirs = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    assert tree_differences(specs, config.dtype, theirs) == []
    weights = make_weights(specs, 2**31 + 5, jnp.float32)
    paged = paged_config_from_env(env)
    pool = PagedPoolModel(
        config, weights, paged.slots, paged.max_len, paged.page_tokens,
        paged.pages, paged.chunk_tokens,
    )
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, paged.slots, paged.max_len,
        paged.max_len - 48, page_tokens=paged.page_tokens,
        pages=paged.pages, chunk_tokens=paged.chunk_tokens,
        prefix_cache=paged.prefix_cache, layout=pool.layout,
    )
    assert pool.layout == paged.layout
    rng = np.random.default_rng(5)
    try:
        requests = []
        for plen, new in ((20, 10), (44, 40), (75, 30)):
            prompt = rng.integers(0, 320, plen).tolist()
            requests.append({
                "prompt": prompt, "served": engine.submit([prompt], new)[0],
            })
    finally:
        engine.stop()
    return TOY, weights, requests


def test_the_reference_agrees_with_the_program_as_correct_compares(eva, served):
    model, weights, requests = served
    correct, compared, positions, steady = check.compare(
        eva.reference, model, weights, requests, TOY_LIMITS
    )
    assert correct, compared
    assert positions == steady == 80     # nothing is routed: all steady


def test_the_int8_control_fails_the_same_limits(eva, served):
    import jax.numpy as jnp

    model, weights, requests = served
    gaps = []
    for r in requests:
        exact, _ = check.served_logits(
            eva.reference, model, weights, r["prompt"], r["served"]
        )
        lower, _ = check.served_logits(
            eva.reference, model, weights, r["prompt"], r["served"],
            lower="int8",
        )
        gaps.append(check.chosen_gaps(exact, np.asarray(jnp.argmax(lower, -1))))
    gaps = np.concatenate(gaps)
    correct, compared = check.judge(
        gaps, np.ones(len(gaps), bool), TOY_LIMITS
    )
    assert not correct, compared


def test_a_program_without_the_family_is_refused_by_its_parameter_tree(eva):
    """What the parent commit does with this configuration: it builds a
    grouped-query decoder of these widths, and the worker entry's
    comparison of trees names what is missing."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, init_params
    from perfbench.harness.weights import tree_differences

    old = TransformerConfig(
        vocab=320, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=96,
        dtype=jnp.float32, remat=False,
    )
    theirs = jax.eval_shape(lambda: init_params(old, jax.random.key(0)))
    differences = tree_differences(eva.weight_specs(TOY), jnp.float32, theirs)
    assert sorted(differences) == [
        "the program has no leaf layers/eva_mu",
        "the program has no leaf layers/eva_phi",
        "the program has no leaf lm_head",
    ]


def test_program_env_refuses_what_the_program_cannot_build(eva):
    with pytest.raises(ValueError):
        eva.program_env(dict(TOY, attention_class="gqa"), CONFIG)
    with pytest.raises(ValueError):
        eva.program_env(dict(TOY, window_size=24), CONFIG)


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_evabyte_keeps_its_published_widths(published, bench_roots, where):
    """By its own names: every width, all heads, the whole vocabulary;
    depth alone is cut."""
    want = {
        "hidden_size": 4096, "intermediate_size": 11008,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "vocab_size": 320, "window_size": 2048, "chunk_size": 16,
        "num_pred_heads": 8, "rope_theta": 100000, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 32768, "attention_class": "eva",
        "norm_add_unit_offset": True, "tie_word_embeddings": False,
        "init_std": 0.01275, "hidden_act": "silu",
    }
    assert {k: published[k] for k in want} == want
    assert published["num_hidden_layers"] == 8
    assert published["published"] == {"num_hidden_layers": 32}
    assert published["family"] == "eva_decoder"
    assert {"pooling_logit", "next_byte_head", "eva_phi_eva_mu"} <= set(
        published["assumed"]
    )
    assert "four pipeline stages of 8" in published["deployment"]
    entry = toyroot.named(
        Manifest(bench_roots[where]).data["configs"], "evabyte-6.5b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == (
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    )


def test_the_catalog_row_is_kept_key_for_key(published):
    """Every key of the catalog's ``config`` for EvaByte stands in the
    file with the catalog's value, but the depth."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    differing = {
        k for k, v in row["config"].items() if published.get(k, "absent") != v
    }
    assert differing == {"num_hidden_layers"}


def test_the_weight_tree_has_the_parameters_the_issue_counts(eva, published):
    counts = {
        "/".join(path): int(np.prod(shape))
        for path, shape, _kind, _scale, _dtype in eva.weight_specs(published)
    }
    layers = sum(v for k, v in counts.items() if k.startswith("layers/"))
    # 202.4M a layer and 8,192 more for phi and mu, norms 8,192
    assert layers == 8 * (4 * 4096 ** 2 + 3 * 4096 * 11008 + 4 * 4096)
    assert counts["embed"] == 320 * 4096
    assert counts["lm_head"] == 4096 * 8 * 320
    assert 1.630e9 < sum(counts.values()) < 1.633e9


def test_needs_of_one_decode_tick_by_hand(eva, published):
    """13 rows on 30,000 entries at the published widths, 8 layers."""
    needs = eva.needs.decode_tick(published, 13, 30000)
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128
    weights = (8 * layer + 4096 * 320 + 13 * 4096) * 2
    cache = 8 * (30000 + 13) * 16384
    acts = 8 * 13 * 4096 * 2 * 2
    assert eva.needs.entry_bytes(published) == 16384
    assert needs["weight_bytes"] == weights
    assert needs["kv_bytes"] == cache
    assert needs["bytes"] == weights + cache + acts
    assert needs["flops"] == 8 * (
        2 * 13 * layer + 4 * 30000 * 32 * 128 + 6 * 13 * 32 * 128
    ) + 2 * 13 * 320 * 4096
    # the weights are read whatever the load; the cache grows with it
    assert 3.2e9 < weights < 3.3e9 and 3.9e9 < cache < 4.0e9


def test_needs_of_one_prefill_chunk_by_hand(eva, published):
    """A chunk of 512 behind 5,000 positions: 904 of its window and 256
    summaries of the two windows past."""
    assert eva.needs.entries_seen(published, 5000) == 904 + 2 * 128
    assert eva.needs.entries_seen(published, 2047) == 2047
    assert eva.needs.entries_seen(published, 2048) == 128
    needs = eva.needs.prefill_chunk(published, 512, 5000)
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128
    assert needs["kv_bytes"] == 8 * (1160 + 512 + 32) * 16384
    assert needs["flops"] == 8 * (
        2 * 512 * layer + 4 * 512 * (1160 + 256) * 32 * 128
        + 6 * 512 * 32 * 128
    ) + 2 * 512 * 320 * 4096
    # 1.7 TFLOP, as ISSUE 28 reckons it: compute bound on a v5e
    assert 1.6e12 < needs["flops"] < 1.8e12
    assert needs["flops"] / 197e12 > needs["bytes"] / 819e9


def _run(samples):
    return {"window": [100.0, 151.0], "trace_window": None,
            "stats_samples": samples}


RECORDED = [
    # one /stats sample a second, as the engine writes them
    {"_t": 99.0, "t": 9.0, "kv_live_tokens": 1, "context_live_tokens": 1,
     "loop": {"window_rollovers": 100, "decode_calls": 5}},
    {"_t": 101.0, "t": 11.0, "kv_live_tokens": 3000,
     "context_live_tokens": 20000,
     "loop": {"window_rollovers": 110, "decode_calls": 50}},
    {"_t": 120.0, "t": 30.0, "kv_live_tokens": 0, "context_live_tokens": 0,
     "loop": {"window_rollovers": 180, "decode_calls": 700}},
    {"_t": 150.0, "t": 60.0, "kv_live_tokens": 5000,
     "context_live_tokens": 20000,
     "loop": {"window_rollovers": 257, "decode_calls": 1700}},
    {"_t": 152.0, "t": 62.0, "kv_live_tokens": 9, "context_live_tokens": 9,
     "loop": {"window_rollovers": 999, "decode_calls": 1800}},
]


def test_the_two_new_readers_on_a_recorded_sample():
    bench = Manifest(REPO)
    share = bench.reader("per_layer", "kv_entries_per_context_token.chat")
    rollovers = bench.reader("per_layer", "engine_window_rollovers_per_s.chat")
    run = _run(RECORDED)
    # the samples inside the window that hold any context: 0.15, 0.25
    assert share(run) == pytest.approx(0.2)
    assert rollovers(run) == pytest.approx((257 - 110) / 49.0)
    # a program from before the counters: nothing, and no error
    old = _run([
        {"_t": 101.0, "t": 11.0, "kv_live_tokens": 3000,
         "loop": {"decode_calls": 50}},
        {"_t": 150.0, "t": 60.0, "kv_live_tokens": 5000,
         "loop": {"decode_calls": 1700}},
    ])
    assert share(old) is None and rollovers(old) is None
    assert share(_run([])) is None and rollovers(_run([])) is None


@pytest.mark.parametrize("where", toyroot.ROOTS)
def test_the_cell_and_its_entries(bench_roots, where):
    bench = Manifest(bench_roots[where])
    cell = bench.cell("evabyte.docqa")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b", "docqa-steady", 1
    )
    assert bench.family("evabyte-6.5b").name == "eva_decoder"
    mix = bench.traffic("docqa-steady")
    assert mix["sizing_env"] == {
        "MAX_LEN": 32768, "MAX_NEW_TOKENS": 1024, "SERVE_SLOTS": 24,
        "SERVE_BATCH": 1, "KV_PAGES": 4096, "PREFILL_CHUNK_TOKENS": 512,
        "KV_PAGE_TOKENS": 16,
    }
    assert mix["ramp_s"] == 30
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"kind": "lognormal", "median": 8192, "sigma": 0.6, "min": 4096,
         "max": 30720},
        {"kind": "lognormal", "median": 256, "sigma": 0.7, "min": 32,
         "max": 1024},
    )
    params = bench.cell_params("evabyte.docqa")
    assert params["rate_rps"] == pytest.approx(0.7 * params["knee_rps"], abs=0.06)
    reported = {m["name"] for m in bench.metrics("per_layer", "evabyte.docqa")}
    assert {"kv_entries_per_context_token.chat",
            "engine_window_rollovers_per_s.chat", "decode_step_roofline.chat",
            "prefill_chunk_roofline.chat"} <= reported
    # the two new ones are read in this cell alone
    assert "kv_entries_per_context_token.chat" not in {
        m["name"] for m in bench.metrics("per_layer", "mixtral8x7b.chat")
    }
    assert {m["name"] for m in bench.metrics("end_to_end", "evabyte.docqa")} \
        == {"norm_lat_p50_s", "setup_s"}


def test_the_mix_offers_what_the_issue_reckons():
    """About 66 requests in the 51 s window at the 1.3/s ISSUE 28
    reckoned (the sweep's rate is lower: 54 at 1.05/s), prompts of about
    ten thousand bytes, answers of about 330: every one inside MAX_LEN,
    every prompt more than two windows' worth of chunks."""
    from perfbench.harness.traffic import pairs

    bench = Manifest(REPO)
    mix = bench.traffic("docqa-steady")
    lengths = pairs(mix, 66)
    prompts = [p for p, _ in lengths]
    answers = [a for _, a in lengths]
    assert 9000 < np.mean(prompts) < 10500 and 300 < np.mean(answers) < 360
    assert min(prompts) >= 4096 and max(prompts) <= 30720
    assert all(p + a <= 32768 and a <= 1024 for p, a in lengths)


def test_needs_of_the_decode_kernel_by_hand(eva, published):
    needs = eva.needs.eva_decode_attention(published, 13, 30000)
    assert needs["bytes"] == 30013 * 16384 + 2 * 13 * 32 * 128 * 2
    assert needs["flops"] == 4 * 30013 * 32 * 128
    # bound by bytes: 0.49 GB a layer is 0.6 ms at 819 GB/s
    assert needs["bytes"] / 819e9 > needs["flops"] / 197e12


def test_the_kernels_roofline_reader_on_a_recorded_trace(eva, published):
    read = Manifest(REPO).reader(
        "per_layer", "eva_decode_attention_roofline.chat"
    )
    run = {
        # the stamp ends when /trace/stop has returned, long after the
        # 4 s the profiler recorded: the sums are read over [110, 115]
        "window": [100.0, 151.0], "trace_window": [110.0, 140.8],
        "mix": {"trace_s": 4},
        "config_file": CONFIG, "model": published,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "stats_samples": [
            # the gauges also hold rows that no decode call reads (a
            # long prompt still prefilling): the reader takes the
            # engine's own sums over the span's decode calls
            {"_t": 109.0, "active_slots": 99, "kv_live_tokens": 99,
             "loop": {"decode_calls": 0, "decode_rows_sum": 0,
                      "decode_entries_sum": 0}},
            {"_t": 111.0, "active_slots": 12, "kv_live_tokens": 48000,
             "loop": {"decode_calls": 1000, "decode_rows_sum": 12000,
                      "decode_entries_sum": 28000000}},
            {"_t": 112.0, "active_slots": 14, "kv_live_tokens": 52000,
             "loop": {"decode_calls": 1050, "decode_rows_sum": 12600,
                      "decode_entries_sum": 29400000}},
            {"_t": 113.0, "active_slots": 14, "kv_live_tokens": 52000,
             "loop": {"decode_calls": 1100, "decode_rows_sum": 13300,
                      "decode_entries_sum": 31000000}},
            {"_t": 120.0, "active_slots": 99, "kv_live_tokens": 99,
             "loop": {"decode_calls": 1500, "decode_rows_sum": 99999,
                      "decode_entries_sum": 99999999}},
        ],
        "trace": {
            "programs": {"jit__decode": {"count": 100, "median_ms": 12.0}},
            "breakdown": {"device_ops": [
                ["jit__decode:eva_decode_attention.5 bf16[24,32,128]", 0.8],
                ["jit__prefill:fusion.1 bf16[512,4096]", 1.5],
            ]},
        },
    }
    # 800 calls took 0.8 s: 1 ms each; the span's 100 decode calls had
    # 13 rows on 30,000 entries each, which need
    # 0.4917 GB, 0.6004 ms at the peak
    least = (30013 * 16384 + 2 * 13 * 32 * 128 * 2) / 819e9
    assert read(run) == pytest.approx(100.0 * least / 1e-3)
    assert 55 < read(run) < 65
    # a program without the sums (the gauges are not read in their
    # place), without the kernel, or an untraced run: nothing
    bare = [{k: v for k, v in s.items() if k != "loop"}
            for s in run["stats_samples"]]
    assert read(dict(run, stats_samples=bare)) is None
    run["trace"]["breakdown"]["device_ops"].pop(0)
    assert read(run) is None
    assert read(dict(run, trace=None)) is None
