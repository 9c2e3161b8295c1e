"""The prefill chunk's width where the env states none
(serve/paging.py ``chosen_chunk_tokens``): chosen from the model the
same env describes, held to what the geometry serves, the same on the
scheduler's side and the worker's, and said in ``/stats``.
"""

import json
import os

import pytest

from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.serve.paging import (
    RowLayout,
    chosen_chunk_tokens,
    chunk_weights_from_env,
    paged_config_from_env,
)
from dcos_commons_tpu.specification.specs import SpecError
from dcos_commons_tpu.specification.yaml_spec import from_yaml_file
from dcos_commons_tpu.testing.chain_model import ChainModel, chain_oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "perfbench", "configs")
CHAT = {"MAX_LEN": "2048", "SERVE_SLOTS": "64", "KV_PAGES": "4096"}
DOCQA = {"MAX_LEN": "32768", "SERVE_SLOTS": "24", "KV_PAGES": "4096"}


def _size_names(config):
    """The size names a grouped-query configuration reaches its task
    under (perfbench/families/gqa_decoder/program_env.py)."""
    with open(os.path.join(CONFIGS, config), encoding="utf-8") as f:
        model = json.load(f)
    return {
        "D_MODEL": str(model["hidden_size"]),
        "N_LAYERS": str(model["num_hidden_layers"]),
        "N_HEADS": str(model["num_attention_heads"]),
        "N_KV_HEADS": str(model["num_key_value_heads"]),
        "D_FF": str(model["intermediate_size"]),
        "N_EXPERTS": str(model.get("num_local_experts", 0)),
    }


def _file(config):
    return {"MODEL_CONFIG": os.path.join(CONFIGS, config)}


def _toy_file(tmp_path, **keys):
    path = tmp_path / "model.json"
    path.write_text(json.dumps({
        "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "vocab_size": 64, **keys,
    }))
    return {"MODEL_CONFIG": str(path)}


# name -> (env, or a function of tmp_path that makes one; the width
# chosen; weights read over weights a token multiplies, to 0.1)
CHOICES = {
    # the three published configurations, as their cells size them
    "mixtral-8x7b": ({**_size_names("mixtral-8x7b-v0.1.json"), **CHAT},
                     512, 3.7),
    "lfm2-24b-a2b": ({**_file("lfm2-24b-a2b.json"), **CHAT}, 512, 9.8),
    "evabyte-6.5b": ({**_file("evabyte-6.5b.json"), **DOCQA}, 256, 1.0),
    # a dense grouped-query model (Mistral-7B's widths)
    "dense-gqa": ({"D_MODEL": "4096", "N_LAYERS": "32", "N_HEADS": "32",
                   "N_KV_HEADS": "8", "D_FF": "14336",
                   "MAX_LEN": "4096"}, 256, 1.0),
    # toy envs: the size names' defaults, short rows, odd pages
    "defaults": ({}, 256, 1.0),
    "short-rows": ({"MAX_LEN": "48", "D_MODEL": "32", "N_HEADS": "4"},
                   48, 1.0),
    "rows-under-a-page": ({"MAX_LEN": "10"}, 16, 1.0),
    "odd-pages": ({"MAX_LEN": "4096", "KV_PAGE_TOKENS": "24",
                   "N_EXPERTS": "8"}, 504, 3.4),
    "toy-mixture-short": ({"MAX_LEN": "64", "N_EXPERTS": "4",
                           "KV_PAGE_TOKENS": "4"}, 64, 1.8),
    "toy-window": (lambda tmp: {
        **_toy_file(tmp, attention_class="eva", window_size=32,
                    chunk_size=4),
        "MAX_LEN": "128", "KV_PAGE_TOKENS": "4",
    }, 32, 1.0),
    "toy-conv-mixture": (lambda tmp: {
        **_toy_file(tmp, layer_types=["conv", "full_attention"],
                    num_experts=8, num_experts_per_tok=2,
                    moe_intermediate_size=48, num_dense_layers=1),
        "MAX_LEN": "1024", "KV_PAGE_TOKENS": "4",
    }, 512, 2.1),
}


@pytest.mark.parametrize("name", sorted(CHOICES))
def test_the_width_chosen_from_the_model(name, tmp_path):
    env, width, ratio = CHOICES[name]
    env = env(tmp_path) if callable(env) else env
    paged = paged_config_from_env(env)
    assert paged.chunk_source == "model"
    assert paged.chunk_weights == chunk_weights_from_env(env)
    read, per_token = paged.chunk_weights
    assert read / per_token == pytest.approx(ratio, abs=0.05)
    assert paged.chunk_tokens == width
    # what the geometry allows, whatever was wanted
    layout, page = paged.layout, paged.page_tokens
    assert paged.chunk_tokens % page == 0
    assert paged.chunk_tokens <= max(512 - 512 % page, page)
    assert paged.chunk_tokens <= max(paged.max_len, page)
    if layout.window:
        assert paged.chunk_tokens % layout.chunk == 0
        assert paged.chunk_tokens <= layout.window
    # an engine takes it as it takes a stated width
    model = ChainModel(page_tokens=page)
    engine = PagedEngine(
        model.prefill_chunk, model.decode, 1, paged.max_len,
        paged.max_len - 1, page_tokens=page,
        pages=layout.table_len(paged.max_len),
        chunk_tokens=paged.chunk_tokens, prefix_cache=False,
        layout=layout,
    )
    engine.stop()


@pytest.mark.parametrize("config,kinds", [
    ("mixtral-8x7b-v0.1.json", None), ("lfm2-24b-a2b.json", "file"),
    ("evabyte-6.5b.json", "file"),
])
def test_the_weights_counted_are_the_tree_init_params_builds(config, kinds):
    """``read`` is every matrix of every layer of the tree the program
    builds from the same env (norms, biases and EVA's two vectors a
    head are not matrices a chunk multiplies by)."""
    import jax

    from dcos_commons_tpu.models import init_params
    from dcos_commons_tpu.models.transformer import config_from_env

    env = _file(config) if kinds else _size_names(config)
    shapes = jax.eval_shape(
        lambda: init_params(config_from_env(env), jax.random.key(0))
    )
    matrices = sum(
        leaf.size for path, leaf in
        jax.tree_util.tree_leaves_with_path(shapes["layers"])
        if leaf.ndim >= 3 and "eva_" not in jax.tree_util.keystr(path)
    )
    assert chunk_weights_from_env(env)[0] == matrices


@pytest.mark.parametrize("stated,env", [
    ("8", {"MAX_LEN": "64"}),
    ("64", {**_size_names("mixtral-8x7b-v0.1.json"), **CHAT}),
    # over the ceiling and over MAX_LEN: the operator's to choose
    ("1024", {"MAX_LEN": "256"}),
    ("512", {**_file("evabyte-6.5b.json"), **DOCQA}),
])
def test_a_stated_width_wins(stated, env):
    paged = paged_config_from_env({**env, "PREFILL_CHUNK_TOKENS": stated})
    assert paged.chunk_tokens == int(stated)
    assert paged.chunk_source == "env" and paged.chunk_weights is None
    assert f"chunk {stated} (env)" in paged.chunk_note


@pytest.mark.parametrize("stated,env", [
    ("-1", {}),
    ("-64", {**_file("evabyte-6.5b.json"), **DOCQA}),
    # not whole 16-position chunks; wider than one window
    ("500", {**_file("evabyte-6.5b.json"), **DOCQA}),
    ("4096", {**_file("evabyte-6.5b.json"), **DOCQA}),
])
def test_a_stated_width_is_validated_as_before(stated, env):
    with pytest.raises(SpecError, match="PREFILL_CHUNK_TOKENS"):
        paged_config_from_env({**env, "PREFILL_CHUNK_TOKENS": stated})


@pytest.mark.parametrize("unset", ["", "0", None])
def test_unset_is_empty_or_zero(unset):
    env = {} if unset is None else {"PREFILL_CHUNK_TOKENS": unset}
    assert paged_config_from_env(env).chunk_source == "model"


def test_sizes_that_describe_no_model_are_a_spec_error():
    with pytest.raises(SpecError, match="no prefill chunk width"):
        paged_config_from_env({"N_HEADS": "0"})
    # the same sizes under a stated width are not this check's to judge
    assert paged_config_from_env(
        {"N_HEADS": "0", "PREFILL_CHUNK_TOKENS": "64"}
    ).chunk_tokens == 64


@pytest.mark.parametrize("ratio,width", [
    (1.0, 256), (1.07, 512), (2.0, 512), (3.7, 512), (12.0, 512),
    (0.5, 128),
])
def test_the_rule_is_the_smallest_power_of_two_over_the_ridge(ratio, width):
    """Unclamped below the ceiling: read / per_token x 240 FLOPs a
    byte, rounded up to a power of two."""
    assert chosen_chunk_tokens(
        int(ratio * 1000), 1000, RowLayout(16), 4096
    ) == width


@pytest.mark.parametrize("name,stated", [
    ("mixtral-8x7b", ""), ("lfm2-24b-a2b", ""), ("evabyte-6.5b", ""),
    ("evabyte-6.5b", "512"), ("dense-gqa", ""), ("defaults", ""),
])
def test_the_scheduler_and_the_worker_reach_the_same_width(name, stated):
    """The scheduler renders the service YAML with its env and checks
    the task's env (analysis/shardcheck.py); the worker reads the same
    names from its process env, beside what the launch adds."""
    cell = dict(CHOICES[name][0])
    templated = {k: cell.pop(k) for k in list(cell)
                 if k in ("D_MODEL", "N_LAYERS", "MAX_LEN", "SERVE_SLOTS",
                          "KV_PAGES", "KV_PAGE_TOKENS", "MODEL_CONFIG")}
    scheduler_env = {
        **templated, **{f"TASKCFG_ALL_{k}": v for k, v in cell.items()},
    }
    if stated:
        scheduler_env["PREFILL_CHUNK_TOKENS"] = stated
    spec = from_yaml_file(
        os.path.join(REPO, "frameworks", "jax", "svc_serve.yml"),
        scheduler_env,
    )
    task_env = spec.pods[0].tasks[0].env
    assert task_env["PREFILL_CHUNK_TOKENS"] == stated
    checked = paged_config_from_env(task_env)
    served = paged_config_from_env({
        **task_env, "SANDBOX": "/tmp/x", "PORT_HTTP": "23100",
        "TASK_NAME": "server-0-api", "JAX_PLATFORMS": "cpu",
    })
    assert checked == served
    assert checked.chunk_source == ("env" if stated else "model")
    assert checked.chunk_tokens == (
        int(stated) if stated else CHOICES[name][1]
    )
    # the gang's YAML leaves the width to the code too
    gang = from_yaml_file(
        os.path.join(REPO, "frameworks", "jax", "svc_serve_gang.yml"), {}
    )
    assert gang.pods[0].tasks[0].env["PREFILL_CHUNK_TOKENS"] == ""


@pytest.mark.parametrize("stated", ["", "8"])
def test_stats_say_the_width_and_who_chose_it(stated):
    """What a worker hands its engine (frameworks/jax/serve_worker.py):
    ``prefill_chunk_source`` beside ``prefill_chunk_tokens``, and in
    ``model`` the two counts a chosen width was chosen from."""
    env = {"MAX_LEN": "32", "KV_PAGE_TOKENS": "4", "SERVE_SLOTS": "2",
           "PREFILL_CHUNK_TOKENS": stated}
    paged = paged_config_from_env(env)
    model = ChainModel(page_tokens=paged.page_tokens)
    engine = PagedEngine(
        model.prefill_chunk, model.decode, paged.slots, paged.max_len,
        24, page_tokens=paged.page_tokens, pages=paged.pages,
        chunk_tokens=paged.chunk_tokens, prefix_cache=False,
    )
    try:
        engine.annotate_stats(
            model={"d_model": 512, **paged.chunk_stats},
            prefill_chunk_source=paged.chunk_source,
        )
        prompt = list(range(1, 20))
        assert engine.submit([prompt], 4, 0.0)[0] == chain_oracle(prompt, 4)
        stats = engine.stats()
        assert stats["prefill_chunk_tokens"] == (8 if stated else 32)
        assert stats["prefill_chunk_source"] == (
            "env" if stated else "model"
        )
        assert ("chunk_read_weights" in stats["model"]) == (not stated)
        # 19 tokens: three chunks of 8, one of 32
        assert stats["loop"]["prefill_calls"] == (3 if stated else 1)
    finally:
        engine.stop()
