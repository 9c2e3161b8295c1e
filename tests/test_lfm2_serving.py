"""A mixed layer pattern on the serving path (models/decode.py
``layer_plan`` / ``_walk_pattern``, the conv state beside the paged
arena, models/moe.py ``moe_serve_ffn``) against the float32 reference
(dcos_commons_tpu/models/reference/lfm2_moe.py), on seeded random
weights at a small size: the benchmark's 9-layer pattern (one leading
dense conv layer, two periods of attention conv conv conv with a
mixture each) at hidden 64, 4/2 heads of 16, 8 experts of 48, top 2.

Prompts are prefilled in chunks and decoded through the pool's two
compiled programs, rows in slots and pages as an engine would give
them, and every served position's logits are held to the reference's
full forward pass over the whole sequence.
"""

import hashlib
import json

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.serve.migration import MigrationError
from dcos_commons_tpu.serve.paging import RowLayout, paged_config_from_env

MODEL = {
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv"],
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_dense_layers": 1,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 9,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128,
    "tie_word_embeddings": True, "qk_norm": True,
    "router_activation": "sigmoid",
}
PAGE, CHUNK, SLOTS, MAX_LEN = 4, 8, 4, 64
# Float32 on both sides, the same equations in another order of
# summation (pages, chunks, a state carried between calls, sorted rows
# in groups against a loop over experts): the largest difference seen
# over the cases below is 5e-6 on logits of magnitude 3.  A conv state
# that is off by one position, or a padded position routed to an
# expert, moves logits by 1e-2 and more.
TOLERANCE = 3e-5


@pytest.fixture(scope="module")
def config_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("lfm2") / "toy-lfm2.json"
    path.write_text(json.dumps(MODEL))
    return str(path)


@pytest.fixture(scope="module")
def config(config_file):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import config_from_env

    return config_from_env(
        {"MODEL_CONFIG": config_file}, dtype=jnp.float32, remat=False
    )


@pytest.fixture(scope="module")
def params(config):
    import jax

    from dcos_commons_tpu.models import init_params

    tree = init_params(config, jax.random.key(3))
    # norms and the selection bias away from their trivial values
    keys = iter(jax.random.split(jax.random.key(4), 64))

    def shake(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, tree)


def _pool(config, params):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    return PagedPoolModel(
        config, params, SLOTS, MAX_LEN, PAGE, pages=48, chunk_tokens=CHUNK
    )


class Rows:
    """Drives a pool as an engine would: a row has a slot and a table
    of pages of its own; prefill goes chunk by chunk, decode steps
    carry every row that is decoding, the other slots a zero table."""

    def __init__(self, pool):
        self.pool = pool
        self.next_page = 1
        self.tables = np.zeros((SLOTS, pool.pages_per_row), np.int32)
        self.seq, self.logit_rows = {}, {}

    def admit(self, slot, prompt, room=8):
        pages = -(-(len(prompt) + room) // PAGE)
        self.tables[slot] = 0
        self.tables[slot, :pages] = np.arange(
            self.next_page, self.next_page + pages
        )
        self.next_page += pages
        self.seq[slot] = list(prompt)
        first = None
        for start in range(0, len(prompt), CHUNK):
            true_len = min(CHUNK, len(prompt) - start)
            tokens = np.zeros((1, CHUNK), np.int32)
            tokens[0, :true_len] = prompt[start:start + true_len]
            first = self.pool.prefill_chunk(
                tokens, slot=slot, table=self.tables[slot], start=start,
                true_len=true_len, temp=0.0, seed=0,
                final=start + CHUNK >= len(prompt),
            )
        self.seq[slot].append(first)

    def retire(self, slot):
        self.tables[slot] = 0
        return self.seq.pop(slot)

    def step(self):
        tok = np.zeros(SLOTS, np.int32)
        pos = np.zeros(SLOTS, np.int32)
        tables = np.zeros_like(self.tables)
        for slot, seq in self.seq.items():
            tok[slot], pos[slot] = seq[-1], len(seq) - 1
            tables[slot] = self.tables[slot]
        nxt = self.pool.decode(
            tok, pos, np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32),
            tables,
        )
        for slot, seq in self.seq.items():
            seq.append(int(nxt[slot]))


def _greedy_by_reference(params, seq, prompt_len):
    """The reference's own greedy continuation of ``seq[:prompt_len]``
    read off ONE full forward over ``seq[:-1]``: position ``i``'s
    argmax is what a server must have put at ``i + 1``; returns the
    largest gap between the served token's logit and the best."""
    from dcos_commons_tpu.models.reference import lfm2_moe

    logits = np.asarray(lfm2_moe.logits(
        MODEL, params, np.asarray(seq[:-1], np.int32)
    ))
    served = logits[prompt_len - 1:]
    chosen = np.asarray(seq[prompt_len:])
    return float(np.max(
        served.max(-1) - served[np.arange(len(chosen)), chosen]
    ))


def test_the_pattern_is_read_as_data_and_walked_by_periods(config):
    from dcos_commons_tpu.models.decode import layer_plan

    assert config.layer_kinds[0] == ("conv", "dense")
    assert config.layer_kinds[1] == ("attention", "moe")
    assert config.layer_kinds[2:5] == (("conv", "moe"),) * 3
    assert (config.rope_theta, config.rms_norm_eps) == (1e6, 1e-5)
    assert (config.moe_score, config.moe_expert_bias, config.qk_norm) == (
        "sigmoid", True, True,
    )
    assert (config.n_experts, config.moe_top_k, config.moe_d_ff) == (8, 2, 48)
    # one leading layer, two whole periods of four under one scan
    assert layer_plan(config.layer_kinds) == (1, 4, 2, 0)
    # a pattern of one kind is one period of one layer
    assert layer_plan((("attention", "dense"),) * 5) == (0, 1, 5, 0)
    # the published 40 layers: two dense conv layers, nine periods, and
    # the two layers that begin a tenth
    published = (
        [("conv", "dense")] * 2
        + [("attention", "moe")] + [("conv", "moe")] * 3
    )
    published = tuple(
        published + published[2:] * 8 + [("attention", "moe"), ("conv", "moe")]
    )
    assert len(published) == 40
    assert layer_plan(published) == (2, 4, 9, 2)


def test_chunked_prefill_and_cached_decode_equal_the_reference(config, params):
    """Two rows of different lengths (neither a multiple of the chunk)
    share the decode steps; a third is then admitted into the slot the
    first just left, whose conv state it must not inherit."""
    rng = np.random.default_rng(0)
    rows = Rows(_pool(config, params))
    first, second = rng.integers(0, 128, 13), rng.integers(0, 128, 21)
    rows.admit(2, first)
    rows.admit(0, second)
    for _ in range(5):
        rows.step()
    assert _greedy_by_reference(params, rows.seq[0], 21) < TOLERANCE
    assert _greedy_by_reference(params, rows.retire(2), 13) < TOLERANCE
    third = rng.integers(0, 128, 10)
    rows.admit(2, third)
    for _ in range(4):
        rows.step()
    assert _greedy_by_reference(params, rows.seq[2], 10) < TOLERANCE
    assert _greedy_by_reference(params, rows.seq[0], 21) < TOLERANCE


def test_conv_state_after_chunks_is_the_last_two_gated_inputs(config, params):
    """Layer 0 is a conv layer whose input is the embedding: its state
    after the prompt's chunks is ``u = B * X`` at the last two TRUE
    positions, whatever the padding of the last chunk."""
    import jax.numpy as jnp

    from dcos_commons_tpu.models.reference import lfm2_moe

    pool = _pool(config, params)
    rows = Rows(pool)
    prompt = np.random.default_rng(1).integers(0, 128, 19)
    rows.admit(3, prompt)
    stack = params["layers"]["conv"]
    x = params["embed"][jnp.asarray(prompt)]
    normed = lfm2_moe._rms(x, stack["conv_norm"][0], 1e-5)
    b_gate, _c, x_gate = jnp.split(normed @ stack["conv_in"][0], 3, axis=-1)
    want = np.asarray(b_gate * x_gate)[-2:]
    got = np.asarray(pool.cache["conv_state"][0, 3])
    np.testing.assert_allclose(got, want, atol=1e-5)
    # nobody else's slot was written
    assert not np.asarray(pool.cache["conv_state"][:, :3]).any()
    # a decode step shifts it by one and leaves the idle slots alone
    rows.step()
    after = np.asarray(pool.cache["conv_state"][0])
    np.testing.assert_allclose(after[3, 0], want[1], atol=1e-5)
    assert not after[:3].any()


def test_sigmoid_routing_with_bias_picks_the_references_experts():
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.moe import MoEConfig, init_moe_params, route

    moe = MoEConfig(
        d_model=32, d_ff=16, n_experts=16, top_k=4, dtype=jnp.float32,
        score="sigmoid", expert_bias=True,
    )
    p = init_moe_params(moe, jax.random.key(0))
    p["expert_bias"] = p["expert_bias"] * 30.0   # a bias that matters
    x = jax.random.normal(jax.random.key(1), (64, 32))
    weights, chosen, _scores = route(moe, p, x)
    score = 1.0 / (1.0 + np.exp(-np.asarray(x @ p["router"], np.float64)))
    select = score + np.asarray(p["expert_bias"], np.float64)
    order = np.argsort(-select, -1)
    margin = np.take_along_axis(select, order[:, 3:4], -1) - \
        np.take_along_axis(select, order[:, 4:5], -1)
    wide = margin[:, 0] > 1e-4
    assert wide.sum() > 50
    assert (np.sort(np.asarray(chosen), -1)[wide]
            == np.sort(order[:, :4], -1)[wide]).all()
    # the bias moved the choice and never the weights
    assert (np.sort(np.argsort(-score, -1)[:, :4], -1)
            != np.sort(order[:, :4], -1)).any()
    picked = np.take_along_axis(score, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        np.asarray(weights), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-5,
    )


@pytest.mark.parametrize("score,bias,experts,top_k", [
    ("softmax", False, 4, 2), ("softmax", False, 8, 2),
    ("sigmoid", True, 16, 4), ("sigmoid", False, 8, 1),
])
def test_rows_that_stand_for_nothing_reach_no_expert_group(
    score, bias, experts, top_k
):
    """The grouped dispatch against a loop over experts; dead rows get
    zeros, and the counts are the live rows' by hand."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.moe import (
        MoEConfig,
        init_moe_params,
        moe_serve_ffn,
        route,
    )

    moe = MoEConfig(
        d_model=32, d_ff=24, n_experts=experts, top_k=top_k,
        dtype=jnp.float32, score=score, expert_bias=bias,
    )
    layers = jax.vmap(lambda k: init_moe_params(moe, k))(
        jax.random.split(jax.random.key(0), 3)
    )
    held = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    mine = {k: v[1] for k, v in layers.items() if k not in held}
    x = jax.random.normal(jax.random.key(1), (12, 32))
    live = jnp.asarray([1, 0, 1, 1, 0, 0, 1, 0, 1, 1, 0, 1], bool)
    y, counts = jax.jit(
        lambda x, live: moe_serve_ffn(moe, mine, held, 1, x, live)
    )(x, live)
    weights, chosen, _ = route(moe, mine, x)
    want = np.zeros((12, 32))
    for t in np.flatnonzero(np.asarray(live)):
        for w, e in zip(np.asarray(weights[t]), np.asarray(chosen[t])):
            gate = jax.nn.silu(x[t] @ held["w_gate"][1, e])
            up = x[t] @ held["w_up"][1, e]
            want[t] += w * np.asarray((gate * up) @ held["w_down"][1, e])
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert not np.asarray(y)[~np.asarray(live)].any()
    touched = len(set(np.asarray(chosen)[np.asarray(live)].reshape(-1)))
    assert counts.tolist() == [int(live.sum()) * top_k, touched]
    # with every row live nothing is masked
    _y, counts = moe_serve_ffn(moe, mine, held, 1, x)
    assert counts.tolist() == [12 * top_k, len(set(
        np.asarray(chosen).reshape(-1)
    ))]


def test_the_grouped_kernel_computes_what_ragged_dot_does():
    """The Pallas kernel of a TPU, interpreted here, from the dispatch
    plan of sixteen assignments already in order: groups at their place
    among all the stack's; the six rows past the last group belong to
    nobody (``ragged_dot`` gives zeros there, the kernel writes
    nothing)."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.ops import grouped_matmul as gm

    rows = jax.random.normal(jax.random.key(0), (16, 32))
    stack = jax.random.normal(jax.random.key(1), (3 * 4, 32, 24))
    chosen = jnp.asarray([0] * 3 + [2] * 5 + [3] * 2 + [1] * 6)[:, None]
    live = jnp.arange(16) < 10
    plan = gm.dispatch_plan(chosen, live, 4)
    assert plan.tiles is None
    assert plan.group_sizes.tolist() == [3, 0, 5, 2]
    assert plan.src.tolist() == plan.back.tolist() == list(range(16))
    want = gm.grouped_matmul(rows, stack, plan, 4)
    assert not np.asarray(want)[10:].any()
    with mock.patch.object(gm, "grouped_matmul_kernel", lambda: "interpret"):
        tiled = gm.dispatch_plan(chosen, live, 4)
        got = gm.grouped_matmul(rows, stack, tiled, jnp.int32(4))
    # three groups hold rows, all in the one row tile
    assert int(tiled.tiles.num_tiles) == 3
    assert tiled.tiles.group_ids[:3].tolist() == [0, 2, 3]
    assert tiled.tiles.group_offsets.tolist() == [0, 3, 3, 8, 10]
    np.testing.assert_allclose(
        np.asarray(got)[:10], np.asarray(want)[:10], atol=1e-4
    )
    by_hand = np.asarray(rows[3:8] @ stack[4 + 2])
    np.testing.assert_allclose(np.asarray(got)[3:8], by_hand, atol=1e-4)


def test_the_pool_counts_assignments_and_groups_with_the_tokens(
    config, params
):
    """Two live rows of four slots, eight expert layers, two experts a
    token: 32 assignments a step, and as many groups as the rows'
    choices are distinct, fetched with the step's tokens.  The prompts'
    chunks are counted apart, padding left out, and reach the host with
    each prompt's last chunk: 9 + 12 positions in two chunks each."""
    pool = _pool(config, params)
    rows = Rows(pool)
    rng = np.random.default_rng(2)
    rows.admit(1, rng.integers(0, 128, 9))
    rows.admit(3, rng.integers(0, 128, 12))
    counters = pool.loop_counters()
    # a layer's groups: at least the two one token chooses, at most all
    # eight experts (two of them in the chunk of one position)
    assert counters.pop("moe_prefill_groups_touched_sum") in range(
        4 * 2 * 8, (8 + 2 + 8 + 8) * 8 + 1
    )
    assert counters == {
        "moe_assignments_sum": 0, "moe_groups_touched_sum": 0,
        "moe_prefill_assignments_sum": (9 + 12) * 2 * 8,
        "moe_prefill_chunks_counted": 4,
    }
    rows.step()
    rows.step()
    counters = pool.loop_counters()
    assert counters["moe_assignments_sum"] == 2 * 2 * 2 * 8
    assert 2 * 2 * 8 <= counters["moe_groups_touched_sum"] <= 2 * 4 * 8


def _digest_tree(tree):
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(leaf.dtype).encode())
        h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()[:16]


def _digest_jaxpr(fn, *args):
    import jax

    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()
    ).hexdigest()[:16]


# Taken on the commit before the layer pattern (c5d458b), by the code of
# this test: what a pattern of ONE kind has to leave as it was.
ONE_KIND = {
    "mixtral": dict(
        fields=dict(vocab=128, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=96, n_experts=4),
        tree="2926af94c8cb94a0", forward="2463f348e8f0ae12",
    ),
    "evabyte": dict(
        fields=dict(vocab=320, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=4, d_ff=96, attention="eva", window_size=32,
                    chunk_size=4, norm_unit_offset=True,
                    tie_embeddings=False, n_pred_heads=8,
                    rope_theta=100000.0, rms_norm_eps=1e-5),
        # the two programs as ISSUE 40 left them (the chunk norms the
        # one row whose logits it returns); ``prefill`` re-taken at
        # ISSUE 52, whose one trunk for every family
        # (``_serving_trunk``) traces the chunk's positions before the
        # EVA part asks for them: the same equations in another order,
        # which tests/test_serving_programs.py holds by multiset
        # against the commit before (was 362c53e717b4c27c); ``decode``
        # kept its order
        tree="8cd45ac75946971e", prefill="170c5dd5aaf2f172",
        decode="38fb3c32658004c4",
    ),
}


@pytest.mark.parametrize("name", sorted(ONE_KIND))
def test_a_pattern_of_one_kind_keeps_the_tree_and_the_programs(name):
    """The seeded parameter tree of both accepted configurations' kinds
    is, leaf for leaf and value for value, what it was; EvaByte's two
    serving programs are the jaxprs they were; Mixtral's training
    forward is, and its two serving programs (whose expert dispatch
    this commit replaced) still scan ONE stack of layers."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import (
        TransformerConfig,
        forward,
        init_params,
    )
    from dcos_commons_tpu.models import decode as D

    case = ONE_KIND[name]
    config = TransformerConfig(dtype=jnp.float32, remat=False,
                               **case["fields"])
    assert config.one_kind
    params = init_params(config, jax.random.key(0))
    assert _digest_tree(params) == case["tree"]
    assert not any(
        part in params["layers"]
        for part in ("attention", "conv", "dense", "moe")
    )
    cache = D.init_paged_kv_cache(config, 9, 4)
    programs = {
        "prefill": (
            lambda p, c, t, tb: D.paged_prefill_chunk(
                config, p, c, t, tb, jnp.int32(0), jnp.int32(5)
            ),
            (params, cache, jnp.zeros((1, 8), jnp.int32),
             jnp.zeros(12, jnp.int32)),
        ),
        "decode": (
            lambda p, c, t, ps, tb: D.paged_decode_step(
                config, p, c, t, ps, tb
            ),
            (params, cache, jnp.zeros(3, jnp.int32), jnp.zeros(3, jnp.int32),
             jnp.zeros((3, 12), jnp.int32)),
        ),
    }
    for program, (fn, args) in programs.items():
        if program in case:
            assert _digest_jaxpr(fn, *args) == case[program], program
        else:
            scans = [
                eqn for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                if eqn.primitive.name == "scan"
            ]
            assert len(scans) == 1
            assert scans[0].params["length"] == config.n_layers
    if "forward" in case:
        assert _digest_jaxpr(
            lambda p, t: forward(config, p, t), params,
            jnp.zeros((1, 8), jnp.int32),
        ) == case["forward"]


def test_the_training_forward_refuses_a_pattern_it_cannot_run(config, params):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import forward, generate

    with pytest.raises(NotImplementedError, match="serving path only"):
        forward(config, params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="paged arena alone"):
        generate(config, params, jnp.zeros((1, 8), jnp.int32), 4)


def test_pages_never_travel_or_are_shared_without_their_state(
    config, config_file, params
):
    """A row of this model is more than its pages: the prefix cache is
    off and says why, and export, splice, freeze and hand-off refuse
    with the reason a client can read."""
    pool = _pool(config, params)
    state = 7 * 2 * 64 * 4   # conv layers x taps - 1 x hidden x float32
    assert pool.layout.state_bytes_per_row == state
    reason = pool.layout.carries_state
    assert "recurrent state outside its pages" in reason
    with pytest.raises(ValueError, match="outside its pages"):
        pool.export_page(1)
    with pytest.raises(ValueError, match="outside its pages"):
        pool.import_page(1, {})
    env = {"MODEL_CONFIG": config_file, "MAX_LEN": "64", "SERVE_SLOTS": "4",
           "KV_PAGE_TOKENS": "4", "PREFILL_CHUNK_TOKENS": "8"}
    paged = paged_config_from_env(env)
    assert paged.prefix_cache is False
    assert paged.layout.state_bytes_per_row == 7 * 2 * 64 * 2
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, SLOTS, MAX_LEN, MAX_LEN - 8,
        page_tokens=PAGE, pages=48, chunk_tokens=CHUNK, prefix_cache=True,
        layout=pool.layout, read_page=pool.export_page,
        write_page=pool.import_page, resolve_decode_fn=pool.resolve_decode,
        device_counters=pool.loop_counters,
    )
    try:
        prompt = list(np.random.default_rng(5).integers(0, 128, 17))
        served = engine.submit([prompt], 6, 0.0)[0]
        again = engine.submit([prompt], 6, 0.0)[0]
        assert served == again
        assert _greedy_by_reference(params, prompt + served, 17) < TOLERANCE
        stats = engine.stats()
        assert stats["state_bytes_per_row"] == state
        assert stats["prefix_cache"] == "off: " + reason
        # the same prompt twice found nothing to share
        assert stats["prefix_cache_hits"] == 0
        assert stats["loop"]["moe_assignments_sum"] > 0
        assert stats["loop"]["moe_groups_touched_sum"] > 0
        for verb in (lambda: engine.freeze(1),
                     lambda: engine.export_frozen(1),
                     lambda: engine.splice(None)):
            with pytest.raises(MigrationError, match="outside its pages"):
                verb()
    finally:
        engine.stop()
    with pytest.raises(ValueError, match="no prefill hand-off"):
        PagedEngine(
            pool.prefill_chunk, pool.decode, SLOTS, MAX_LEN, MAX_LEN - 8,
            page_tokens=PAGE, pages=48, chunk_tokens=CHUNK,
            layout=pool.layout, handoff=lambda row: None,
        )
    # a layout without such state is as it was
    assert RowLayout(PAGE).carries_state == ""
