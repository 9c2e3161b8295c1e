"""Window attention layers among full ones on the serving path
(models/decode.py: the two attention operators of one walk, each kind's
cache; ops/paged_decode.py ``window_decode_attention``; serve/paging.py
``RowLayout``'s ring) against the float32 reference
(dcos_commons_tpu/models/reference/afmoe.py), on seeded random weights
at a small size: hidden 64, 4 query heads of 32 (so ``head_dim`` is NOT
``hidden / heads``), 2 KV heads, window 32, pages of 16, chunks of 16,
layers ``sliding(dense) sliding sliding sliding full``, 8 experts top-2
and one shared, an output gate on attention, four norms a layer, the
embedding scaled.

Prompts are several windows long, so every row's ring (3 pages: the
window and one chunk) wraps more than once.
"""

import json

import numpy as np
import pytest

from dcos_commons_tpu.serve.paging import RowLayout

MODEL = {
    "model_type": "afmoe", "hidden_size": 64, "head_dim": 32,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "sliding_window": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_dense_layers": 1, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1,
    "num_hidden_layers": 5, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "mup_enabled": True, "n_group": 1,
    "topk_group": 1, "tie_word_embeddings": False, "vocab_size": 128,
    # what the published config has no key for
    "qk_norm": True, "attention_gate": True, "sandwich_norm": True,
    "nope_on_full_attention": True, "use_expert_bias": True,
    "route_norm_eps": 1e-20,
}
PAGE, CHUNK, SLOTS, MAX_LEN, WINDOW = 16, 16, 3, 128, 32
RING = (WINDOW + CHUNK) // PAGE
# Float32 on both sides, the same equations in another order of
# summation: the largest difference seen over the cases below is 3e-6
# on logits of magnitude 4.  A window that is off by one
# position, a ring entry read for the wrong position or a full layer
# that rotates moves logits by 1e-2 and more
# (``test_the_reference_can_tell_a_window_that_is_off_by_one``).
TOLERANCE = 3e-5


@pytest.fixture(scope="module")
def config_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("afmoe") / "toy-afmoe.json"
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
    # norms away from their trivial values
    keys = iter(jax.random.split(jax.random.key(4), 64))

    def shake(path, leaf):
        if "norm" in jax.tree_util.keystr(path):
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, tree)


def reference_logits(params, seq, model=MODEL):
    from dcos_commons_tpu.models.reference import afmoe

    return np.asarray(afmoe.logits(model, params, np.asarray(seq, np.int32)))


class Served:
    """Drives the two serving programs as an engine would and keeps the
    LOGITS they return: a row has a slot, its slot's ring and history
    pages of its own; prefill goes chunk by chunk, a decode step carries
    every row that is decoding, the other slots a zero table."""

    def __init__(self, config, params, kernel):
        import jax

        from dcos_commons_tpu.models.decode import (
            init_paged_kv_cache,
            paged_decode_step,
            paged_prefill_chunk,
        )

        self.layout = RowLayout(
            PAGE, sliding_window=WINDOW
        ).with_chunk(CHUNK)
        assert self.layout.ring_pages == RING
        self.cache = init_paged_kv_cache(
            config, 64, PAGE, slots=SLOTS,
            window_pages=SLOTS * RING + 1,
        )
        self.params = params
        self.chunk = jax.jit(lambda cache, tokens, table, start, n: (
            paged_prefill_chunk(
                config, params, cache, tokens, table, start, n,
                ring_pages=RING,
            )
        ))
        self.step_fn = jax.jit(lambda cache, tok, pos, tables: (
            paged_decode_step(
                config, params, cache, tok, pos, tables, ring_pages=RING
            )
        ))
        self.kernel = kernel
        self.next_page = 1
        self.tables = np.zeros(
            (SLOTS, self.layout.table_len(MAX_LEN)), np.int32
        )
        self.seq, self.logits, self.counts = {}, {}, []

    def admit(self, slot, prompt, room=16):
        pages = -(-(len(prompt) + room) // PAGE)
        self.tables[slot] = 0
        self.tables[slot, :RING] = self.layout.ring_entries(slot)
        self.tables[slot, RING:RING + pages] = np.arange(
            self.next_page, self.next_page + pages
        )
        self.next_page += pages
        self.seq[slot], self.logits[slot] = list(prompt), {}
        for start in range(0, len(prompt), CHUNK):
            true_len = min(CHUNK, len(prompt) - start)
            tokens = np.zeros((1, CHUNK), np.int32)
            tokens[0, :true_len] = prompt[start:start + true_len]
            logits, self.cache, counts = self.chunk(
                self.cache, tokens, self.tables[slot], start, true_len
            )
            self.counts.append(np.asarray(counts))
            # the chunk's last real position
            self.logits[slot][start + true_len - 1] = np.asarray(logits[0])
        self.seq[slot].append(int(np.argmax(logits[0])))

    def step(self):
        tok = np.zeros(SLOTS, np.int32)
        pos = np.zeros(SLOTS, np.int32)
        tables = np.zeros_like(self.tables)
        for slot, seq in self.seq.items():
            tok[slot], pos[slot] = seq[-1], len(seq) - 1
            tables[slot] = self.tables[slot]
        logits, self.cache, counts = self.step_fn(self.cache, tok, pos, tables)
        self.counts.append(np.asarray(counts))
        for slot, seq in self.seq.items():
            self.logits[slot][len(seq) - 1] = np.asarray(logits[slot])
            seq.append(int(np.argmax(logits[slot])))

    def worst(self, slot):
        """The largest difference between a served position's logits
        and the reference's full forward over the row's sequence."""
        want = reference_logits(self.params, self.seq[slot][:-1])
        return max(
            float(np.max(np.abs(got - want[at])))
            for at, got in self.logits[slot].items()
        )


@pytest.fixture(params=["xla", "interpret"])
def kernel(request, monkeypatch):
    """The decode attention and the grouped matmul as the CPU runs them
    (gathers, ``ragged_dot``), or the chip's Pallas kernels interpreted;
    a prefill chunk's history a block of two pages at a time."""
    from dcos_commons_tpu.models import decode
    from dcos_commons_tpu.ops import grouped_matmul

    monkeypatch.setattr(decode, "CHUNK_ATTENTION_BLOCK", 2 * PAGE)
    if request.param == "interpret":
        monkeypatch.setattr(
            decode, "decode_attention_kernel", lambda *_: "interpret"
        )
        monkeypatch.setattr(
            grouped_matmul, "grouped_matmul_kernel", lambda: "interpret"
        )
    return request.param


def test_the_file_is_read_as_data(config):
    from dcos_commons_tpu.models.decode import layer_plan

    assert config.head_dim == 32 != config.d_model // config.n_heads
    assert config.layer_kinds == (
        ("sliding", "dense"),) + (("sliding", "moe"),) * 3 + (
        ("attention", "moe"),)
    # one leading layer, three window layers under one scan, the full one
    assert layer_plan(config.layer_kinds) == (1, 1, 3, 1)
    assert (config.sliding_window, config.n_shared_experts) == (32, 1)
    assert (config.moe_score, config.moe_norm_topk, config.moe_scaling,
            config.moe_norm_eps) == ("sigmoid", True, 2.826, 1e-20)
    assert (config.attention_gate, config.sandwich_norm, config.embed_scale,
            config.nope_full_attention, config.qk_norm,
            config.tie_embeddings) == (True, True, True, True, True, False)


def test_chunked_prefill_and_cached_decode_equal_the_reference(
        config, params, kernel):
    """Rows of different lengths (none a multiple of the chunk, each
    several windows long: every ring wraps) share the decode steps; a
    third is admitted into the slot the first left, whose ring it must
    not read."""
    rng = np.random.default_rng(0)
    served = Served(config, params, kernel)
    served.admit(2, rng.integers(0, 128, 71))
    served.admit(0, rng.integers(0, 128, 100))
    for _ in range(6):
        served.step()
    assert served.worst(0) < TOLERANCE
    assert served.worst(2) < TOLERANCE
    del served.seq[2]
    served.admit(2, rng.integers(0, 128, 45))
    for _ in range(3):
        served.step()
    assert served.worst(2) < TOLERANCE
    assert served.worst(0) < TOLERANCE


@pytest.mark.parametrize("last", [
    WINDOW - 1, WINDOW, WINDOW + 1,             # the first window's edge
    3 * WINDOW - 1, 3 * WINDOW, 3 * WINDOW + 1,  # after the ring wrapped
    WINDOW + CHUNK // 2,                         # a chunk that straddles it
])
def test_the_windows_edge(config, params, kernel, last):
    """A query at ``p`` sees exactly ``(p - W, p]``: the prompt ends at
    ``last`` (prefill reads it there) and two decode steps go on from
    it, so both programs cross the edge."""
    rng = np.random.default_rng(last)
    served = Served(config, params, kernel)
    served.admit(1, rng.integers(0, 128, last + 1))
    served.step()
    served.step()
    assert sorted(served.logits[1])[-3:] == [last, last + 1, last + 2]
    assert served.worst(1) < TOLERANCE


@pytest.mark.parametrize("off", [-1, 1])
def test_the_reference_can_tell_a_window_that_is_off_by_one(params, off):
    """What the tolerance above can see: the same forward with a window
    one position narrower or wider moves logits a hundred tolerances."""
    seq = np.random.default_rng(5).integers(0, 128, 3 * WINDOW + 2)
    exact = reference_logits(params, seq)
    moved = reference_logits(
        params, seq, dict(MODEL, sliding_window=WINDOW + off)
    )
    assert np.max(np.abs(exact[:WINDOW - 1] - moved[:WINDOW - 1])) < 1e-5
    assert np.max(np.abs(exact[WINDOW + 1:] - moved[WINDOW + 1:])) > (
        100 * TOLERANCE
    )


def test_both_kernels_steps_are_stated(config, monkeypatch):
    """``/stats`` ``model.decode_attention_step`` names the full
    layers' kernel and the window layers', each with the step its own
    arena's shapes give."""
    import jax

    from dcos_commons_tpu.models import decode
    from dcos_commons_tpu.ops.paged_decode import walk_step

    cache = decode.init_paged_kv_cache(
        config, 64, PAGE, slots=SLOTS, window_pages=SLOTS * RING + 1
    )
    assert decode.decode_attention_step(config, cache) == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode.decode_attention_step(config, cache) == {
        "paged_decode_attention": walk_step(cache["k"]),
        "paged_decode_attention_window": walk_step(cache["k_window"]),
    }


@pytest.mark.parametrize("idle", [(), (0, 3, 6)], ids=["all_live", "idle"])
def test_the_window_kernel_reads_the_last_window_out_of_a_ring(idle):
    """ops/paged_decode.py ``window_decode_attention`` (interpreted)
    against a softmax over the positions ``(p - W, p]`` laid out in
    position order, at positions before, at and after the window's
    edge and after several wraps; slots it is told are idle, before,
    between and behind the live rows, are not read and give zeros."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.ops.paged_decode import window_decode_attention

    heads, kv, hd, ring = 4, 2, 128, 3
    pos = np.array([0, 5, WINDOW - 1, WINDOW, WINDOW + 1, 100, 111])
    rows = len(pos)
    keys = jax.random.split(jax.random.key(0), 3)
    k_seq = jax.random.normal(keys[0], (rows, 112, kv, hd))
    v_seq = jax.random.normal(keys[1], (rows, 112, kv, hd))
    q = jax.random.normal(keys[2], (rows, heads, hd))
    arena_k = np.zeros((1 + rows * ring, PAGE, kv, hd), np.float32)
    arena_v = np.zeros_like(arena_k)
    ids = 1 + np.arange(rows * ring).reshape(rows, ring)
    for s in range(rows):
        for p in range(pos[s] + 1):          # later positions write over
            arena_k[ids[s, (p // PAGE) % ring], p % PAGE] = k_seq[s, p]
            arena_v[ids[s, (p // PAGE) % ring], p % PAGE] = v_seq[s, p]
    live = np.array([s not in idle for s in range(rows)])
    got = window_decode_attention(
        q, jnp.asarray(arena_k), jnp.asarray(arena_v),
        jnp.asarray(ids * live[:, None]), jnp.asarray(pos, jnp.int32),
        jnp.asarray(live) if idle else None, window=WINDOW,
        scale=hd ** -0.5, interpret=True,
    )
    for s in range(rows):
        if s in idle:
            assert not np.asarray(got[s]).any()
            continue
        lo = max(0, pos[s] - WINDOW + 1)
        for head in range(heads):
            g = head // (heads // kv)
            score = (k_seq[s, lo:pos[s] + 1, g] @ q[s, head]) * hd ** -0.5
            want = jax.nn.softmax(score) @ v_seq[s, lo:pos[s] + 1, g]
            np.testing.assert_allclose(got[s, head], want, atol=2e-5)


def test_shared_expert_is_added_and_counters_count_the_routed_alone(
        config, params):
    """A mixture layer's output is shared(h) + the routed sum, and what
    it counts is the routed assignments: ``tokens x top_k``, the shared
    expert never among them."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.moe import moe_serve_ffn
    from dcos_commons_tpu.models.transformer import moe_config_of

    moe_config = moe_config_of(config)
    assert moe_config.n_shared == 1
    stack = params["layers"]["moe"]
    layer = jax.tree.map(lambda a: a[1], stack)
    experts = {name: stack[name] for name in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.key(9), (7, 64))
    live = jnp.array([True] * 5 + [False] * 2)
    y, counts = moe_serve_ffn(moe_config, layer, experts, 1, x, live)
    import dataclasses

    routed_only = dataclasses.replace(moe_config, n_shared=0)
    y_routed, counts_routed = moe_serve_ffn(
        routed_only, layer, experts, 1, x, live
    )
    shared = (
        jax.nn.silu(x @ layer["shared_gate"]) * (x @ layer["shared_up"])
    ) @ layer["shared_down"]
    np.testing.assert_allclose(y, y_routed + shared, atol=1e-5)
    assert np.abs(np.asarray(shared)).max() > 0.1
    assert list(np.asarray(counts)) == list(np.asarray(counts_routed))
    assert int(counts[0]) == 5 * config.moe_top_k
    assert 1 <= int(counts[1]) <= config.n_experts


def test_the_programs_count_routed_assignments_alone(config, params):
    served = Served(config, params, "xla")
    served.admit(0, np.arange(20) % 128)
    served.step()
    n_moe = config.n_layers_of("moe")
    # two chunks of 16 and 4 true positions, then one decode row
    assert [int(c[0]) for c in served.counts] == [
        16 * 2 * n_moe, 4 * 2 * n_moe, 1 * 2 * n_moe,
    ]
