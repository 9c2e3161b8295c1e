"""Window attention layers among full ones, off the device: the row
layout of the two kinds of cache (serve/paging.py ``RowLayout``) against
a brute-force model of a ring and a history, the engine's pages over a
thousand admissions, what a configuration file may state
(models/transformer.py ``config_fields_from_file``) and everything this
layout refuses, by its message."""

import json

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.serve.migration import MigrationError
from dcos_commons_tpu.serve.paging import (
    PageAllocator,
    RowLayout,
    paged_config_from_env,
)

PAGE, WINDOW, CHUNK, MAX_LEN = 4, 12, 8, 96
LAYOUT = RowLayout(PAGE, sliding_window=WINDOW).with_chunk(CHUNK)
RING = LAYOUT.ring_pages


class BruteForce:
    """A row as positions written one by one: a ring page holds the
    positions last written into it, the history keeps every page."""

    def __init__(self):
        self.ring = {}                   # ring page -> {offset: position}
        self.history = set()             # virtual pages written
        self.touched = []                # table entries, by first touch

    def write(self, first, last):
        for p in range(first, last + 1):
            self.ring.setdefault((p // PAGE) % RING, {})[p % PAGE] = p
            if p // PAGE not in self.history:
                self.history.add(p // PAGE)
                self.touched.append(RING + p // PAGE)

    def ring_pages_seen_from(self, query):
        """Ring pages that hold a position the query at ``query`` sees."""
        return sorted(
            page for page, held in self.ring.items()
            if any(query - WINDOW < p <= query for p in held.values())
        )


def test_the_ring_covers_a_window_and_a_chunk():
    assert RING == (WINDOW + CHUNK) // PAGE == 5
    # a window that ends inside a page takes one page more
    assert RowLayout(4, sliding_window=10).with_chunk(8) \
        .ring_pages == 6
    assert RowLayout(16, sliding_window=2048).with_chunk(512) \
        .ring_pages == 160
    with pytest.raises(ValueError, match="whole pages"):
        LAYOUT.with_chunk(6)
    with pytest.raises(ValueError, match="no window layer"):
        RowLayout(PAGE, ring_pages=3)
    # a layout is whole once it knows the chunk
    assert RowLayout(PAGE, sliding_window=WINDOW).ring_pages == 0
    with pytest.raises(ValueError, match="not both"):
        RowLayout(PAGE, window=16, chunk=4, sliding_window=8, ring_pages=3)
    # a layout with no window layer is what it was
    assert RowLayout(PAGE).with_chunk(CHUNK) == RowLayout(PAGE)


@pytest.mark.parametrize("prompt,new", [(5, 3), (13, 20), (40, 9), (77, 19)])
def test_the_layout_against_a_ring_and_a_history_written_by_hand(prompt, new):
    assert LAYOUT.table_len(MAX_LEN) == RING + MAX_LEN // PAGE
    brute = BruteForce()
    written = 0
    # prefill by chunks, then decode steps one position at a time
    spans = [
        (s, min(s + CHUNK, prompt) - 1) for s in range(0, prompt, CHUNK)
    ] + [(p, p) for p in range(prompt, prompt + new - 1)]
    for first, last in spans:
        before = list(brute.touched)
        # while a chunk is written, no position its first query still
        # sees is lost: the ring holds the window AND the chunk
        for p in range(first, last + 1):
            brute.write(p, p)
            for query in range(first, p + 1):
                held = {
                    pos for page in brute.ring.values()
                    for pos in page.values()
                }
                assert set(range(max(0, query - WINDOW + 1), query + 1)) \
                    <= held
        fresh = [v for v in brute.touched if v not in before]
        assert [
            v for v in LAYOUT.write_slots(first, last) if v not in before
        ] == fresh
        written = last + 1
        # what the next query reads: of the ring, the pages that hold a
        # position it sees; of the history, every page
        assert LAYOUT.live_slots(written) == (
            brute.ring_pages_seen_from(written)
            + [RING + v for v in sorted(brute.history)]
        )
        assert LAYOUT.entries(written) == written
        assert LAYOUT.window_entries(written) == min(written, WINDOW)
    # the worst case is the history's pages alone: a ring is resident
    assert LAYOUT.worst_case_pages(prompt, new) == len(brute.history)
    assert LAYOUT.worst_case_pages(prompt, new) == -(-(prompt + new - 1) // PAGE)
    assert LAYOUT.rollovers(0, written) == LAYOUT.summaries(0, written) == 0


def test_every_slots_ring_is_its_own():
    rings = [LAYOUT.ring_entries(slot) for slot in range(4)]
    flat = [page for ring in rings for page in ring]
    assert sorted(flat) == list(range(1, 4 * RING + 1))   # 0 is the trash
    assert all(len(ring) == RING for ring in rings)


def test_the_allocator_gives_and_takes_back_history_pages_alone():
    alloc = PageAllocator(40, PAGE, prefix_cache=True, layout=LAYOUT)
    rng = np.random.default_rng(1)
    for _ in range(1000):
        prompt = [int(t) for t in rng.integers(0, 50, rng.integers(1, 60))]
        new = int(rng.integers(1, 30))
        admission = alloc.admit(prompt, new)
        assert admission is not None and admission.cached_pages == 0
        need = LAYOUT.worst_case_pages(len(prompt), new)
        assert alloc.reserved_pages == need
        pages = [
            alloc.alloc(admission)
            for _v in LAYOUT.write_slots(0, len(prompt) + new - 2)
        ]
        assert len(pages) == need
        alloc.retire(admission, pages)
        alloc.check_invariants()
        assert alloc.free_pages == 40 and alloc.reserved_pages == 0


class Fake:
    """A device half that counts: every prompt's first token is 1, a
    step answers its input plus one; it keeps the tables it was handed."""

    def __init__(self):
        self.tables = []

    def prefill_chunk(self, tokens, *, slot, table, start, true_len, temp,
                      seed):
        self.tables.append((slot, np.array(table)))
        return 1

    def decode(self, tok, pos, temps, seeds, tables, n_active):
        for slot in range(len(tok)):
            if tables[slot].any():
                self.tables.append((slot, np.array(tables[slot])))
        return np.asarray(tok) + 1


def test_a_thousand_admissions_return_every_ring_and_history_page():
    fake, slots = Fake(), 3
    engine = PagedEngine(
        fake.prefill_chunk, fake.decode, slots, MAX_LEN, MAX_LEN - 16,
        page_tokens=PAGE, pages=slots * MAX_LEN // PAGE, chunk_tokens=CHUNK,
        layout=LAYOUT,
    )
    rng = np.random.default_rng(2)
    try:
        for _ in range(250):
            prompts = [
                [int(t) for t in rng.integers(0, 50, rng.integers(1, 70))]
                for _ in range(4)
            ]
            new = int(rng.integers(1, 16))
            out = engine.submit(prompts, new)
            assert out == [list(range(1, new + 1))] * 4
        stats = engine.stats()
    finally:
        engine.stop()
    assert stats["requests_admitted"] == 1000
    assert stats["kv_pages_free"] == stats["kv_pages_total"]
    assert stats["kv_history_pages_in_use"] == 0
    assert stats["kv_window_pages_in_use"] == 0
    assert stats["kv_window_live_tokens"] == stats["kv_live_tokens"] == 0
    assert stats["prefix_cache"].startswith("off: a row of this model keeps")
    # every table the device saw: the slot's own ring first, then
    # history pages out of the budget, none of them a ring's number by
    # accident of the two arenas' numbering being the same
    for slot, table in fake.tables:
        assert list(table[:RING]) == LAYOUT.ring_entries(slot)
        history = table[RING:]
        held = history[history > 0]
        assert len(set(held)) == len(held)
        assert (held <= slots * MAX_LEN // PAGE).all()


def test_the_gauges_of_a_row_in_flight():
    """A row of 30 positions holds 30 history entries and the last 12
    of its ring; its slot's whole ring is in use."""
    import threading

    gate, fake = threading.Event(), Fake()
    decode = fake.decode

    def held_decode(*args):
        gate.wait(5)
        return decode(*args)

    engine = PagedEngine(
        fake.prefill_chunk, held_decode, 2, MAX_LEN, MAX_LEN - 16,
        page_tokens=PAGE, pages=48, chunk_tokens=CHUNK, layout=LAYOUT,
    )
    try:
        done = threading.Thread(
            target=engine.submit, args=([list(range(30))], 5)
        )
        done.start()
        for _ in range(200):
            stats = engine.stats()
            if stats["context_live_tokens"] >= 30:
                break
            gate.wait(0.01)
        gate.set()
        done.join(10)
    finally:
        gate.set()
        engine.stop()
    assert stats["context_live_tokens"] == stats["kv_live_tokens"] >= 30
    assert stats["kv_window_live_tokens"] == WINDOW
    assert stats["kv_window_pages_in_use"] == RING
    assert stats["kv_history_pages_in_use"] >= 8


# -- what a configuration file may state ---------------------------------

FILE = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "num_hidden_layers": 3, "intermediate_size": 96,
    "vocab_size": 128,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 48, "num_dense_layers": 1,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.5, "route_norm_eps": 1e-20, "mup_enabled": True,
    "attention_gate": True, "sandwich_norm": True,
    "nope_on_full_attention": True, "rope_scaling": None, "n_group": 1,
    "topk_group": 1, "tie_word_embeddings": False,
}


def fields_of(tmp_path, **changes):
    from dcos_commons_tpu.models.transformer import config_fields_from_file

    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(FILE, **changes)))
    return config_fields_from_file(str(path))


def test_each_new_key_is_read(tmp_path):
    fields = fields_of(tmp_path)
    assert fields["d_head"] == 32
    assert fields["layer_types"] == ("sliding", "sliding", "attention")
    assert fields["sliding_window"] == 32
    assert fields["n_shared_experts"] == 1
    assert (fields["moe_score"], fields["moe_norm_topk"],
            fields["moe_scaling"], fields["moe_norm_eps"]) == (
        "sigmoid", True, 2.5, 1e-20)
    assert fields["embed_scale"] is True
    assert (fields["attention_gate"], fields["sandwich_norm"],
            fields["nope_full_attention"]) == (True, True, True)
    assert fields["tie_embeddings"] is False


def test_a_stated_head_dim_is_honoured_and_derived_only_where_absent(tmp_path):
    from dcos_commons_tpu.models import config_from_env

    path = tmp_path / "model.json"
    path.write_text(json.dumps(FILE))
    stated = config_from_env({"MODEL_CONFIG": str(path)})
    assert stated.head_dim == 32 and stated.d_model // stated.n_heads == 16
    # the old refusal ("the program derives head_dim") is gone where
    # the file states one; where it states none the program derives it
    absent = {k: v for k, v in FILE.items() if k != "head_dim"}
    path.write_text(json.dumps(absent))
    derived = config_from_env({"MODEL_CONFIG": str(path)})
    assert derived.d_head == 0 and derived.head_dim == 16
    # the eight env integers alone: as ever
    plain = config_from_env({"D_MODEL": "64", "N_HEADS": "4"})
    assert plain.d_head == 0 and plain.head_dim == 16
    assert plain.sliding_window == 0 and not plain.attention_gate


def test_a_window_that_no_layer_keeps_is_passed_over(tmp_path):
    fields = fields_of(tmp_path, layer_types=["full_attention"] * 3)
    assert "sliding_window" not in fields


@pytest.mark.parametrize("changes,named", [
    ({"n_group": 2}, "n_group 2"),
    ({"topk_group": 4}, "topk_group 4"),
    ({"num_expert_groups": 2}, "num_expert_groups 2"),
    ({"num_limited_groups": 2}, "num_limited_groups 2"),
    ({"num_shared_experts": 2}, "num_shared_experts 2"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": ["chunked_attention"] * 3}, "chunked_attention"),
])
def test_what_is_not_built_is_refused_by_its_name(tmp_path, changes, named):
    with pytest.raises(ValueError, match=named):
        fields_of(tmp_path, **changes)


# -- what this layout cannot do yet, each by its message ------------------


def window_config(**changes):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    fields = dict(
        vocab=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=48,
        layer_types=("sliding", "sliding", "attention"), sliding_window=8,
        dtype=jnp.float32, remat=False,
    )
    fields.update(changes)
    return TransformerConfig(**fields)


def test_patterns_that_are_not_built_are_refused():
    with pytest.raises(ValueError, match="at least one full"):
        window_config(layer_types=("sliding",) * 3)
    with pytest.raises(ValueError, match="sliding_window >= 1"):
        window_config(sliding_window=0)
    with pytest.raises(ValueError, match="no conv layer"):
        window_config(layer_types=("sliding", "conv", "attention"))
    with pytest.raises(ValueError, match="one shared expert"):
        window_config(n_experts=4, n_shared_experts=2)
    with pytest.raises(ValueError, match="eva attention has no output gate"):
        window_config(
            layer_types=(), attention="eva", window_size=8, chunk_size=4,
            attention_gate=True,
        )


def test_prefix_sharing_and_every_migration_verb_are_refused():
    reason = "keeps the last 12 positions of its window attention layers"
    assert reason in LAYOUT.carries_state
    fake = Fake()
    with pytest.raises(ValueError, match="no prefill hand-off.*" + reason):
        PagedEngine(
            fake.prefill_chunk, fake.decode, 2, MAX_LEN, 64,
            page_tokens=PAGE, pages=48, chunk_tokens=CHUNK, layout=LAYOUT,
            handoff=lambda *a: None,
        )
    engine = PagedEngine(
        fake.prefill_chunk, fake.decode, 2, MAX_LEN, 64, page_tokens=PAGE,
        pages=48, chunk_tokens=CHUNK, layout=LAYOUT, prefix_cache=True,
        read_page=lambda p: {}, write_page=lambda p, d: None,
    )
    try:
        assert engine.stats()["prefix_cache"].startswith("off: a row of")
        for verb in (
            lambda: engine.freeze(1), lambda: engine.export_frozen(1),
            lambda: engine.splice(object()),
        ):
            with pytest.raises(MigrationError, match=reason):
                verb()
    finally:
        engine.stop()


def test_the_pool_refuses_pages_that_travel_int8_rings_and_a_mesh():
    import jax

    from dcos_commons_tpu.models import init_params
    from dcos_commons_tpu.models.decode import (
        init_paged_kv_cache,
        paged_decode_step,
    )
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config = window_config()
    params = init_params(config, jax.random.key(0))
    pool = PagedPoolModel(config, params, 2, 32, 4, pages=16, chunk_tokens=4)
    assert pool.layout.ring_pages == 3
    assert set(pool.cache) == {"k", "v", "k_window", "v_window"}
    assert pool.cache["k"].shape[:2] == (1, 17)
    assert pool.cache["k_window"].shape[:2] == (2, 2 * 3 + 1)
    with pytest.raises(ValueError, match="window attention layers in a ring"):
        pool.export_page(1)
    with pytest.raises(ValueError, match="window attention layers in a ring"):
        pool.import_page(1, {})
    with pytest.raises(ValueError, match="no int8 ring is built"):
        PagedPoolModel(
            config, params, 2, 32, 4, pages=16, chunk_tokens=4,
            kv_dtype="int8",
        )
    with pytest.raises(ValueError, match="the window layers' rings"):
        PagedPoolModel(
            config, params, 2, 32, 4, pages=16, chunk_tokens=4,
            cache_sharding=object(),
        )
    # a program handed no ring for a pattern that needs one, or one for
    # a pattern that has none
    cache = init_paged_kv_cache(config, 17, 4, slots=2, window_pages=7)
    with pytest.raises(ValueError, match="a ring of 0 pages a row"):
        paged_decode_step(
            config, params, cache, np.zeros(2, np.int32),
            np.zeros(2, np.int32), np.zeros((2, 11), np.int32),
        )


def test_the_training_forward_refuses_the_serving_only_parts():
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import forward, init_params

    tokens = jnp.zeros((1, 8), jnp.int32)
    config = window_config()
    with pytest.raises(NotImplementedError, match="has a serving path only"):
        forward(config, init_params(config, jax.random.key(0)), tokens)
    gated = window_config(layer_types=(), attention_gate=True)
    with pytest.raises(NotImplementedError, match="attention_gate"):
        forward(gated, init_params(gated, jax.random.key(0)), tokens)


def test_the_env_gives_the_geometry_and_refuses_what_cannot_serve(tmp_path):
    from dcos_commons_tpu.specification.specs import SpecError

    path = tmp_path / "model.json"
    path.write_text(json.dumps(FILE))
    env = {"MODEL_CONFIG": str(path), "MAX_LEN": "128", "SERVE_SLOTS": "3",
           "KV_PAGE_TOKENS": "16", "PREFILL_CHUNK_TOKENS": "32"}
    paged = paged_config_from_env(env)
    assert paged.layout.sliding_window == 32
    assert paged.layout.ring_pages == (32 + 32) // 16
    # KV_PAGES keeps meaning the history pool: unset, every slot a
    # whole row of it; the rings stand beside it
    assert paged.pages == 3 * 8 and paged.pages_per_row == 4 + 8
    assert paged.window_arena_pages == 3 * 4 + 1
    assert paged.prefix_cache is False
    with pytest.raises(SpecError, match="whole pages"):
        paged_config_from_env(dict(env, PREFILL_CHUNK_TOKENS="24"))
    # unset, the code chooses the chunk from the model: in whole pages
    chosen = paged_config_from_env(
        {k: v for k, v in env.items() if k != "PREFILL_CHUNK_TOKENS"}
    )
    assert chosen.chunk_tokens % 16 == 0
    assert chosen.layout.ring_pages == (32 + chosen.chunk_tokens) // 16
