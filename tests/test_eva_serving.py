"""EVA attention on the serving path (models/decode.py, serve/paging.py
RowLayout, the engine) against the float32 reference
(dcos_commons_tpu/models/reference/eva.py), on seeded random weights at
a small size: window 32, chunk 4, hidden 64, 4 heads, 2 layers.

The prompt is prefilled in chunks and then decoded through the paged
arena, with the row's two-region table filled as the engine fills it
(``RowLayout.write_slots``: the ring's pages are allocated once and
written over in place when a window ends), and the logits of every
served position are held to the reference's full forward pass over the
whole sequence.
"""

import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.serve.migration import (
    InProcessTransport,
    MigrationError,
    SessionMigratedError,
    migrate_session,
)
from dcos_commons_tpu.serve.paging import (
    PageAllocator,
    RowLayout,
    paged_config_from_env,
    worst_case_pages,
)
from dcos_commons_tpu.testing.chain_model import settled_stats

WINDOW, CHUNK = 32, 4
MAX_LEN = 160
LAYOUT = RowLayout(CHUNK, WINDOW, CHUNK)
MODEL = dict(
    attention_class="eva", hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    vocab_size=320, rope_theta=100000.0, rms_norm_eps=1e-5,
    norm_add_unit_offset=True, tie_word_embeddings=False,
    num_pred_heads=8, window_size=WINDOW, chunk_size=CHUNK,
    init_std=0.5,
)
# Float32 on both sides, the same equations in another order of
# summation (pages and a joint softmax over two sets against whole
# windows): the largest difference seen over the cases below is 4e-6 on
# logits of magnitude 3-4, and a mask that is wrong by one key moves
# logits by 1e-2 and more.  2e-5 leaves five times the rounding seen;
# the same program in bfloat16 misses it by three orders of magnitude
# (test_bfloat16_would_fail_the_tolerance).
TOLERANCE = 2e-5


def _config(dtype=None):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab=320, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=96, rope_theta=100000.0, dtype=dtype or jnp.float32,
        remat=False, rms_norm_eps=1e-5, norm_unit_offset=True,
        tie_embeddings=False, n_pred_heads=8, attention="eva",
        window_size=WINDOW, chunk_size=CHUNK, eva_init_std=0.5,
    )


@pytest.fixture(scope="module")
def toy():
    """(config, float32 params): norm offsets drawn around zero, so
    that the unit offset and the epsilon both matter."""
    import jax

    from dcos_commons_tpu.models import init_params

    config = _config()
    params = init_params(config, jax.random.key(1))
    key = jax.random.key(2)
    for i, name in enumerate(("attn_norm", "mlp_norm")):
        leaf = params["layers"][name]
        params["layers"][name] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape, leaf.dtype
        )
    params["final_norm"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 2), params["final_norm"].shape
    )
    return config, params


class Row:
    """One row served by hand: the table is filled as the engine fills
    it, page numbers drawn in order from 1."""

    def __init__(self, config, params, n_pages=64, first_page=1):
        import jax

        from dcos_commons_tpu.models.decode import (
            init_paged_kv_cache,
            paged_decode_step,
            paged_prefill_chunk,
        )

        self.cache = init_paged_kv_cache(config, n_pages, CHUNK)
        self.table = np.zeros(LAYOUT.table_len(MAX_LEN), np.int32)
        self.next_page = first_page
        self.tokens = []
        self._prefill = jax.jit(
            lambda c, t, tb, s, n: paged_prefill_chunk(
                config, params, c, t, tb, s, n
            )
        )
        self._decode = jax.jit(
            lambda c, t, p, tb: paged_decode_step(config, params, c, t, p, tb)
        )

    def _ensure(self, first, last):
        for v in LAYOUT.write_slots(first, last):
            if self.table[v] == 0:
                self.table[v] = self.next_page
                self.next_page += 1

    def prefill(self, prompt, chunk_tokens):
        """Logits at the prompt's last position."""
        start = 0
        while start < len(prompt):
            n = min(chunk_tokens, len(prompt) - start)
            padded = np.zeros((1, chunk_tokens), np.int32)
            padded[0, :n] = prompt[start:start + n]
            self._ensure(start, start + n - 1)
            logits, self.cache, _ = self._prefill(
                self.cache, padded, self.table.copy(), start, n
            )
            start += n
        self.tokens = list(prompt)
        return np.asarray(logits[0])

    def decode(self, token):
        """Append ``token`` and return the logits behind it; the row
        rides slot 1 of two, slot 0 idle on the trash page."""
        pos = len(self.tokens)
        self.tokens.append(int(token))
        self._ensure(pos, pos)
        tables = np.zeros((2, len(self.table)), np.int32)
        tables[1] = self.table
        logits, self.cache, _ = self._decode(
            self.cache, np.array([0, token], np.int32),
            np.array([0, pos], np.int32), tables,
        )
        return np.asarray(logits[1])


def _served(config, params, prompt, n_new, chunk_tokens):
    """(tokens, logits [n_new, vocab]) of a greedy run through the
    paged path."""
    row = Row(config, params)
    out = [row.prefill(prompt, chunk_tokens)]
    for _ in range(n_new - 1):
        out.append(row.decode(int(np.argmax(out[-1]))))
    return row.tokens, np.stack(out).astype(np.float32)


def _reference(params, tokens, first_row):
    from dcos_commons_tpu.models.reference import eva

    return np.asarray(eva.logits(
        MODEL, params, np.asarray(tokens, np.int32),
        rows=np.arange(first_row, len(tokens)),
    ))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 320, n).tolist()


# (prompt length, new tokens, prefill chunk): window 32, chunk 4
CASES = {
    "a_inside_one_window": (20, 8, 8),
    "b_boundary_in_prefill": (40, 6, 8),
    "c_boundary_in_decode": (28, 10, 8),
    "d_three_boundaries": (60, 45, 8),
    "e_chunk_ends_on_last_prompt_byte": (16, 6, 8),
    "prefill_chunk_straddles_a_boundary": (70, 30, 24),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_prefill_then_decode_agrees_with_the_reference(toy, case):
    config, params = toy
    plen, n_new, chunk_tokens = CASES[case]
    tokens, got = _served(config, params, _prompt(plen), n_new, chunk_tokens)
    want = _reference(params, tokens, plen - 1)
    assert got.shape == want.shape == (n_new, 320)
    assert float(np.abs(got - want).max()) < TOLERANCE


def test_bfloat16_would_fail_the_tolerance(toy):
    """The tolerance separates precisions: the same program and weights
    in bfloat16 miss the float32 reference by far more."""
    import jax
    import jax.numpy as jnp

    _config32, params = toy
    plen, n_new, chunk_tokens = CASES["b_boundary_in_prefill"]
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    row = Row(_config(jnp.bfloat16), low)
    prompt = _prompt(plen)
    got = row.prefill(prompt, chunk_tokens).astype(np.float32)
    want = _reference(params, prompt, plen - 1)[0]
    assert float(np.abs(got - want).max()) > 100 * TOLERANCE


@pytest.mark.parametrize("fault", [
    "own_window_summaries_visible", "past_window_exact_keys_kept",
])
def test_a_wrong_mask_fails_the_agreement(toy, fault, monkeypatch):
    """What the agreement test is worth: make the summaries of the
    query's own window visible, or keep a past window's exact keys in
    sight, and the logits leave the tolerance."""
    import jax.numpy as jnp

    from dcos_commons_tpu.models import decode

    block = decode._softmax_block

    def wrong(carry, qg, keys, values, mask, scale):
        # a decode row's two sets: its window (32 entries) and the
        # summaries (40 entries at this MAX_LEN); prefill's blocks of 8
        # entries are left as they are
        if fault == "own_window_summaries_visible" and mask.shape[-1] == 40:
            # one window's worth of summaries more than is due
            mask = jnp.roll(mask, WINDOW // CHUNK, axis=-1)
            mask = mask.at[..., :WINDOW // CHUNK].set(True)
        elif fault == "past_window_exact_keys_kept" \
                and mask.shape[-1] == WINDOW:
            mask = jnp.ones_like(mask)
        return block(carry, qg, keys, values, mask, scale)

    monkeypatch.setattr(decode, "_softmax_block", wrong)
    config, params = toy
    # decode through the second window: the ring still holds the first
    # window's keys behind the current position, and the second
    # window's own summaries are written as its chunks end
    plen, n_new, chunk_tokens = 44, 12, 8
    tokens, got = _served(config, params, _prompt(plen), n_new, chunk_tokens)
    want = _reference(params, tokens, plen - 1)
    assert float(np.abs(got - want).max()) > 100 * TOLERANCE


# -- the row layout and the allocator ----------------------------------


def test_row_layout_by_hand():
    eva = RowLayout(16, 2048, 16)
    assert eva.table_len(32768) == 128 + 128
    assert RowLayout(16).table_len(32768) == 2048
    # position 2040..2055: the ring's last page, then its first again,
    # and the summary of chunk 127 (summary page 7)
    assert eva.write_slots(2040, 2055) == [127, 0, 128 + 7]
    assert eva.write_slots(15, 15) == [0, 128]
    assert eva.write_slots(14, 14) == [0]
    # a whole window and more touches every ring page once
    assert eva.write_slots(0, 5000)[:128] == list(range(128))
    assert eva.worst_case_pages(32768 - 1024, 1024) == 256
    # behind one shared window (8 summary pages): its ring is private
    assert eva.worst_case_pages(4096, 1, cached_pages=8) == 128 + 8
    assert eva.live_slots(2048 + 40) == [0, 1, 2] + list(range(128, 137))
    assert eva.live_slots(2048) == list(range(128, 136))
    assert (eva.entries(2047), eva.entries(2048), eva.entries(10000)) == (
        2047, 128, 1808 + 4 * 128
    )
    assert [eva.rollovers(*s) for s in ((0, 2047), (2047, 2048), (2048, 2048),
                                        (0, 4096))] == [0, 1, 1, 2]
    assert [eva.summaries(*s) for s in ((0, 14), (0, 15), (15, 15),
                                        (16, 47))] == [0, 1, 1, 2]
    full = RowLayout(16)
    for plen, new in ((1, 1), (100, 20), (16, 1), (17, 16)):
        assert full.worst_case_pages(plen, new) == worst_case_pages(
            plen, new, 16
        )
    assert full.entries(777) == 777 and full.rollovers(0, 9999) == 0
    with pytest.raises(ValueError):
        RowLayout(16, 2048, 8)       # a page is not a chunk
    with pytest.raises(ValueError):
        RowLayout(16, 2048 + 16, 16)  # not whole summary pages


def test_admission_admits_eight_rows_where_the_old_rule_admits_one():
    """KV_PAGES 2048 and MAX_LEN 32768: one row by the rule that keeps
    every token, eight by the two-region rule."""
    max_len, new, pages = 32768, 1024, 2048
    prompt = [7] * (max_len - new)

    def admitted(layout):
        alloc = PageAllocator(pages, 16, prefix_cache=False, layout=layout)
        count = 0
        while alloc.admit(prompt, new) is not None:
            count += 1
        alloc.check_invariants()
        return count

    assert admitted(None) == 1
    assert admitted(RowLayout(16, 2048, 16)) == 8


def test_paged_config_reads_the_layout_from_the_model_file(tmp_path):
    import json

    from dcos_commons_tpu.specification.specs import SpecError

    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(MODEL, window_size=2048, chunk_size=16)))
    env = {"MODEL_CONFIG": str(path), "MAX_LEN": "32768",
           "SERVE_SLOTS": "24", "KV_PAGES": "4096",
           "PREFILL_CHUNK_TOKENS": "512", "KV_PAGE_TOKENS": "16"}
    paged = paged_config_from_env(env)
    assert paged.layout == RowLayout(16, 2048, 16)
    assert paged.pages_per_row == 256
    # unset KV_PAGES: full residency by the layout's own table
    assert paged_config_from_env(
        {k: v for k, v in env.items() if k != "KV_PAGES"}
    ).pages == 24 * 256
    with pytest.raises(SpecError):   # not whole chunks
        paged_config_from_env(dict(env, PREFILL_CHUNK_TOKENS="500"))
    with pytest.raises(SpecError):   # a page that is not a chunk
        paged_config_from_env(dict(env, KV_PAGE_TOKENS="32"))
    with pytest.raises(SpecError):   # cannot hold one MAX_LEN row
        paged_config_from_env(dict(env, KV_PAGES="255"))
    assert paged_config_from_env(
        {k: v for k, v in env.items() if k != "MODEL_CONFIG"}
    ).pages_per_row == 2048


def test_config_from_a_file_wins_over_the_size_names(tmp_path):
    import json

    from dcos_commons_tpu.models import config_from_env

    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(MODEL, head_dim=16, model_type="x")))
    config = config_from_env({
        "MODEL_CONFIG": str(path), "VOCAB": "8192", "D_MODEL": "512",
        "N_LAYERS": "4",
    })
    assert (config.vocab, config.d_model, config.n_layers) == (320, 64, 2)
    assert (config.attention, config.window_size, config.chunk_size) == (
        "eva", WINDOW, CHUNK
    )
    assert config.rope_theta == 100000.0 and config.rms_norm_eps == 1e-5
    assert config.norm_unit_offset and not config.tie_embeddings
    assert config.n_pred_heads == 8 and config.eva_init_std == 0.5
    # the eight names alone build what they always built
    plain = config_from_env({"VOCAB": "64", "D_MODEL": "32"})
    assert (plain.vocab, plain.attention, plain.tie_embeddings) == (
        64, "gqa", True
    )
    # a head_dim that is not hidden_size / heads is taken as stated
    # (PR 44: the heads need not fill the hidden size)
    path.write_text(json.dumps(dict(MODEL, head_dim=32)))
    stated = config_from_env({"MODEL_CONFIG": str(path)})
    assert stated.head_dim == 32 != stated.d_model // stated.n_heads
    path.write_text(json.dumps(dict(MODEL, attention_class="latent")))
    with pytest.raises(ValueError):
        config_from_env({"MODEL_CONFIG": str(path)})


# -- through the pool and the engine ------------------------------------


def _pod(config, params, slots=3, pages=80, chunk_tokens=8, step_s=0.0,
         prefix=True, ahead=False):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    pool = PagedPoolModel(
        config, params, slots, MAX_LEN, CHUNK, pages, chunk_tokens
    )
    pool.warm()
    assert pool.layout == LAYOUT

    def decode(*args, **kwargs):
        time.sleep(step_s)
        return pool.decode(*args, **kwargs)

    engine = PagedEngine(
        pool.prefill_chunk, decode, slots, MAX_LEN, MAX_LEN - 48,
        page_tokens=CHUNK, pages=pages, chunk_tokens=chunk_tokens,
        prefix_cache=prefix, layout=pool.layout, queue_timeout_s=120,
        read_page=pool.export_page, write_page=pool.import_page,
        # the loop one call ahead of the pool (ISSUE 31)
        **({"resolve_decode_fn": pool.resolve_decode} if ahead else {}),
    )
    return pool, engine


def _private_pages(engine):
    with engine._cv:
        rows = [r for r in engine._rows if r is not None]
        rows += list(engine._prefilling)
        return [p for r in rows for p in r.private_pages]


@pytest.mark.parametrize("ahead", [False, True],
                         ids=["sync", "one-ahead"])
def test_engine_serves_rows_across_windows_with_ring_reuse(toy, ahead):
    """Three rows in flight, each crossing one to three window ends:
    the engine's tables (ring pages reused in place, summary pages
    appended) give the tokens of the row served by hand, the
    allocator's invariants hold while rows are live and after, and the
    counters count what happened; one call ahead (a window's first
    position queued before its last one's token is read) as
    synchronously."""
    config, params = toy
    jobs = [(_prompt(40, 1), 30), (_prompt(70, 2), 40), (_prompt(9, 3), 45)]
    # the first n - 1 tokens of each answer, served by hand
    want = [
        _served(config, params, p, n, 8)[0][len(p):] for p, n in jobs
    ]
    _pool, engine = _pod(config, params, step_s=0.002, prefix=False,
                         ahead=ahead)
    try:
        results, errors = [None] * len(jobs), []

        def client(i):
            try:
                results[i] = engine.submit([jobs[i][0]], jobs[i][1])[0]
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        seen_live = 0
        while any(t.is_alive() for t in threads):
            with engine._cv:
                private = [
                    p for r in list(engine._rows) + list(engine._prefilling)
                    if r is not None for p in r.private_pages
                ]
                engine._allocator.check_invariants(private)
                # no row ever holds more than its worst case
                for r in engine._rows:
                    if r is not None and r.table is not None:
                        assert np.count_nonzero(r.table) <= \
                            LAYOUT.worst_case_pages(len(r.tokens), r.n)
            seen_live += bool(private)
            time.sleep(0.005)
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        # a sampled token is the argmax of logits that agree to 4e-6:
        # the engine's run and the by-hand run are one program
        assert [r[:-1] for r in results] == want
        assert [len(r) for r in results] == [n for _p, n in jobs]
        assert seen_live > 0
        engine._allocator.check_invariants()
        stats = engine.stats()
        assert stats["kv_pages_free"] == 80 and stats["kv_live_tokens"] == 0
        loop = stats["loop"]
        ends = [len(p) + n - 1 for p, n in jobs]   # positions written
        assert loop["window_rollovers"] == sum(
            LAYOUT.rollovers(0, e - 1) for e in ends
        )
        assert loop["summary_entries_written"] == sum(
            LAYOUT.summaries(0, e - 1) for e in ends
        )
        # each decode call counted its own rows and what they read:
        # a row's first token comes from its prefill, every other one
        # from a decode call at the position that token is written to
        assert loop["decode_rows_sum"] == sum(n - 1 for _p, n in jobs)
        assert loop["decode_entries_sum"] == sum(
            LAYOUT.entries(pos)
            for p, n in jobs for pos in range(len(p), len(p) + n - 1)
        )
        # every end was known by ``n`` before it was read
        assert loop["ahead_discarded_rows"] == 0
        assert bool(loop["decode_ahead_calls"]) == ahead
        assert bool(loop["prefill_unfetched_calls"]) == ahead
    finally:
        engine.stop()


def _held_to_the_reference(params, prompt, out):
    """The served tokens are the reference's own first choices, read
    teacher-forced over the whole sequence (where its first choice
    leads by more than the rounding between the two)."""
    want = _reference(params, prompt + out[:-1], len(prompt) - 1)
    assert want.shape[0] == len(out)
    top = np.sort(want, axis=-1)
    clear = top[:, -1] - top[:, -2] > 10 * TOLERANCE
    assert clear.mean() > 0.9
    assert (np.argmax(want, axis=-1) == np.asarray(out))[clear].all()


def test_fences_while_a_window_ends_one_ahead_match_the_reference(toy):
    """A row is frozen, exported and released again and again while it
    crosses three window ends one call ahead.  A step queued behind
    the one in flight may already have written a new window's first
    position over the ring: so a fence waits for what is in flight and
    applies it, and the tokens stay the float32 reference's."""
    config, params = toy
    prompt, n = _prompt(44, 11), 100
    _pool, engine = _pod(config, params, step_s=0.001, prefix=False,
                         ahead=True)
    try:
        result = {}
        t = threading.Thread(target=lambda: result.update(
            out=engine.submit([prompt], n)[0]
        ))
        t.start()
        fences, ends_seen = 0, set()
        while t.is_alive():
            sess = engine.sessions()
            if not sess or sess[0]["state"] != "decode":
                time.sleep(0.001)
                continue
            rid = sess[0]["rid"]
            try:
                engine.freeze(rid)
                snap = engine.export_frozen(rid)
            except MigrationError:  # the row finished first
                break
            assert snap.kv_end == len(prompt) + len(snap.out) - 1
            assert sorted(v for v, _ in snap.pages) == LAYOUT.live_slots(
                snap.kv_end
            )
            ends_seen.add(snap.kv_end // WINDOW)
            fences += 1
            engine.unfreeze(rid)
            time.sleep(0.004)
        t.join(timeout=60)
        out = result["out"]
        assert len(out) == n and fences >= 5 and len(ends_seen) >= 3
        _held_to_the_reference(params, prompt, out)
        stats = engine.stats()
        assert stats["loop"]["ahead_discarded_rows"] == 0
        assert stats["loop"]["window_rollovers"] == LAYOUT.rollovers(
            0, len(prompt) + n - 2
        )
        engine._allocator.check_invariants()
    finally:
        engine.stop()


def test_eos_at_a_window_end_with_the_next_window_already_queued(toy):
    """The row's last token is read while the step behind it, the
    first position of the NEXT window, is queued: that step writes
    over the ring's first page and is dropped.  The answer is cut at
    the eos, the pages come home, and the next occupant of the one
    slot and of those pages serves the reference's tokens."""
    config, params = toy
    prompt = _prompt(40, 21)
    full = _served(config, params, prompt, 40, 8)[0][len(prompt):]
    j = 2 * WINDOW - len(prompt)        # the token at position 64
    eos = full[j]
    assert eos not in full[:j]
    _pool, engine = _pod(config, params, slots=1, pages=30, prefix=False,
                         ahead=True)
    try:
        free0 = engine.stats()["kv_pages_free"]
        got = engine.submit([prompt], 40, eos_id=eos)[0]
        assert got == full[:j + 1]
        stats = settled_stats(engine)
        assert stats["loop"]["ahead_discarded_rows"] == 1
        # the dropped step crossed the window's end
        assert stats["loop"]["window_rollovers"] == LAYOUT.rollovers(
            0, len(prompt) + j
        )
        assert stats["kv_pages_free"] == free0
        after = _prompt(50, 22)
        out = engine.submit([after], 30)[0]
        _held_to_the_reference(params, after, out)
        engine._allocator.check_invariants()
        assert engine.stats()["kv_pages_free"] == free0
    finally:
        engine.stop()


def test_stats_count_entries_and_the_positions_they_stand_for(toy):
    config, params = toy
    _pool, engine = _pod(config, params, step_s=0.01, prefix=False)
    try:
        result = {}
        t = threading.Thread(target=lambda: result.update(
            out=engine.submit([_prompt(70, 4)], 40)
        ))
        t.start()
        pairs = []
        while t.is_alive():
            s = engine.stats()
            if s["active_slots"]:
                pairs.append((s["kv_live_tokens"], s["context_live_tokens"]))
            time.sleep(0.01)
        t.join(timeout=60)
        assert pairs
        for entries, positions in pairs:
            assert entries == LAYOUT.entries(positions) < positions
    finally:
        engine.stop()


def test_whole_window_prefix_hit_is_token_identical(toy):
    """A second request with the same first 70 bytes pins the first
    TWO windows' summary pages (a hit is whole windows: 64 positions =
    4 summary pages), prefills from position 64 on, and serves the
    tokens of the cold path."""
    config, params = toy
    prompt = _prompt(70, 5)
    _pool, engine = _pod(config, params)
    try:
        cold = engine.submit([prompt], 12)[0]
        before = engine.stats()["prefix_cache_hits"]
        warm = engine.submit([prompt + [5, 6]], 12)[0]
        stats = engine.stats()
        assert stats["prefix_cache_hits"] - before == 4
        engine._allocator.check_invariants()
        again = engine.submit([prompt], 12)[0]
        assert again == cold
    finally:
        engine.stop()
    tokens, _ = _served(config, params, prompt + [5, 6], 12, 8)
    assert warm[:-1] == tokens[72:]


def test_export_then_import_mid_window_reproduces_the_next_logits(toy):
    """A row frozen in the middle of its third window: the entries a
    later step can read (``live_slots``: the current window's ring
    pages and every summary page) are exported from one arena and
    imported at OTHER page numbers of another, and the next logits are
    bit for bit the ones the first arena gives."""
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config, params = toy
    here = Row(config, params, n_pages=64)
    logits = here.prefill(_prompt(60, 6), 8)
    for _ in range(14):                      # positions 60..73
        logits = here.decode(int(np.argmax(logits)))
    kv_end = len(here.tokens)
    assert kv_end // WINDOW == 2 and kv_end % WINDOW

    src = PagedPoolModel(config, params, 2, MAX_LEN, CHUNK, 63, 8)
    dst = PagedPoolModel(config, params, 2, MAX_LEN, CHUNK, 63, 8)
    src.cache = here.cache
    there = Row(config, params, n_pages=64, first_page=40)
    live = LAYOUT.live_slots(kv_end)
    dead_ring = [v for v in range(LAYOUT.window_pages) if v not in live]
    assert dead_ring and all(here.table[v] for v in dead_ring)
    for v in live:
        there.table[v] = there.next_page
        there.next_page += 1
        dst.import_page(int(there.table[v]), src.export_page(int(here.table[v])))
    there.cache, there.tokens = dst.cache, list(here.tokens)
    nxt = int(np.argmax(logits))
    for _ in range(12):                      # through the next window's end
        a, b = here.decode(nxt), there.decode(nxt)
        assert np.array_equal(a, b)
        nxt = int(np.argmax(a))


@pytest.mark.parametrize("ahead", [False, True],
                         ids=["sync", "one-ahead"])
def test_migration_of_a_windowed_row_between_engines(toy, ahead):
    """The fenced cutover protocol over an EVA row past its first
    window: the destination finishes the source's own continuation, the
    snapshot carries no dead ring page, and a pool of another layout
    refuses it.  One call ahead the fence finds a step in flight on
    the source, and the destination resumes one call ahead."""
    config, params = toy
    prompt, n = _prompt(50, 7), 40
    want = _served(config, params, prompt, n, 8)[0][len(prompt):]
    _sp, src = _pod(config, params, step_s=0.01, ahead=ahead)
    _dp, dst = _pod(config, params, ahead=ahead)
    other = PagedEngine(
        lambda *a, **k: 0, lambda *a, **k: np.zeros(3, np.int32), 3,
        MAX_LEN, MAX_LEN - 48, page_tokens=CHUNK, pages=80, chunk_tokens=8,
        read_page=lambda p: {}, write_page=lambda p, d: None,
    )
    try:
        result = {}

        def client():
            try:
                result["r"] = src.submit([prompt], n)
            except BaseException as e:  # noqa: BLE001 — the assertion target
                result["r"] = e

        t = threading.Thread(target=client, daemon=True)
        t.start()
        deadline = time.monotonic() + 30
        rid = None
        while time.monotonic() < deadline and rid is None:
            sess = src.sessions()
            if sess and sess[0]["state"] == "decode" \
                    and src.stats()["tokens_out"] >= 20:
                rid = sess[0]["rid"]
            time.sleep(0.002)
        assert rid is not None
        src.freeze(rid)
        snap = src.export_frozen(rid)
        assert (snap.window, snap.chunk) == (WINDOW, CHUNK)
        assert snap.kv_end >= 2 * WINDOW
        assert sorted(v for v, _ in snap.pages) == LAYOUT.live_slots(
            snap.kv_end
        )
        with pytest.raises(Exception, match="row layout mismatch"):
            other.splice(snap)
        record = migrate_session(
            src, dst, rid, dest_name="dst", transport=InProcessTransport(),
            already_frozen=True,
        )
        assert record.ok and record.stage == "release"
        t.join(timeout=30)
        assert isinstance(result["r"], SessionMigratedError), result["r"]
        out = dst.collect(result["r"].dest_rid, timeout=60)
        assert out[:-1] == want and len(out) == n
        src._allocator.check_invariants(_private_pages(src))
        dst._allocator.check_invariants(_private_pages(dst))
    finally:
        src.stop()
        dst.stop()
        other.stop()


def test_the_slot_pool_and_the_training_forward_refuse_eva(toy):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import forward, prefill

    config, params = toy
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError):
        prefill(config, params, tokens, 16)
    with pytest.raises(NotImplementedError):
        forward(config, params, tokens)


# -- the decode kernel (ops/eva_decode.py) ------------------------------


def test_the_decode_kernel_reads_what_the_gather_path_reads(toy, monkeypatch):
    """The Pallas kernel, interpreted here, against the XLA path (gather
    the whole table, ``_softmax_block`` over its two regions) on one row through two window
    ends beside an idle slot: the same logits to rounding, and the
    agreement with the reference holds with the kernel in the path."""
    from dcos_commons_tpu.models import decode

    config, params = toy
    plen, n_new, chunk_tokens = 44, 30, 8
    tokens, plain = _served(config, params, _prompt(plen), n_new, chunk_tokens)
    monkeypatch.setattr(
        decode, "decode_attention_kernel", lambda config, cache: "interpret"
    )
    row = Row(config, params)
    out = [row.prefill(tokens[:plen], chunk_tokens)]
    for token in tokens[plen:]:
        out.append(row.decode(token))
    kernel = np.stack(out).astype(np.float32)
    assert float(np.abs(kernel - plain).max()) < TOLERANCE
    want = _reference(params, tokens, plen - 1)
    assert float(np.abs(kernel - want).max()) < TOLERANCE


def test_the_kernels_step_is_stated(toy, monkeypatch):
    """``/stats`` ``model.decode_attention_step`` under the one name an
    EVA program holds the kernel by."""
    import jax

    from dcos_commons_tpu.models import decode
    from dcos_commons_tpu.ops.paged_decode import walk_step

    config, _params = toy
    cache = decode.init_paged_kv_cache(config, 8, CHUNK)
    assert decode.decode_attention_step(config, cache) == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode.decode_attention_step(config, cache) == {
        "eva_decode_attention": walk_step(cache["k"]),
    }


def test_live_pages_by_hand():
    import jax.numpy as jnp

    from dcos_commons_tpu.ops.eva_decode import live_pages

    # window 32, chunk and page 4: 8 ring entries, then summary pages
    tables = jnp.arange(100, 118, dtype=jnp.int32)[None].repeat(3, 0)
    pos = jnp.asarray([0, 37, 95], jnp.int32)
    ids, n_ring, n_pages, n_win, n_sum = live_pages(tables, pos, 32, 4, 4)
    assert n_win.tolist() == [1, 6, 32] and n_sum.tolist() == [0, 8, 16]
    assert n_ring.tolist() == [1, 2, 8] and n_pages.tolist() == [1, 4, 12]
    assert ids[0, :1].tolist() == [100]
    assert ids[1, :4].tolist() == [100, 101, 108, 109]
    assert ids[2, :12].tolist() == list(range(100, 108)) + [108, 109, 110, 111]


# -- a decode step riding a prefill chunk (ISSUE 40) --------------------

# (the prefilling row's chunk width, where its next chunk starts, how
# many of the chunk's positions are real)
RIDER_CHUNKS = {
    "inside_a_window": (8, 8, 8),
    "short_last_chunk": (8, 16, 5),
    "straddles_a_windows_end": (24, 24, 24),
}


def _pool_by_hand(config, params, chunk_tokens, start, path, monkeypatch):
    """Four slots over one arena, by hand: slot 1 decodes at the last
    position of a chunk that is also its window's last (its step pools
    a summary and the next one begins a window), slot 2 in the middle
    of its third window, slots 0 and 3 idle; a fifth row, outside the
    decode set as the engine keeps it, has been prefilled up to
    ``start``.  Returns (the shared cache, the prefilling row, the
    decode step ``(token, pos, tables)``, the two programs)."""
    import jax

    from dcos_commons_tpu.models import decode

    if path == "interpret":
        monkeypatch.setattr(
            decode, "decode_attention_kernel",
            lambda config, cache: "interpret",
        )
    rows, cache = [], None
    for i, plen in enumerate((31, 75, start)):
        row = Row(config, params, n_pages=128, first_page=1 + 40 * i)
        if cache is not None:
            row.cache = cache
        if plen:
            row.prefill(_prompt(plen, 10 + i), chunk_tokens)
        cache = row.cache
        rows.append(row)
    a, b, filling = rows
    token = np.zeros(4, np.int32)
    pos = np.zeros(4, np.int32)
    tables = np.zeros((4, len(a.table)), np.int32)
    for slot, row in ((1, a), (2, b)):
        at = len(row.tokens)
        row._ensure(at, at)
        token[slot], pos[slot], tables[slot] = 7 + slot, at, row.table
    chunk = jax.jit(
        lambda c, t, tb, s, n, riders=None: decode.paged_prefill_chunk(
            config, params, c, t, tb, s, n, 0, riders
        )
    )
    step = jax.jit(
        lambda c, t, p, tb: decode.paged_decode_step(
            config, params, c, t, p, tb
        )
    )
    return cache, filling, (token, pos, tables), chunk, step


def _next_chunk(filling, chunk_tokens, start, true_len):
    padded = np.zeros((1, chunk_tokens), np.int32)
    padded[0, :true_len] = _prompt(true_len, 99)
    filling._ensure(start, start + true_len - 1)
    return padded, filling.table.copy(), start, true_len


def _arenas_agree(got, want, but_page=None):
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if but_page is not None:
            a, b = np.delete(a, but_page, 1), np.delete(b, but_page, 1)
        assert float(np.abs(a - b).max()) < TOLERANCE, name


@pytest.mark.parametrize("path", ["gather", "interpret"])
@pytest.mark.parametrize("case", sorted(RIDER_CHUNKS))
def test_a_chunk_with_riders_is_the_chunk_then_the_step(
        toy, case, path, monkeypatch):
    """One program for both leaves the arena, the chunk's logits and
    the riders' logits as the chunk's program followed by the decode
    step's leaves them, on either attention path of the riders."""
    config, params = toy
    chunk_tokens, start, true_len = RIDER_CHUNKS[case]
    cache, filling, riders, chunk, step = _pool_by_hand(
        config, params, chunk_tokens, start, path, monkeypatch
    )
    args = _next_chunk(filling, chunk_tokens, start, true_len)
    want_first, then, _ = chunk(cache, *args)
    want_rows, want_cache, _ = step(then, *riders)
    first, got_cache, counts, rows = chunk(cache, *args, riders=riders)
    assert counts is None
    assert first.shape == (1, 320) and rows.shape == (4, 320)
    assert float(np.abs(first - want_first).max()) < TOLERANCE
    assert float(np.abs(rows - want_rows).max()) < TOLERANCE
    _arenas_agree(got_cache, want_cache)
    # and it did something: both rows' pages, and the summary slot 1's
    # step finishes, are not what they were
    for name in ("k", "v"):
        before, after = np.asarray(cache[name]), np.asarray(got_cache[name])
        _token, pos, tables = riders
        for slot in (1, 2):
            ring = tables[slot][(pos[slot] % WINDOW) // CHUNK]
            assert np.abs(after[:, ring] - before[:, ring]).max() > 0
        summary = tables[1][LAYOUT.window_pages + (pos[1] // CHUNK) // CHUNK]
        assert np.abs(after[:, summary] - before[:, summary]).max() > 0


@pytest.mark.parametrize("path", ["gather", "interpret"])
def test_idle_riders_change_nothing_but_the_trash_page(
        toy, path, monkeypatch):
    config, params = toy
    cache, filling, riders, chunk, _step = _pool_by_hand(
        config, params, 8, 8, path, monkeypatch
    )
    args = _next_chunk(filling, 8, 8, 8)
    want_first, want_cache, _ = chunk(cache, *args)
    idle = tuple(np.zeros_like(a) for a in riders)
    first, got_cache, _, _rows = chunk(cache, *args, riders=idle)
    assert float(np.abs(first - want_first).max()) < TOLERANCE
    _arenas_agree(got_cache, want_cache, but_page=0)


def test_a_family_without_the_mixed_layer_refuses_riders():
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, decode

    config = TransformerConfig(
        vocab=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
        d_ff=64, dtype=jnp.float32, remat=False,
    )
    assert not decode.chunk_carries_riders(config)
    with pytest.raises(NotImplementedError, match="riders|beside a chunk"):
        decode.paged_prefill_chunk(
            config, {}, {}, jnp.zeros((1, 4), jnp.int32),
            jnp.zeros(4, jnp.int32), 0, 4, 0,
            (jnp.zeros(2, jnp.int32),) * 3,
        )


@pytest.mark.parametrize("riders", [False, True],
                         ids=["plain-chunk", "rider-chunk"])
def test_warm_traces_each_program_once(toy, riders):
    """Every way the loop calls the two programs is warmed, riders or
    not: serving after ``warm()`` compiles nothing."""
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config, params = toy
    pool = PagedPoolModel(
        config, params, 3, MAX_LEN, CHUNK, 40, 8, riders=riders
    )
    assert pool.chunk_riders is riders
    pool.warm()
    assert pool._prefill_c._cache_size() == 1
    assert pool._decode_c._cache_size() == 1
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, 3, MAX_LEN, MAX_LEN - 48,
        page_tokens=CHUNK, pages=40, chunk_tokens=8, layout=pool.layout,
        queue_timeout_s=120, resolve_decode_fn=pool.resolve_decode,
        chunk_riders=pool.chunk_riders,
    )
    # one call, so both rows are admitted in one tick: the short
    # prompt decodes while the long one is still prefilling
    prompts = [_prompt(6, 0), _prompt(50, 1)]
    try:
        outs = engine.submit(prompts, 12)
        loop = settled_stats(engine)["loop"]
    finally:
        engine.stop()
    assert [len(out) for out in outs] == [12, 12]
    # the short prompt's one chunk had nobody to carry; each of the
    # long prompt's seven carried the short row's next step
    assert loop["prefill_calls"] == 8
    assert loop["prefill_rider_calls"] == (7 if riders else 0)
    assert pool._prefill_c._cache_size() == 1
    assert pool._decode_c._cache_size() == 1
    # the same tokens with and without riders: the reference's
    for prompt, out in zip(prompts, outs):
        _held_to_the_reference(params, prompt, out)
