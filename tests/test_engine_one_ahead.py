"""The engine loop one device call ahead (ISSUE 31): a prefill chunk
is fetched only when it is its prompt's last, and decode step N + 1
is dispatched before step N's tokens are read.

Two layers.  ORDER, against a recording device half
(``testing.chain_model.OneAhead`` around the chain fake): what was
dispatched and resolved when, which chunks were fetched, what the
counters count, and the chain oracle for every request mix.
EXACTNESS, through the real ``PagedPoolModel`` at toy size: greedy
rows equal ``models.decode.generate``, sampled rows equal the
synchronous loop's under the same seeds, rows that end by ``eos`` with
their next step in flight are cut where they should be, their pages
come home, and the next occupant of the slot and pages decodes
exactly.
"""

import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve import engine as engine_mod
from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.testing.chain_model import (
    ChainModel,
    OneAhead,
    V,
    chain_oracle,
    settled_stats,
    swarm,
)


def _engine(half, slots, pages, max_len=32, prompt_len=24, chunk=5,
            **kw):
    ahead = half.engine_kwargs() if isinstance(half, OneAhead) else {}
    return PagedEngine(
        half.prefill_chunk, half.decode, slots, max_len, prompt_len,
        page_tokens=4, pages=pages, chunk_tokens=chunk,
        prefix_cache=False, **ahead, **kw,
    )


def _eos_cuts(jobs):
    """Rows that end by ``eos`` at a decode step which is not also
    their last by ``n``: the loop could not know, so their next step
    was already queued and its sample is dropped."""
    cuts = 0
    for rows, n, eos in jobs:
        for row in rows:
            out = chain_oracle(row, n, eos)
            cuts += 2 <= len(out) < n and out[-1] == eos
    return cuts


# -- order ---------------------------------------------------------------


def test_next_step_is_dispatched_before_the_previous_is_resolved():
    half = OneAhead(ChainModel(slots=1))
    engine = _engine(half, slots=1, pages=8)
    try:
        prompt, n = [1, 2, 3], 8
        assert engine.submit([prompt], n) == [chain_oracle(prompt, n)]
        loop = engine.stats()["loop"]
    finally:
        engine.stop()
    steps = n - 1  # the first token is the prefill's
    assert half.steps == steps == loop["decode_calls"]
    log = half.log
    for k in range(steps - 1):
        assert log.index(("dispatch", k + 1)) < log.index(("resolve", k))
    # the last step's end was known by ``n``: nothing rode behind it
    assert log[-1] == ("resolve", steps - 1)
    assert loop["decode_ahead_calls"] == steps - 1
    assert loop["ahead_discarded_rows"] == 0
    # each step computed for the one row, at the position it wrote
    assert loop["decode_rows_sum"] == steps


def test_only_a_prompts_last_chunk_is_fetched():
    half = OneAhead(ChainModel(slots=2))
    engine = _engine(half, slots=2, pages=12)
    try:
        # one call, so both rows are admitted in one tick: two slots
        rows = [list(range(1, 14)), [7, 7]]
        assert engine.submit(rows, 3) == [chain_oracle(r, 3) for r in rows]
        loop = engine.stats()["loop"]
    finally:
        engine.stop()
    chunks = [e for e in half.log if e[0] == "chunk"]
    by_slot = {}
    for _, slot, start, fetched in chunks:
        by_slot.setdefault(slot, []).append((start, fetched))
    # 13 tokens in chunks of 5: two unfetched, the last fetched
    assert sorted(by_slot.values()) == [
        [(0, False), (5, False), (10, True)], [(0, True)],
    ]
    assert loop["prefill_calls"] == 4
    assert loop["prefill_unfetched_calls"] == 2


def test_a_device_half_that_cannot_carry_is_the_same_loop_at_depth_0():
    model = ChainModel(slots=2)
    engine = _engine(model, slots=2, pages=12)
    try:
        jobs = [([list(range(1, 14))], 6, None), ([[7, 7]], 9, 40)]
        results = swarm(engine, jobs)
        for (rows, n, eos), result in zip(jobs, results):
            assert result == [chain_oracle(r, n, eos) for r in rows]
        loop = engine.stats()["loop"]
    finally:
        engine.stop()
    assert loop["decode_calls"] == model.decode_calls > 0
    assert loop["decode_ahead_calls"] == 0
    assert loop["prefill_unfetched_calls"] == 0
    assert loop["ahead_discarded_rows"] == 0


def test_a_row_ended_by_eos_drops_the_step_queued_behind_it():
    """One slot, so the next request takes the slot and the pages the
    cut row's queued step still wrote into."""
    half = OneAhead(ChainModel(slots=1))
    engine = _engine(half, slots=1, pages=8)
    try:
        prompt, n = [2, 9, 4], 10
        full = chain_oracle(prompt, n)
        eos = full[4]
        cut = chain_oracle(prompt, n, eos)
        assert 2 <= len(cut) < n
        free0 = engine.stats()["kv_pages_free"]
        assert engine.submit([prompt], n, eos_id=eos) == [cut]
        after = [5, 6, 7, 8, 1]
        assert engine.submit([after], 9) == [chain_oracle(after, 9)]
        stats = settled_stats(engine)
    finally:
        engine.stop()
    assert stats["loop"]["ahead_discarded_rows"] == 1
    assert stats["kv_pages_free"] == free0 == 8
    assert stats["kv_pages_reserved"] == 0 and stats["active_slots"] == 0
    # the step behind the cut one WAS dispatched, and then resolved
    # with nobody to credit: the loop never parks on an open step
    steps = len(cut) - 1
    assert ("dispatch", steps) in half.log
    assert ("resolve", steps) in half.log


def test_one_ahead_property_any_request_mix_matches_oracle():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.given(
        st.lists(
            st.tuples(
                st.lists(
                    st.lists(st.integers(0, V - 1), min_size=1,
                             max_size=9),
                    min_size=1, max_size=3,
                ),
                st.integers(1, 8),
                st.one_of(st.none(), st.integers(0, V - 1)),
            ),
            min_size=1, max_size=6,
        ),
        st.integers(1, 4),   # slots
        st.integers(3, 10),  # pages (>= one worst-case request: 3)
        st.integers(1, 6),   # chunk width
    )
    @hypothesis.settings(
        max_examples=40, deadline=None,
        suppress_health_check=[hypothesis.HealthCheck.too_slow],
    )
    def run(jobs, slots, pages, chunk):
        max_len = 12
        jobs = [
            (rows, min(n, max_len - max(len(r) for r in rows)), eos)
            for rows, n, eos in jobs
        ]
        jobs = [j for j in jobs if j[1] >= 1]
        if not jobs:
            return
        half = OneAhead(ChainModel())
        engine = _engine(
            half, slots=slots, pages=pages, max_len=max_len,
            prompt_len=9, chunk=chunk,
        )
        try:
            results = swarm(engine, jobs)
            for (rows, n, eos), result in zip(jobs, results):
                assert result == [chain_oracle(r, n, eos) for r in rows]
            stats = settled_stats(engine)
            assert stats["active_slots"] == 0
            assert stats["queue_depth"] == 0
            assert stats["kv_pages_free"] == pages
            assert stats["kv_pages_reserved"] == 0
            engine._allocator.check_invariants()
            loop = stats["loop"]
            assert loop["ahead_discarded_rows"] == _eos_cuts(jobs)
            assert loop["decode_calls"] == half.steps
            # whatever was dispatched was resolved before the loop
            # parked
            assert half._outstanding is None and not engine._inflight
        finally:
            engine.stop()

    run()


@pytest.mark.parametrize("env,chosen", [
    # a dense model's 256; a mixture's 512; a short MAX_LEN's clamp
    ({"MAX_LEN": "1024"}, 256),
    ({"MAX_LEN": "1024", "N_EXPERTS": "8"}, 512),
    ({"MAX_LEN": "96", "N_EXPERTS": "8"}, 96),
])
def test_the_chosen_chunk_width_serves_what_64_serves(env, chosen):
    """The width the code chooses where the env states none
    (serve/paging.py) changes how many calls a prompt takes, never a
    token."""
    from dcos_commons_tpu.serve.paging import paged_config_from_env

    env = {**env, "KV_PAGE_TOKENS": "4", "SERVE_SLOTS": "3"}
    rng = np.random.default_rng(36)
    jobs = [
        ([list(rng.integers(0, V, size=n))], 5, None)
        for n in (3, 64, 65, 90, 17, 80)
    ]
    served, calls = {}, {}
    for stated in ("64", ""):
        paged = paged_config_from_env(
            {**env, "PREFILL_CHUNK_TOKENS": stated}
        )
        half = OneAhead(ChainModel())
        engine = _engine(
            half, slots=paged.slots, pages=paged.pages,
            max_len=paged.max_len, prompt_len=paged.max_len - 5,
            chunk=paged.chunk_tokens,
        )
        try:
            served[stated] = swarm(engine, jobs)
            calls[stated] = settled_stats(engine)["loop"]["prefill_calls"]
            width = engine.stats()["prefill_chunk_tokens"]
        finally:
            engine.stop()
        assert width == (64 if stated else chosen)
    assert served["64"] == served[""] == [
        [chain_oracle(rows[0], n, eos)] for rows, n, eos in jobs
    ]
    # 64-wide: 1 + 1 + 2 + 2 + 1 + 2 chunks; chosen: one a prompt
    assert (calls["64"], calls[""]) == (9, 6)


def test_stop_leaves_no_step_behind():
    half = OneAhead(ChainModel(slots=1))
    engine = _engine(half, slots=1, pages=16, max_len=64, prompt_len=8)
    t = threading.Thread(
        target=lambda: engine.submit([[1, 2]], 50), daemon=True
    )
    t.start()
    deadline = time.monotonic() + 10
    while half.steps < 3 and time.monotonic() < deadline:
        time.sleep(0.001)
    engine.stop()
    assert half.steps >= 3
    assert half._outstanding is None
    dispatched = [e[1] for e in half.log if e[0] == "dispatch"]
    resolved = [e[1] for e in half.log if e[0] == "resolve"]
    assert resolved == dispatched


class _FailsAt(ChainModel):
    """Raises from its ``at``-th decode call, once."""

    def __init__(self, at, **kw):
        super().__init__(**kw)
        self.at = at

    def decode(self, *args):
        if self.decode_calls == self.at:
            self.at = -1
            raise RuntimeError("device fell over")
        return super().decode(*args)


def test_a_failing_step_fans_out_and_leaves_no_handle_behind():
    half = OneAhead(_FailsAt(3, slots=2))
    engine = _engine(half, slots=2, pages=16)
    try:
        with pytest.raises(RuntimeError, match="fell over"):
            engine.submit([[1, 2, 3], [4, 5]], 12)
        # what was dispatched before the failing call was resolved
        assert [e for e in half.log if e[0] != "chunk"][-1] == \
            ("resolve", 2)
        stats = settled_stats(engine)
        assert half._outstanding is None and not engine._inflight
        assert stats["active_slots"] == 0 and stats["kv_pages_free"] == 16
        # and serves on, exactly
        prompt = [9, 9, 1]
        assert engine.submit([prompt], 7) == [chain_oracle(prompt, 7)]
    finally:
        engine.stop()


# -- exactness through the real pool ---------------------------------------

MAX_LEN, NEW = 48, 8
PROMPT_LEN = MAX_LEN - NEW
PROMPTS = [
    [1, 2, 3, 4],                             # shorter than a chunk
    [9, 8],
    [5, 6, 7, 2, 1],
    [3],
    [11, 12, 13, 14, 15, 16, 17, 2, 9],       # 9 tokens: 2 chunks
]


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, init_params

    config = TransformerConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=96, max_seq=64, dtype=jnp.float32, remat=False,
    )
    return config, init_params(config, jax.random.key(0))


def _oracle(config, params, prompt, n):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import generate

    out = generate(
        config, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=n,
    )
    return [int(t) for t in out[0]]


def _real(config, params, ahead, slots=3, pages=30, prefix=True):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    pool = PagedPoolModel(config, params, slots, MAX_LEN, 4, pages, 6)
    pool.warm()
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, slots, MAX_LEN, PROMPT_LEN,
        page_tokens=4, pages=pages, chunk_tokens=6,
        prefix_cache=prefix, queue_timeout_s=120,
        **({"resolve_decode_fn": pool.resolve_decode} if ahead else {}),
    )
    return pool, engine


def _staggered(engine, jobs):
    """``jobs`` of (prompt, n, temperature, eos), each from its own
    client, arriving 10 ms apart: admission mid-flight."""
    results = [None] * len(jobs)
    errors = []

    def client(i):
        prompt, n, temp, eos = jobs[i]
        try:
            results[i] = engine.submit(
                [prompt], n, temperature=temp, eos_id=eos
            )[0]
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(jobs))]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    return results


def test_real_pool_one_ahead_greedy_equals_generate(tiny):
    config, params = tiny
    pool, engine = _real(config, params, ahead=True)
    try:
        got = _staggered(engine, [(p, NEW, 0.0, None) for p in PROMPTS])
        assert got == [_oracle(config, params, p, NEW) for p in PROMPTS]
        engine._allocator.check_invariants()
        loop = engine.stats()["loop"]
        assert loop["decode_ahead_calls"] > 0
        assert loop["prefill_unfetched_calls"] >= 1  # the 9-token one
        assert loop["ahead_discarded_rows"] == 0     # no eos was sent
        # warm() ran every way the loop calls the two programs:
        # nothing compiled under traffic
        assert pool._decode_c._cache_size() == 1
        assert pool._prefill_c._cache_size() == 1
    finally:
        engine.stop()


def test_real_pool_one_ahead_sampled_rows_equal_the_synchronous_loop(
        tiny, monkeypatch):
    """A row's key is ``fold_in(key(seed), pos)``: with the seeds held
    equal, the loop one call ahead samples what the synchronous loop
    samples, whatever the admission order made of the slots."""
    config, params = tiny
    monkeypatch.setattr(
        engine_mod.os, "urandom", lambda n: (1234567).to_bytes(n, "little")
    )
    jobs = [
        (p, NEW, temp, None)
        for p, temp in zip(PROMPTS, (0.9, 0.0, 1.3, 0.7, 0.9))
    ]
    outs = []
    for ahead in (False, True):
        _pool, engine = _real(config, params, ahead=ahead, prefix=False)
        try:
            outs.append(_staggered(engine, jobs))
        finally:
            engine.stop()
    assert outs[0] == outs[1]
    # and it did sample: a greedy run differs somewhere
    assert outs[1] != [_oracle(config, params, p, NEW) for p in PROMPTS]


def test_real_pool_eos_with_the_next_step_in_flight(tiny):
    """Rows that end by ``eos`` mid-answer: a prefix of the whole
    generation, the dropped samples counted, every page home, and the
    slots' and pages' next occupants exact."""
    config, params = tiny
    full = [_oracle(config, params, p, NEW) for p in PROMPTS]
    pool, engine = _real(config, params, ahead=True, slots=2, pages=14,
                         prefix=False)
    try:
        free0 = engine.stats()["kv_pages_free"]
        jobs, want, cuts = [], [], 0
        for prompt, row in zip(PROMPTS, full):
            eos = row[3]
            cut = row[: row.index(eos) + 1]
            cuts += len(cut) >= 2  # ended at a decode step, not by n
            jobs.append((prompt, NEW, 0.0, eos))
            want.append(cut)
        assert _staggered(engine, jobs) == want
        stats = settled_stats(engine)
        assert stats["loop"]["ahead_discarded_rows"] == cuts > 0
        assert stats["kv_pages_free"] == free0
        assert stats["kv_pages_reserved"] == 0
        engine._allocator.check_invariants()
        # the next occupants of the freed slots and pages
        again = _staggered(
            engine, [(p, NEW, 0.0, None) for p in PROMPTS]
        )
        assert again == full
        assert engine.stats()["kv_pages_free"] == free0
        assert pool._decode_c._cache_size() == 1
    finally:
        engine.stop()


def test_pool_decode_refuses_a_synchronous_call_over_an_open_step(tiny):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config, params = tiny
    pool = PagedPoolModel(config, params, 2, MAX_LEN, 4, 8, 6)
    zeros = (
        np.zeros(2, np.int32), np.zeros(2, np.int32),
        np.zeros(2, np.float32), np.zeros(2, np.int32),
        np.zeros((2, pool.pages_per_row), np.int32),
    )
    assert pool.resolve_decode().size == 0
    assert pool.decode(*zeros, carry=np.zeros(2, bool)).size == 0
    with pytest.raises(RuntimeError, match="outstanding"):
        pool.decode(*zeros)
    first = pool.resolve_decode()
    assert first.shape == (2,) and pool.resolve_decode().size == 0
    # synchronous again: this step's own tokens, the same program
    assert np.array_equal(pool.decode(*zeros), first)
    assert pool.prefill_chunk(
        np.zeros((1, 6), np.int32), slot=0,
        table=np.zeros(pool.pages_per_row, np.int32), start=0,
        true_len=6, temp=0.0, seed=0, final=False,
    ) is None
