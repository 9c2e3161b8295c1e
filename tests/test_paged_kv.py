"""Paged KV serving (ISSUE 11): allocator/prefix-cache soundness and
the engine's greedy equivalence to whole-batch ``generate``.

Three layers of coverage:

* ALLOCATOR properties (no jax): page conservation, no double-free,
  reservation soundness (``reserved <= available`` so an admitted
  request can never OOM mid-generation), refcounted prefix entries
  freed only at refcount zero, leaf-first LRU eviction — held across
  randomized admit/alloc/register/retire/abandon sequences by a
  hypothesis sweep calling ``check_invariants`` after every op.

* ENGINE properties against a deterministic fake model: chunked
  prefill reproduces the oracle chain for any prompt length / chunk
  width, page-budget exhaustion queues (FIFO) and completes, the
  budget-starved 503 carries the distinct kv-page-budget reason and
  lands in the requests_timed_out_memory split, and — the
  copy-on-write contract — no physical page is ever written after it
  was published into the prefix cache.

* REAL-MODEL equivalence (tiny flagship on CPU): tokens produced by
  the paged engine — chunked prefill, page-table attention, prefix-
  cache hits, mixed chunked/unchunked admission — are IDENTICAL to
  whole-batch ``generate`` on the same prompts, including through the gang driver's paged broadcast protocol
  executed for real in a single-process gang sim.
"""

import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.serve.engine import PagedEngine, QueueTimeoutError
from dcos_commons_tpu.serve.paging import (
    PageAllocator,
    paged_config_from_env,
    worst_case_pages,
)
from dcos_commons_tpu.testing.chain_model import (
    ChainModel as FakePagedModel,
    V as _V,
    chain_first as _chain_first,
    chain_next as _chain_next,
    chain_oracle as _chain_oracle,
    swarm as _swarm,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- allocator unit + property coverage --------------------------------


def test_allocator_admit_reserve_alloc_retire_roundtrip():
    a = PageAllocator(pages=8, page_tokens=4)
    adm = a.admit([1, 2, 3, 4, 5], max_new=4)  # worst: ceil(8/4) = 2
    assert adm is not None and adm.reserve_left == 2
    assert a.reserved_pages == 2
    pages = [a.alloc(adm), a.alloc(adm)]
    assert a.reserved_pages == 0
    with pytest.raises(RuntimeError):
        a.alloc(adm)  # past the worst case: engine bug, loud
    a.retire(adm, pages)
    assert a.free_pages == 8 and a.reserved_pages == 0
    a.check_invariants()


def test_allocator_admission_denied_when_budget_reserved():
    a = PageAllocator(pages=4, page_tokens=4)
    adm = a.admit([1] * 4, max_new=13)  # worst: ceil(16/4) = 4 pages
    assert adm is not None
    assert a.admit([2], max_new=1) is None  # 1 page needed, 0 left
    assert not a.would_admit([2], max_new=1)
    a.retire(adm, [])
    assert a.would_admit([2], max_new=1)


def test_allocator_double_free_and_foreign_free_raise():
    a = PageAllocator(pages=4, page_tokens=2)
    adm = a.admit([1, 2], max_new=2)
    page = a.alloc(adm)
    a.free_page(page)
    with pytest.raises(RuntimeError, match="double free"):
        a.free_page(page)
    with pytest.raises(RuntimeError):
        a.free_page(0)  # the trash page is never owned


def test_prefix_chain_register_match_refcount_and_leaf_eviction():
    a = PageAllocator(pages=6, page_tokens=2)
    adm = a.admit([1, 2, 3, 4, 9], max_new=2)  # matches nothing yet
    p1, p2 = a.alloc(adm), a.alloc(adm)
    assert a.register(adm, (1, 2), p1)
    assert a.register(adm, (3, 4), p2)
    # registered pages are cache-owned: a private free must refuse
    with pytest.raises(RuntimeError, match="prefix cache"):
        a.free_page(p1)
    a.retire(adm, [])
    # zero refs: the WHOLE chain is reclaimable (refcounts are
    # monotone down a chain, so leaf-first eviction reaches it all)
    assert a.cached_pages == 2 and a.reclaimable_pages == 2
    # a second identical prefix pins the chain (refs > 0 again)
    adm2 = a.admit([1, 2, 3, 4, 9], max_new=2)
    assert adm2.cached_pages == 2
    assert a.reclaimable_pages == 0
    a.retire(adm2, [])
    # more than free + reclaimable can ever supply: denied outright
    assert a.admit([5, 5], max_new=13) is None  # worst: 7 > 6
    # eviction under pressure: the LEAF (3,4) goes first, then (1,2)
    adm3 = a.admit([7, 8], max_new=11)  # worst: ceil(12/2) = 6 pages
    assert adm3 is not None  # 4 free + 2 reclaimable = 6
    held = [a.alloc(adm3) for _ in range(6)]
    assert a.cached_pages == 0  # both entries evicted, leaf first
    assert a.evictions == 2
    a.retire(adm3, held)
    a.check_invariants()


def test_register_duplicate_key_keeps_page_private_and_closes_chain():
    a = PageAllocator(pages=8, page_tokens=2)
    adm1 = a.admit([1, 2, 3, 4, 5], max_new=2)
    q1, q2 = a.alloc(adm1), a.alloc(adm1)
    assert a.register(adm1, (1, 2), q1)
    assert a.register(adm1, (3, 4), q2)
    # a concurrent identical prompt that matched NOTHING (admitted
    # before registration) tries to publish the same keys
    a2 = PageAllocator(pages=8, page_tokens=2)  # fresh: simulate race
    adm_a = a2.admit([1, 2, 3, 4, 5], max_new=2)
    adm_b = a2.admit([1, 2, 3, 4, 5], max_new=2)
    pa1, pb1 = a2.alloc(adm_a), a2.alloc(adm_b)
    assert a2.register(adm_a, (1, 2), pa1)
    assert not a2.register(adm_b, (1, 2), pb1)  # duplicate: private
    assert not adm_b.chain_open
    pb2 = a2.alloc(adm_b)
    # chain closed: deeper pages stay private too
    assert not a2.register(adm_b, (3, 4), pb2)
    a2.retire(adm_b, [pb1, pb2])
    a2.retire(adm_a, [])
    a2.check_invariants()


def test_allocator_property_random_lifecycles_conserve_pages():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.given(
        st.lists(
            st.tuples(
                st.lists(st.integers(0, 3), min_size=1, max_size=12),
                st.integers(1, 6),    # max_new
                st.integers(0, 100),  # progress % before retire
                st.booleans(),        # abandon (retire with no allocs)
            ),
            min_size=1, max_size=24,
        ),
        st.integers(2, 12),  # pages
        st.integers(1, 4),   # page_tokens
    )
    @hypothesis.settings(max_examples=120, deadline=None)
    def run(jobs, pages, page_tokens):
        a = PageAllocator(pages, page_tokens)
        live = []  # (admission, private_pages, prompt, progress plan)

        def all_private():
            return [p for _, pp, _ in live for p in pp]

        for prompt, max_new, pct, abandon in jobs:
            worst = worst_case_pages(len(prompt), max_new, page_tokens)
            if worst > pages:
                continue  # submit-time 400, never reaches admission
            adm = a.admit(prompt, max_new)
            if adm is None:
                # budget-blocked: retire the oldest live request and
                # retry once (the engine's FIFO drain, compressed)
                if live:
                    old_adm, old_pages, _ = live.pop(0)
                    a.retire(old_adm, old_pages)
                    a.check_invariants(all_private())
                    adm = a.admit(prompt, max_new)
                if adm is None:
                    continue
            private = []
            live.append((adm, private, prompt))
            a.check_invariants(all_private())
            if abandon:
                live.pop()
                a.retire(adm, private)
                a.check_invariants(all_private())
                continue
            # consume part of the reservation, registering full
            # prompt pages as they complete (the engine's chunk walk)
            to_alloc = (adm.reserve_left * pct) // 100
            v = adm.cached_pages
            for _ in range(to_alloc):
                page = a.alloc(adm)
                private.append(page)
                a.check_invariants(all_private())
                covered = (v + 1) * page_tokens
                if covered <= len(prompt):
                    toks = tuple(
                        prompt[v * page_tokens:covered]
                    )
                    if a.register(adm, toks, page):
                        private.remove(page)
                    a.check_invariants(all_private())
                v += 1
        for adm, private, _ in live:
            a.retire(adm, private)
        a.check_invariants([])
        # everything returned: free + resident cache == total
        assert a.free_pages + a.cached_pages == pages
        assert a.reserved_pages == 0
        # nothing is pinned any more, so EVERY resident entry is
        # zero-ref, parents included: reclaimable counts all of them
        # (they are evicted leaf first), and that is what available()
        # lets an admission draw on
        assert a.cached_pages == a.reclaimable_pages
        assert a.available() == pages

    run()


def test_paged_config_from_env_contract():
    from dcos_commons_tpu.specification.specs import SpecError

    cfg = paged_config_from_env({"MAX_LEN": "64", "SERVE_BATCH": "4"})
    assert cfg.page_tokens == 16 and cfg.pages == 16  # 4 * ceil(64/16)
    assert cfg.pages_per_row == 4 and cfg.arena_pages == 17
    with pytest.raises(SpecError, match="overcommitted"):
        paged_config_from_env({
            "MAX_LEN": "64", "KV_PAGES": "2", "KV_PAGE_TOKENS": "16",
        })
    with pytest.raises(SpecError):
        paged_config_from_env({"PREFILL_CHUNK_TOKENS": "-1"})
    off = paged_config_from_env({"PREFIX_CACHE": "0"})
    assert off.prefix_cache is False


# -- engine vs a deterministic fake model ------------------------------


def _paged_engine(model, slots, pages, max_len=32, prompt_len=24,
                  chunk=5, prefix=False, **kw):
    return PagedEngine(
        model.prefill_chunk, model.decode, slots, max_len, prompt_len,
        page_tokens=4, pages=pages, chunk_tokens=chunk,
        prefix_cache=prefix, **kw,
    )


def test_paged_engine_chunked_prefill_matches_oracle():
    model = FakePagedModel()
    engine = _paged_engine(model, slots=3, pages=24)
    try:
        jobs = [
            ([[1, 2, 3]], 8, None),               # single chunk
            ([list(range(1, 14))], 5, None),      # 13 tokens: 3 chunks
            ([[4], [5, 6]], 5, None),
            ([list(range(2, 20))], 6, None),      # 18 tokens: 4 chunks
        ]
        results = _swarm(engine, jobs)
        for (rows, n, eos), result in zip(jobs, results):
            assert result == [_chain_oracle(r, n, eos) for r in rows]
        stats = engine.stats()
        assert stats["active_slots"] == 0
        assert stats["kv_pages_free"] == 24  # prefix off: all freed
        assert stats["prefill_chunk_backlog"] == 0
        engine._allocator.check_invariants()
    finally:
        engine.stop()


def test_paged_engine_budget_exhaustion_queues_fifo_and_completes():
    """More worst-case page demand than the arena: the overflow WAITS
    for retirements (strict FIFO, no starvation, no mid-flight OOM)
    and every chain still matches the oracle."""
    model = FakePagedModel()
    # 8 pages of 4: each job below worst-cases 3 pages, so at most 2
    # run concurrently even though 4 decode rows exist
    engine = _paged_engine(model, slots=4, pages=8, max_len=12,
                           prompt_len=8)
    try:
        jobs = [([[i + 1, i + 2]], 8, None) for i in range(7)]
        results = _swarm(engine, jobs)
        for (rows, n, eos), result in zip(jobs, results):
            assert result == [_chain_oracle(rows[0], n, eos)]
        assert model.max_active <= 2
        stats = engine.stats()
        assert stats["kv_pages_free"] == 8
        assert stats["kv_pages_reserved"] == 0
    finally:
        engine.stop()


def test_paged_timeout_names_the_starved_resource():
    """A budget-starved request 503s with the kv-page-budget reason
    (the requests_timed_out_memory split); a slot-starved one keeps
    the kv-slot reason (compute split)."""
    gate = threading.Event()  # never set: decode wedges
    model = FakePagedModel(step_gate=gate)
    # 4 pages: the occupant's worst case takes them all; slots ample
    engine = _paged_engine(model, slots=3, pages=4, max_len=16,
                           prompt_len=8, queue_timeout_s=0.3)
    try:
        occupant = threading.Thread(
            target=lambda: pytest.raises(
                Exception, engine.submit, [[9, 9]], 14
            ),
            daemon=True,
        )
        occupant.start()
        time.sleep(0.1)
        with pytest.raises(QueueTimeoutError) as exc:
            engine.submit([[5]], 4)
        assert exc.value.kind == "kv-page-budget"
        assert "page budget" in str(exc.value)
        deadline = time.monotonic() + 5
        while (engine.stats()["requests_timed_out"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        stats = engine.stats()
        assert stats["requests_timed_out_memory"] == 1
        assert stats["requests_timed_out_compute"] == 1  # the stalled
    finally:
        gate.set()
        engine.stop()
    # slot starvation: pages ample, one decode row, wedged occupant
    gate2 = threading.Event()
    model2 = FakePagedModel(step_gate=gate2)
    engine2 = _paged_engine(model2, slots=1, pages=24,
                            queue_timeout_s=0.3)
    try:
        occupant = threading.Thread(
            target=lambda: pytest.raises(
                Exception, engine2.submit, [[9]], 8
            ),
            daemon=True,
        )
        occupant.start()
        time.sleep(0.1)
        with pytest.raises(QueueTimeoutError) as exc:
            engine2.submit([[5]], 4)
        assert exc.value.kind == "kv-slot"
        assert engine2.stats()["requests_timed_out_memory"] == 0
    finally:
        gate2.set()
        engine2.stop()


def test_paged_prefill_failure_mid_prompt_frees_rows_and_pages():
    """A model failure on a prompt's SECOND chunk, with another row
    already decoding: both clients get the error at once (not their
    timeout), every row and page comes back, and the engine serves
    the next request from a clean arena."""
    model = FakePagedModel()
    orig = model.prefill_chunk
    boom = RuntimeError("chunk exploded")

    def chunk(padded, slot, table, start, true_len, temp, seed):
        if start > 0 and boom is not None:
            raise boom
        return orig(padded, slot, table, start, true_len, temp, seed)

    def slow_decode(*args, decode=model.decode):
        time.sleep(0.01)  # the short row outlives the long prompt
        return decode(*args)

    model.prefill_chunk = chunk
    model.decode = slow_decode
    engine = _paged_engine(model, slots=3, pages=24, queue_timeout_s=30)
    try:
        t0 = time.monotonic()
        errors = []

        def client(prompt, n):
            try:
                engine.submit([prompt], n)
            except RuntimeError as e:
                errors.append(e)

        short = threading.Thread(target=client, args=([1, 2], 30))
        short.start()
        deadline = time.monotonic() + 10
        while (engine.stats()["active_slots"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        # the long prompt's second chunk (5 of 12 tokens a chunk) is
        # the one that raises, a tick after its first
        long_ = threading.Thread(
            target=client, args=(list(range(1, 13)), 4)
        )
        long_.start()
        long_.join(timeout=10)
        short.join(timeout=10)
        assert time.monotonic() - t0 < 8.0  # the error, not a timeout
        assert errors and all(e is boom for e in errors), errors
        stats = engine.stats()
        assert stats["active_slots"] == 0 and stats["free_slots"] == 3
        assert stats["kv_pages_free"] == 24
        assert stats["kv_pages_reserved"] == 0
        engine._allocator.check_invariants()
        boom = None
        assert engine.submit([list(range(1, 13))], 4)[0] == \
            _chain_oracle(list(range(1, 13)), 4)
    finally:
        engine.stop()


def test_paged_long_prefill_is_progress_not_a_stall():
    """A prompt whose CHUNKED prefill spans several timeout windows
    must not be cut off as 'stalled': chunk progress is progress."""
    model = FakePagedModel()
    orig = model.prefill_chunk

    def slow_chunk(*a, **kw):
        time.sleep(0.15)  # half a window per chunk
        return orig(*a, **kw)

    model.prefill_chunk = slow_chunk
    engine = _paged_engine(model, slots=1, pages=24, chunk=3,
                           queue_timeout_s=0.3)
    try:
        # 15 tokens / 3-token chunks = 5 chunks ~= 0.75s > 2 windows
        prompt = list(range(1, 16))
        got = engine.submit([prompt], 4)[0]
        assert got == _chain_oracle(prompt, 4)
        assert engine.stats()["requests_timed_out"] == 0
    finally:
        engine.stop()


def test_paged_cow_no_write_after_page_published():
    """The copy-on-write contract, audited on the engine's own
    thread: once a page is registered into the prefix cache, no model
    call may ever write to it again.  Identical prompts hammer the
    cache while the audit records every write and every
    registration."""
    events = []  # ("write", page) / ("reg", page), loop-thread order

    class AuditModel(FakePagedModel):
        def prefill_chunk(self, padded, slot, table, start, true_len,
                          temp, seed):
            p = 4
            for pos in range(start, start + true_len):
                events.append(("write", int(table[pos // p])))
            if start == 0:
                self.partial[slot] = []
            buf = self.partial.setdefault(slot, [])
            # cache hits skip earlier chunks: pad the buffer (token
            # values untracked — this test audits pages, not tokens)
            buf.extend([0] * (start - len(buf)))
            buf.extend(int(t) for t in padded[0, :true_len])
            return _chain_first(buf)

        def decode(self, tok, pos, temps, seeds, tables, n_active):
            p = 4
            self.decode_calls += 1
            for s in range(len(tok)):
                if pos[s] > 0:
                    events.append(
                        ("write", int(tables[s][int(pos[s]) // p]))
                    )
            return np.asarray(
                [_chain_next(int(t), int(q))
                 for t, q in zip(tok, pos)],
                np.int32,
            )

    model = AuditModel()
    engine = _paged_engine(model, slots=3, pages=24, prefix=True)
    reg_orig = engine._allocator.register

    def audited_register(adm, toks, page):
        ok = reg_orig(adm, toks, page)
        if ok:
            events.append(("reg", int(page)))
        return ok

    engine._allocator.register = audited_register
    try:
        prompt = list(range(1, 12))  # 2 full pages + a partial
        jobs = [([prompt], 6, None) for _ in range(5)]
        jobs += [([prompt + [77]], 6, None)]  # diverges mid-page 3
        _swarm(engine, jobs)
        assert engine.stats()["prefix_cache_hits"] > 0
        published_at = {}
        for i, (kind, page) in enumerate(events):
            if kind == "reg":
                published_at.setdefault(page, i)
        for i, (kind, page) in enumerate(events):
            if kind == "write" and page in published_at:
                assert i < published_at[page], (
                    f"page {page} written at event {i} after being "
                    f"published at {published_at[page]}"
                )
        engine._allocator.check_invariants()
    finally:
        engine.stop()


def test_paged_engine_property_any_request_mix_matches_oracle():
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.given(
        st.lists(
            st.tuples(
                st.lists(
                    st.lists(st.integers(0, _V - 1), min_size=1,
                             max_size=9),
                    min_size=1, max_size=3,
                ),
                st.integers(1, 8),
                st.one_of(st.none(), st.integers(0, _V - 1)),
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
        # clamp the requested length to what the 12-position virtual
        # row can hold (over-length asks are a submit-time 400, not
        # this test's subject)
        jobs = [
            (rows, min(n, max_len - max(len(r) for r in rows)), eos)
            for rows, n, eos in jobs
        ]
        jobs = [j for j in jobs if j[1] >= 1]
        if not jobs:
            return
        model = FakePagedModel()
        engine = _paged_engine(
            model, slots=slots, pages=pages, max_len=max_len,
            prompt_len=9, chunk=chunk,
        )
        try:
            results = _swarm(engine, jobs)
            for (rows, n, eos), result in zip(jobs, results):
                assert result == [
                    _chain_oracle(r, n, eos) for r in rows
                ]
            stats = engine.stats()
            assert stats["active_slots"] == 0
            assert stats["queue_depth"] == 0
            assert stats["kv_pages_free"] == pages
            assert stats["kv_pages_reserved"] == 0
            engine._allocator.check_invariants()
        finally:
            engine.stop()

    run()


# -- admission gate: page-budget overcommit is a 422, not a 503 --------


_BADSERVE = """
name: badserve
pods:
  server:
    count: 1
    tpu:
      generation: v5e
      chips-per-host: 1
    tasks:
      api:
        goal: RUNNING
        cmd: "python serve_worker.py"
        cpus: 1
        memory: 1024
        env:
          VOCAB: "512"
          D_MODEL: "64"
          N_LAYERS: "2"
          MAX_LEN: "256"
          SERVE_BATCH: "4"
          KV_PAGE_TOKENS: "16"
          KV_PAGES: "3"
"""


def _put_findings(yaml_text):
    pytest.importorskip("jax")  # the workload builder needs it

    from dcos_commons_tpu.multi.admission import validate_service_yaml

    _spec, findings = validate_service_yaml(yaml_text, "badserve")
    return [f.render() for f in findings]


def test_admission_gate_rejects_page_budget_overcommit():
    """The PR 9 admission gate runs the serve workload builders, so
    an arena that cannot hold one MAX_LEN request (a permanent-503
    misconfiguration) is a line-anchored 422 finding at PUT time."""
    findings = _put_findings(_BADSERVE)
    assert any("overcommitted" in f for f in findings), findings
    good = _BADSERVE.replace('KV_PAGES: "3"', 'KV_PAGES: "64"')
    findings = _put_findings(good)
    assert not [f for f in findings if "overcommit" in f], findings


@pytest.mark.parametrize("where", ["env", "put", "shard", "schema"])
def test_kv_page_tokens_zero_is_refused(where):
    """``KV_PAGE_TOKENS`` is a page size and nothing else: 0, which
    selected the slot pool, is refused once, where the geometry is
    derived — so at PUT (the admission gate derives it) and in
    shardcheck's footprint model as at worker start — and the
    option's schema refuses it before a render."""
    from dcos_commons_tpu.specification.specs import SpecError

    if where == "env":
        with pytest.raises(SpecError, match="slot pool .* is gone"):
            paged_config_from_env({"KV_PAGE_TOKENS": "0"})
    elif where == "shard":
        pytest.importorskip("jax")

        from dcos_commons_tpu.analysis.shardcheck import _serve_leaves

        env = {"VOCAB": "512", "D_MODEL": "64", "N_LAYERS": "2",
               "MAX_LEN": "64", "SERVE_BATCH": "2"}
        _config, leaves = _serve_leaves(env, 1)
        assert any(leaf.section == "kv" for leaf in leaves)
        with pytest.raises(SpecError, match="KV_PAGE_TOKENS"):
            _serve_leaves(dict(env, KV_PAGE_TOKENS="0"), 1)
    elif where == "put":
        findings = _put_findings(
            _BADSERVE.replace('KV_PAGE_TOKENS: "16"',
                              'KV_PAGE_TOKENS: "0"')
        )
        assert any(
            "KV_PAGE_TOKENS must be >= 1" in f for f in findings
        ), findings
    else:
        from dcos_commons_tpu.tools.options import (
            OptionsError,
            load_schema,
            render_options,
        )

        schema = load_schema(os.path.join(REPO, "frameworks", "jax"))
        with pytest.raises(OptionsError, match="kv_page_tokens"):
            render_options(schema, {"serving": {"kv_page_tokens": 0}})
        assert render_options(
            schema, {"serving": {"kv_page_tokens": 1}}
        )["KV_PAGE_TOKENS"] == "1"


# -- SLO watcher: the min-direction kv_pages_free signal ---------------


def test_slo_watcher_kv_pages_free_breaches_below_minimum():
    from dcos_commons_tpu.health.detectors import ServingSloWatcher

    w = ServingSloWatcher(kv_pages_free_slo=5)
    events = w.observe({"serve-0-task": {"kv_pages_free": 2}})
    assert len(events) == 1 and not events[0].get("cleared")
    assert events[0]["signal"] == "kv_pages_free"
    assert "below minimum" in events[0]["message"]
    # still breaching: no repeat, magnitude tracked
    assert w.observe({"serve-0-task": {"kv_pages_free": 1}}) == []
    assert w.breaches[("serve-0-task", "kv_pages_free")] == 1
    # recovery clears
    events = w.observe({"serve-0-task": {"kv_pages_free": 9}})
    assert len(events) == 1 and events[0]["cleared"]
    # per-task env override beats the scheduler default
    w2 = ServingSloWatcher(kv_pages_free_slo=0)  # disabled by default
    assert w2.observe({"t": {"kv_pages_free": 1}}) == []
    events = w2.observe(
        {"t": {"kv_pages_free": 1}},
        env_by_task={"t": {"SERVE_KV_PAGES_FREE_SLO": "4"}},
    )
    assert len(events) == 1


# -- real model: token-identical to whole-batch generate ---------------


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


MAX_LEN, NEW = 48, 8
PROMPT_LEN = MAX_LEN - NEW
PROMPTS = [
    [1, 2, 3, 4],                             # shorter than a chunk
    [9, 8],
    [5, 6, 7, 2, 1],
    [3],
    [11, 12, 13, 14, 15, 16, 17, 2, 9],       # 9 tokens: 2 chunks
]


def _oracle(config, params, prompt, n):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import generate

    out = generate(
        config, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=n,
    )
    return [int(t) for t in out[0]]


def _real_paged(config, params, kv_dtype="native", slots=3, pages=30,
                page_tokens=4, chunk=6, prefix=True, **kw):
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    pool = PagedPoolModel(
        config, params, slots, MAX_LEN, page_tokens, pages, chunk,
        kv_dtype=kv_dtype,
    )
    pool.warm()
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, slots, MAX_LEN, PROMPT_LEN,
        page_tokens=page_tokens, pages=pages, chunk_tokens=chunk,
        prefix_cache=prefix, queue_timeout_s=120, **kw,
    )
    return pool, engine


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
def test_paged_engine_greedy_equals_whole_batch_generate(tiny, kv_dtype):
    """Staggered concurrent admission over the paged arena — mixed
    chunked/unchunked prompts, page tables, early retirement —
    reproduces whole-batch generate token for token."""
    config, params = tiny
    _pool, engine = _real_paged(config, params, kv_dtype=kv_dtype)
    try:
        results = [None] * len(PROMPTS)
        errors = []

        def client(i):
            try:
                results[i] = engine.submit([PROMPTS[i]], NEW)[0]
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(len(PROMPTS))
        ]
        for t in threads:
            t.start()
            time.sleep(0.01)  # staggered arrivals: mid-flight admission
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        if kv_dtype == "native":
            oracles = [
                _oracle(config, params, p, NEW) for p in PROMPTS
            ]
            assert results == oracles
        else:
            # int8 equivalence is engine-vs-engine determinism: the
            # quantization error vs the native oracle is expected, but
            # the pool path must be self-consistent per prompt
            again = [engine.submit([p], NEW)[0] for p in PROMPTS]
            assert results == again
        engine._allocator.check_invariants()
    finally:
        engine.stop()


def test_paged_prefix_cache_hit_is_token_identical(tiny):
    """A request served partly from CACHED prompt pages produces the
    same tokens as the cold path — shared pages carry bit-identical
    K/V, and divergence past the shared prefix recomputes privately."""
    config, params = tiny
    _pool, engine = _real_paged(config, params)
    shared = [7, 3, 9, 1, 4, 4, 2, 8]  # exactly 2 full pages (P=4)
    variants = [
        shared + [5],
        shared + [6, 1, 2],
        shared + [5],          # full repeat: max cache reuse
        shared[:6] + [9, 9],   # diverges MID page 2: partial miss
    ]
    try:
        cold = engine.submit([variants[0]], NEW)[0]
        base = engine.stats()["prefix_cache_hits"]
        for v in variants:
            got = engine.submit([v], NEW)[0]
            assert got == _oracle(config, params, v, NEW)
        assert cold == _oracle(config, params, variants[0], NEW)
        stats = engine.stats()
        assert stats["prefix_cache_hits"] > base
        assert 0.0 < stats["prefix_cache_hit_rate"] <= 1.0
        assert stats["kv_pages_cached"] > 0
        engine._allocator.check_invariants()
    finally:
        engine.stop()


def test_pool_engine_early_retirement_and_eos_prefixes(tiny):
    """Mixed requested lengths retire rows early; an EOS cut is a
    PREFIX of the whole-batch generation (plus the eos token)."""
    config, params = tiny
    _pool, engine = _real_paged(config, params, slots=2)
    try:
        full = [_oracle(config, params, p, NEW) for p in PROMPTS[:3]]
        # mixed lengths in ONE submit: 3 rows > 2 slots exercises
        # queue + retirement interleaving; each row a prefix
        mixed = engine.submit(PROMPTS[:3], 3)
        assert mixed == [row[:3] for row in full]
        # eos: pick each row's 3rd token as its stop token
        for prompt, row in zip(PROMPTS[:3], full):
            eos = row[2]
            got = engine.submit([prompt], NEW, eos_id=eos)[0]
            assert got == row[: row.index(eos) + 1]
    finally:
        engine.stop()


def test_paged_gang_sim_broadcast_protocol_equivalence(tiny):
    """The gang driver's PAGED broadcast protocol (chunk/page fields)
    executed for real in a single-process gang sim: rank 0's engine
    callbacks broadcast each tick and _execute_paged_tick runs the
    identical payload — greedy replies stay token-identical."""
    from jax.experimental import multihost_utils

    from dcos_commons_tpu.serve.pool import PagedPoolModel

    path = os.path.join(REPO, "frameworks", "jax",
                        "serve_gang_worker.py")
    spec = importlib.util.spec_from_file_location(
        "gang_worker_paged_ut", path
    )
    gw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gw)

    config, params = tiny
    slots, p_tok, pages, chunk = 3, 4, 30, 6
    m = -(-MAX_LEN // p_tok)
    pool = PagedPoolModel(
        config, params, slots, MAX_LEN, p_tok, pages, chunk
    )
    pool.warm()
    ticks = {"admit": 0, "decode": 0, "noop": 0}

    def prefill_fn(padded, slot, table, start, true_len, temp, seed):
        head = np.asarray(
            [gw.OP_ADMIT, slot, start, true_len, seed,
             round(temp * 1e6)],
            np.int64,
        )
        _, zero_rows, zero_tables, _ = gw._zero_paged_payload(
            slots, m, chunk
        )
        zero_tables[slot] = table
        out = gw._broadcast_paged_tick(
            multihost_utils,
            (head, zero_rows, zero_tables, padded.astype(np.int32)),
            slots, m, chunk,
        )
        ticks["admit"] += 1
        return gw._execute_paged_tick(pool, *out)

    def decode_fn(tok, pos, temps, seeds, tables, n_active):
        head = np.asarray(
            [gw.OP_DECODE, n_active, 0, 0, 0, 0], np.int64
        )
        rows = np.stack([
            tok.astype(np.int64), pos.astype(np.int64),
            np.round(temps.astype(np.float64) * 1e6).astype(np.int64),
            seeds.astype(np.int64),
        ], axis=1)
        out = gw._broadcast_paged_tick(
            multihost_utils,
            (head, rows, tables.astype(np.int64),
             np.zeros((1, chunk), np.int32)),
            slots, m, chunk,
        )
        ticks["decode"] += 1
        return gw._execute_paged_tick(pool, *out)

    def idle():
        out = gw._broadcast_paged_tick(
            multihost_utils, None, slots, m, chunk
        )
        assert gw._execute_paged_tick(pool, *out) is None
        ticks["noop"] += 1

    engine = PagedEngine(
        prefill_fn, decode_fn, slots, MAX_LEN, PROMPT_LEN,
        page_tokens=p_tok, pages=pages, chunk_tokens=chunk,
        queue_timeout_s=120, on_idle=idle, idle_every_s=0.01,
    )
    try:
        results = engine.submit(PROMPTS, NEW)
        oracles = [_oracle(config, params, p, NEW) for p in PROMPTS]
        assert results == oracles
        assert ticks["admit"] >= len(PROMPTS)  # >= 1 chunk each
        assert ticks["decode"] >= NEW - 1
        deadline = time.monotonic() + 5
        while not ticks["noop"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ticks["noop"] >= 1
    finally:
        engine.stop()


# -- the arena stays in place through the layer scan -------------------
#
# The step functions' cost on the chip hangs on ONE structural fact:
# the arena rides the layer scan as a CARRY, never as a scanned array
# (models/decode.py `_scan_layers_over_arena`).  No chip is here to time it, so the
# jaxpr is held to it, and the result is held bit-equal to the plain
# per-layer spelling the functions had before.

ARENA_CASES = [
    (step, kv_dtype)
    for step in ("prefill_chunk", "decode_step")
    for kv_dtype in ("native", "int8")
]
A_LAYERS, A_PAGES, A_PTOK = 3, 11, 4


@pytest.fixture(scope="module")
def arena_model():
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, init_params

    config = TransformerConfig(
        vocab=64, d_model=32, n_layers=A_LAYERS, n_heads=8, n_kv_heads=4,
        d_ff=96, max_seq=64, dtype=jnp.float32, remat=False,
    )
    return config, init_params(config, jax.random.key(3))


def _arena_case(config, step, kv_dtype):
    """(cache, args) for one call: an arena full of earlier keys and
    values (so what a layer gathers matters), page tables with
    unallocated entries, a chunk with two padding tokens, a pool with
    one inactive row."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.decode import init_paged_kv_cache

    zero = init_paged_kv_cache(config, A_PAGES, A_PTOK, kv_dtype)
    keys = jax.random.split(jax.random.key(11), len(zero))
    cache = {}
    for key, (name, arr) in zip(keys, sorted(zero.items())):
        if arr.dtype == jnp.int8:
            cache[name] = jax.random.randint(
                key, arr.shape, -127, 128, jnp.int32
            ).astype(jnp.int8)
        elif name.endswith("_scale"):
            cache[name] = jax.random.uniform(
                key, arr.shape, jnp.float32, 0.001, 0.02
            )
        else:
            cache[name] = jax.random.normal(key, arr.shape, arr.dtype)
    if step == "prefill_chunk":
        # second chunk of a prompt: positions 4..7 are real (virtual
        # page 1 -> physical 7), 8 and 9 are padding; virtual pages
        # 2.. are unallocated
        tokens = jnp.asarray([[5, 9, 2, 31, 0, 0]], jnp.int32)
        table = jnp.asarray([3, 7, 0, 0, 0, 0], jnp.int32)
        return cache, (tokens, table, jnp.int32(4), jnp.int32(4))
    token = jnp.asarray([7, 12, 40, 3], jnp.int32)
    pos = jnp.asarray([5, 9, 0, 2], jnp.int32)        # row 2 inactive
    tables = jnp.asarray([
        [2, 9, 0, 0, 0, 0],
        [4, 1, 6, 0, 0, 0],
        [0, 0, 0, 0, 0, 0],
        [10, 0, 0, 0, 0, 0],
    ], jnp.int32)
    return cache, (token, pos, tables)


def _arena_step(step):
    from dcos_commons_tpu.models import decode

    return {
        "prefill_chunk": decode.paged_prefill_chunk,
        "decode_step": decode.paged_decode_step,
    }[step]


def _scans_over_layers(jaxpr):
    """Every scan of length A_LAYERS in a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == A_LAYERS:
            found.append(eqn)
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                found += _scans_over_layers(inner)
    return found


@pytest.mark.parametrize("step,kv_dtype", ARENA_CASES)
def test_arena_is_a_carry_of_the_layer_scan_never_scanned(
    arena_model, step, kv_dtype
):
    """No xs and no ys of the layer scan is the arena stacked over
    layers; every arena array is in the carry, in and out.  An edit
    that scans the arena again makes XLA slice a whole layer out and
    restack the whole arena in every layer (PERF.md §6, PR 25)."""
    import jax

    config, params = arena_model
    cache, args = _arena_case(config, step, kv_dtype)
    fn = _arena_step(step)
    jaxpr = jax.make_jaxpr(
        lambda cache, *args: fn(config, params, cache, *args)
    )(cache, *args).jaxpr
    scans = _scans_over_layers(jaxpr)
    assert len(scans) == 1, "one scan over the layers is expected"
    eqn = scans[0]
    n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
    carry_in = eqn.invars[eqn.params["num_consts"]:n_fixed]
    carry_out = eqn.outvars[:eqn.params["num_carry"]]
    scanned = eqn.invars[n_fixed:] + eqn.outvars[eqn.params["num_carry"]:]
    per_layer = {tuple(arr.shape[1:]) for arr in cache.values()}
    for var in scanned:
        assert tuple(var.aval.shape[1:]) not in per_layer, (
            f"the layer scan scans {var.aval.str_short()}: the arena "
            "must ride it as a carry"
        )
    for name, arr in cache.items():
        for side, carried in (("in", carry_in), ("out", carry_out)):
            assert any(
                v.aval.size == arr.size and v.aval.dtype == arr.dtype
                for v in carried
            ), f"cache[{name!r}] is not in the scan's carry ({side})"


def _reference_step(config, params, cache, step, args):
    """The plain spelling: a Python loop over layers that takes the
    layer's arena out (``cache[k][l]``), scatters the new rows into
    it, gathers the request's pages from it and restacks the arena at
    the end.  One body serves both steps: a chunk is one row of C
    queries, a decode step S rows of one query."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dcos_commons_tpu.models import decode as D

    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    p_tok = cache["k"].shape[2]
    quantized = "k_scale" in cache
    if step == "prefill_chunk":
        tokens, table, start, true_len = args
        offs = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        positions = (start + offs)[None, :]              # [1, c]
        tables = table[None, :]
        live = (offs < true_len)[None, :]
        x = params["embed"][tokens].astype(config.dtype)
    else:
        token, pos, tables = args
        positions = pos[:, None]                         # [S, 1]
        live = jnp.ones_like(positions, bool)
        x = params["embed"][token][:, None, :].astype(config.dtype)
    b, s = positions.shape
    m = tables.shape[1]
    length = m * p_tok
    vpage = jnp.minimum(positions // p_tok, m - 1)
    phys = jnp.where(
        live, jnp.take_along_axis(tables, vpage, axis=1), 0
    )                                                    # [b, s]
    slot_off = positions % p_tok
    valid = (
        lax.broadcasted_iota(jnp.int32, (b, s, length), 2)
        <= positions[:, :, None]
    )
    # the steps' own contractions, so that sums run in the same order:
    # a chunk keeps its query axis, a decode step has none
    every = slice(None)
    if step == "prefill_chunk":
        qdims, q_shape = "bqkr", (b, s, kv, h // kv, hd)
        per_key = (every, None, every, None, every)
        per_query = (every, every, None, None, every)
    else:
        qdims, q_shape = "bkr", (b, kv, h // kv, hd)
        per_key = (every, every, None, every)
        per_query = (every, 0, None, None, every)
    stacked = {name: [] for name in cache}
    for l in range(config.n_layers):
        layer = jax.tree.map(lambda a: a[l], params["layers"])
        arena = {name: arr[l] for name, arr in cache.items()}
        normed = D._norm(config, x, layer["attn_norm"])
        q, k_new, v_new = D._project_kv(config, layer, normed, positions)
        new = {"k": k_new, "v": v_new}
        if quantized:
            new["k"], new["k_scale"] = D._quantize_kv(k_new)
            new["v"], new["v_scale"] = D._quantize_kv(v_new)
        for name in arena:
            arena[name] = arena[name].at[
                phys.reshape(-1), slot_off.reshape(-1)
            ].set(new[name].reshape((b * s,) + new[name].shape[2:]))
            stacked[name].append(arena[name])
        k_all = arena["k"][tables].reshape(b, length, kv, hd)
        v_all = arena["v"][tables].reshape(b, length, kv, hd)
        qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(q_shape)
        scores = jnp.einsum(
            f"{qdims}d,blkd->{qdims}l", qg, k_all.astype(jnp.float32)
        )
        if quantized:
            ks_all = arena["k_scale"][tables].reshape(b, length, kv)
            vs_all = arena["v_scale"][tables].reshape(b, length, kv)
            scores = scores * ks_all.transpose(0, 2, 1)[per_key]
        scores = jnp.where(valid[per_query], scores, D._NEG)
        probs = jax.nn.softmax(scores, axis=-1)
        if quantized:
            probs = probs * vs_all.transpose(0, 2, 1)[per_key]
        attn = jnp.einsum(
            f"{qdims}l,blkd->{qdims}d", probs, v_all.astype(jnp.float32)
        ).astype(config.dtype)
        x = x + attn.reshape(b, s, h * hd) @ layer["wo"]
        x, _counts = D._serve_ffn(config, layer, x)
    x = D._norm(config, x, params["final_norm"])
    if step == "prefill_chunk":
        x_last = lax.dynamic_index_in_dim(
            x, true_len - 1, axis=1, keepdims=False
        )
    else:
        x_last = x[:, 0]
    logits = jnp.einsum(
        "bd,vd->bv", x_last.astype(jnp.float32),
        params["embed"].astype(jnp.float32),
    )
    return logits, {name: jnp.stack(rows) for name, rows in stacked.items()}


@pytest.mark.parametrize("step,kv_dtype", ARENA_CASES)
def test_arena_in_place_equals_per_layer_slice_and_restack(
    arena_model, step, kv_dtype
):
    """Logits AND the whole returned cache — trash page included — are
    bit-equal to the plain per-layer reference, through tables with
    unallocated entries, a chunk with padding and an inactive row."""
    import jax

    config, params = arena_model
    cache, args = _arena_case(config, step, kv_dtype)
    fn = _arena_step(step)
    logits, new_cache, _ = jax.jit(
        lambda cache, *args: fn(config, params, cache, *args)
    )(cache, *args)
    want_logits, want_cache = jax.jit(
        lambda cache, *args: _reference_step(
            config, params, cache, step, args
        )
    )(cache, *args)
    assert set(new_cache) == set(cache)
    for name, arr in cache.items():
        got = np.asarray(new_cache[name])
        assert got.shape == arr.shape and got.dtype == arr.dtype
        np.testing.assert_array_equal(got, np.asarray(want_cache[name]))
        # and the call did write: the cache is not what went in
        assert not np.array_equal(got, np.asarray(arr))
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(want_logits)
    )


# -- the decode step's attention: no gathered copy of the tables -------


def _avals(jaxpr):
    """Every value a jaxpr makes, nested jaxprs too."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _avals(inner)


def _gathered_values(config, jaxpr, rows, table_len):
    """The values of ``jaxpr`` as large as every row's whole table of
    K or of V: ``[b * M, P, kv, hd]``, ``[b, M * P, kv, hd]`` or any
    other shape of that many elements."""
    size = (
        rows * table_len * A_PTOK * config.n_kv_heads * config.head_dim
    )
    return [
        aval.str_short() for aval in _avals(jaxpr)
        if getattr(aval, "size", 0) == size
    ]


def test_decode_with_the_kernel_holds_no_value_of_rows_x_table_pages(
    arena_model, monkeypatch
):
    """With the page walk in the path (ops/paged_decode.py, which reads
    live pages in place) ``jit__decode`` makes no array of rows x table
    pages pages: the gather that cost 5.6 ms of an 18.4 ms tick
    (PERF.md §6, PR 29) cannot come back unseen.  The gather path's
    jaxpr does hold such values, so the guard is not empty."""
    import jax

    from dcos_commons_tpu.models import decode

    config, params = arena_model
    cache, (token, pos, tables) = _arena_case(config, "decode_step", "native")

    def jaxpr():
        return jax.make_jaxpr(
            lambda cache: decode.paged_decode_step(
                config, params, cache, token, pos, tables
            )
        )(cache).jaxpr

    rows, table_len = tables.shape
    # the arena itself must not be mistaken for a gathered buffer
    assert A_LAYERS * A_PAGES != rows * table_len
    assert _gathered_values(config, jaxpr(), rows, table_len)
    monkeypatch.setattr(
        decode, "decode_attention_kernel", lambda config, cache: "interpret"
    )
    assert _gathered_values(config, jaxpr(), rows, table_len) == []


def test_a_quantized_arena_takes_the_gather_path_on_a_tpu(
    arena_model, monkeypatch
):
    """Where the backend says TPU a native arena would go through the
    kernel; an int8 arena (the kernel
    takes no scales) still takes the gather path and is bit-equal to
    the plain per-layer reference, as at the parent commit."""
    import jax

    from dcos_commons_tpu.models import decode

    config, params = arena_model
    cache, args = _arena_case(config, "decode_step", "int8")
    native, _ = _arena_case(config, "decode_step", "native")
    want_logits, want_cache = jax.jit(
        lambda cache, *args: _reference_step(
            config, params, cache, "decode_step", args
        )
    )(cache, *args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode.decode_attention_kernel(config, native) == "compiled"
    assert decode.decode_attention_kernel(config, cache) is None
    logits, new_cache, _ = jax.jit(
        lambda cache, *args: decode.paged_decode_step(
            config, params, cache, *args
        )
    )(cache, *args)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(want_logits)
    )
    for name in cache:
        np.testing.assert_array_equal(
            np.asarray(new_cache[name]), np.asarray(want_cache[name])
        )
