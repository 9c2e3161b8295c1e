"""Live KV page migration (ISSUE 16): the fenced cutover protocol,
chaos kills at every boundary, and the three consumers built on it.

Coverage layers, all against a deterministic fake model (the
``test_paged_kv`` chain: every token a pure function of its
predecessor and position, so "zero token loss, none doubled" is a
list equality, not a statistic):

* PROTOCOL: a session moved mid-generation produces the exact oracle
  continuation on the destination; chaos kills at every stage
  boundary (mid-snapshot, mid-stream, mid-splice, pre-cutover,
  post-cutover-pre-ack) leave exactly one serving copy, no leaked or
  double-freed pages on either pod (``PageAllocator.check_invariants``
  on both), and the post-cutover failure is retryable-release, never
  a resumed source.

* SPLICE TRANSACTIONALITY: a hypothesis sweep splices fabricated
  sessions (random geometry, random arena pressure) into a pod and
  aborts them — admission is the same transactional rule a fresh
  request faces, so invariants hold after every op and a failed or
  aborted splice restores the arena byte-for-byte.

* CONSUMERS: drain-with-migration moves every live session and its
  report re-points router prefix claims; the router follows a
  migrated session with a collect and routes long prompts to
  prefill-role capacity; prefill pods hand finished pages to decode
  pools and degrade to local decode when no pool answers; role-aware
  health judges a prefill pod on prefill backlog, never on decode
  occupancy (the QuietPodWatcher flap this would otherwise cause is
  the ISSUE's satellite).
"""

import threading
import time

import numpy as np
import pytest

from dcos_commons_tpu.health.detectors import (
    QuietPodWatcher,
    ServingSloWatcher,
)
from dcos_commons_tpu.router.core import RequestRouter
from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.serve.migration import (
    STAGES,
    InProcessTransport,
    MigrationError,
    PrefillHandoff,
    ReleasePendingError,
    SessionMigratedError,
    SessionSnapshot,
    drain_sessions,
    migrate_session,
)

from dcos_commons_tpu.testing.chain_model import (
    OneAhead,
    V as _V,
    chain_first as _chain_first,
    chain_next as _chain_next,
    chain_oracle as _chain_oracle,
)

P = 4  # page tokens


@pytest.fixture(scope="module", autouse=True)
def _racecheck_probes():
    """Dynamic race probes (SDKLINT_RACECHECK=1): migration splices KV
    state into a live decode loop from a foreign thread — watch the
    engine classes' shared-write set so any unordered splice/tick pair
    fails the run (the PR 16 bug class).  No-op in the fast tier."""
    from conftest import racecheck_watch_guard

    yield from racecheck_watch_guard(PagedEngine)


class ChainArena:
    """The fake device half: a dict-of-dicts KV arena whose cell
    contents are the tokens themselves, so a migrated page's payload
    is CONTENT-CHECKABLE — a destination decoding from wrong bytes
    would still produce the right chain (decode is a function of
    token and position), but prefill resume reads the cells, and the
    page-level export/import contract is exercised for real."""

    def __init__(self, step_s=0.004):
        self.cells = {}
        self.lock = threading.Lock()
        self.step_s = step_s

    def prefill_chunk(self, padded, slot, table, start, true_len,
                      temp, seed):
        with self.lock:
            buf = [
                self.cells[int(table[pos // P])][pos % P]
                for pos in range(start)
            ]
            for i in range(true_len):
                pos = start + i
                page = int(table[pos // P])
                tok = int(padded[0, i])
                self.cells.setdefault(page, {})[pos % P] = tok
                buf.append(tok)
        return _chain_first(buf)

    def decode(self, tok, pos, temps, seeds, tables, n_active):
        time.sleep(self.step_s)
        with self.lock:
            for s in range(len(tok)):
                if int(pos[s]) > 0:
                    page = int(tables[s][int(pos[s]) // P])
                    if page != 0:
                        self.cells.setdefault(page, {})[
                            int(pos[s]) % P
                        ] = int(tok[s])
        return np.asarray(
            [_chain_next(int(t), int(q)) for t, q in zip(tok, pos)],
            np.int32,
        )

    def read_page(self, page):
        with self.lock:
            return dict(self.cells.get(page, {}))

    def write_page(self, page, payload):
        with self.lock:
            self.cells[page] = dict(payload)


# How far ahead of its device half an engine of this module runs: 0,
# a step is resolved when its call returns; 1 (ISSUE 31), the next step
# is dispatched before the one before it is read, so every fence below
# finds a step in flight.  The protocol's tests take ``depth`` and run
# at both: what they hold — where a row stands when its fence drops,
# what its pages hold, what the client reads — is the same.
_DEPTH = 0


@pytest.fixture(params=[0, 1], ids=["sync", "one-ahead"])
def depth(request):
    global _DEPTH
    _DEPTH = request.param
    yield request.param
    _DEPTH = 0


def _make_pod(role="unified", handoff=None, pages=40, slots=3,
              step_s=0.004, arena=None):
    arena = arena if arena is not None else ChainArena(step_s=step_s)
    half, ahead = arena, {}
    if _DEPTH:
        half = OneAhead(arena)
        ahead = half.engine_kwargs()
    eng = PagedEngine(
        half.prefill_chunk, half.decode, slots, 64, 48,
        page_tokens=P, pages=pages, chunk_tokens=8, prefix_cache=True,
        role=role, read_page=arena.read_page,
        write_page=arena.write_page, handoff=handoff,
        queue_timeout_s=30, **ahead,
    )
    return arena, eng


def _submit_async(eng, prompt, n, result, key="r"):
    def client():
        try:
            result[key] = eng.submit([prompt], n)
        except BaseException as e:  # noqa: BLE001 — the assertion target
            result[key] = e

    t = threading.Thread(target=client, daemon=True)
    t.start()
    return t


def _wait_mid_decode(eng, min_out=4, timeout=10.0):
    """Block until the single live session is decoding with at least
    ``min_out`` tokens out; returns its rid."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sess = eng.sessions()
        if sess and sess[0]["state"] == "decode" \
                and eng.stats()["tokens_out"] >= min_out:
            return sess[0]["rid"]
        time.sleep(0.005)
    raise AssertionError("session never reached mid-decode")


# -- the wire format ---------------------------------------------------


def test_snapshot_wire_roundtrip_is_json_safe():
    import json

    snap = SessionSnapshot(
        rid=7, tokens=[1, 2, 3], max_new=9, temperature=0.5, eos=42,
        seed=123, out=[11, 12], fill_pos=3, kv_end=4, page_tokens=P,
        pages=[
            (0, np.arange(8, dtype=np.float32).reshape(2, 4)),
            (1, {0: 5, 3: 9}),               # fake-arena cell dict
            (2, {"k": np.zeros(2, np.int8)}),
        ],
        source="pod-0",
    )
    wire = json.loads(json.dumps(snap.to_wire()))  # must survive JSON
    back = SessionSnapshot.from_wire(wire)
    assert back.tokens == snap.tokens and back.out == snap.out
    assert back.eos == 42 and back.kv_end == 4
    assert np.array_equal(back.pages[0][1], snap.pages[0][1])
    assert back.pages[0][1].dtype == np.float32
    assert back.pages[1][1] == {0: 5, 3: 9}  # int keys survive
    assert np.array_equal(back.pages[2][1]["k"], np.zeros(2, np.int8))
    assert back.nbytes() == snap.nbytes()


# -- the protocol ------------------------------------------------------


def test_mid_generation_migration_greedy_equal(depth):
    """The tentpole contract: freeze mid-decode, move, and the
    destination finishes the EXACT oracle continuation — zero tokens
    lost, none doubled — while both arenas stay invariant-clean and
    the source's pages all come home."""
    _sa, src = _make_pod()
    _da, dst = _make_pod()
    try:
        free0 = src.stats()["kv_pages_free"]
        prompt = list(range(1, 14))
        n = 30
        result = {}
        t = _submit_async(src, prompt, n, result)
        rid = _wait_mid_decode(src, min_out=5)
        transport = InProcessTransport()
        record = migrate_session(
            src, dst, rid, dest_name="dst", transport=transport
        )
        assert record.ok and record.stage == "release"
        assert record.pages > 0 and record.bytes > 0
        t.join(timeout=15)
        err = result["r"]
        assert isinstance(err, SessionMigratedError), err
        assert err.moved_to == "dst" and err.dest_rid == record.dest_rid
        out = dst.collect(err.dest_rid, timeout=20)
        assert out == _chain_oracle(prompt, n)
        src._allocator.check_invariants()
        dst._allocator.check_invariants()
        assert src.stats()["migrations_out"] == 1
        assert dst.stats()["migrations_in"] == 1
        assert transport.sessions == 1 and transport.bytes_sent > 0
        # every page the moved session held came back: free again, or
        # parked reclaimable in the prefix cache — nothing leaked
        stats = src.stats()
        assert stats["kv_pages_free"] + \
            stats["kv_pages_reclaimable"] == free0
        assert src.sessions() == []
    finally:
        src.stop()
        dst.stop()


@pytest.mark.parametrize("stage", ["snapshot", "stream", "splice",
                                   "cutover"])
def test_chaos_kill_before_cutover_resumes_source(stage, depth):
    """A death at any PRE-cutover boundary aborts cleanly: the
    destination keeps nothing, the source resumes exactly where it
    froze, and the client's reply is the untouched oracle — the
    failed move is invisible except in the record."""
    assert stage in STAGES
    _sa, src = _make_pod()
    _da, dst = _make_pod()
    try:
        dst_free0 = dst.stats()["kv_pages_free"]
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        n = 24
        result = {}
        t = _submit_async(src, prompt, n, result)
        rid = _wait_mid_decode(src)

        class ChaosKill(RuntimeError):
            pass

        def chaos(at):
            if at == stage:
                raise ChaosKill(at)

        with pytest.raises(ChaosKill):
            migrate_session(src, dst, rid, dest_name="dst",
                            chaos=chaos)
        # nothing activated: the destination is untouched
        assert dst.sessions() == []
        assert dst.stats()["migrations_in"] == 0
        assert dst.stats()["kv_pages_free"] == dst_free0
        # the source resumed and finishes the generation itself
        t.join(timeout=15)
        assert result["r"] == [_chain_oracle(prompt, n)]
        assert src.stats()["migrations_out"] == 0
        src._allocator.check_invariants()
        dst._allocator.check_invariants()
    finally:
        src.stop()
        dst.stop()


def test_chaos_kill_at_release_is_exactly_once(depth):
    """The worst boundary: cutover landed, release died.  The source
    must NOT resume (that would double-decode); the only legal
    continuation is retrying the release — after which the client is
    redirected and the destination's reply is the one oracle copy."""
    _sa, src = _make_pod()
    _da, dst = _make_pod()
    try:
        prompt = [2, 7, 1, 8, 2, 8]
        n = 26
        result = {}
        t = _submit_async(src, prompt, n, result)
        rid = _wait_mid_decode(src)

        def chaos(at):
            if at == "release":
                raise RuntimeError("killed post-cutover pre-ack")

        with pytest.raises(ReleasePendingError) as exc:
            migrate_session(src, dst, rid, dest_name="dst",
                            chaos=chaos)
        pending = exc.value
        assert pending.rid == rid and pending.moved_to == "dst"
        # the destination OWNS the session: cutover is final, so a
        # late abort must refuse (no-op) rather than kill the row
        dst.abort_splice(pending.dest_rid)
        assert dst.stats()["migrations_in"] == 1
        # the source row is still frozen — not serving, not released:
        # sessions() lists only unfenced rows
        assert src.sessions() == []
        assert not t.join(timeout=0.2) and t.is_alive()
        # retried release (idempotent per rid) completes the protocol
        src.release_migrated(rid, moved_to="dst",
                             dest_rid=pending.dest_rid)
        t.join(timeout=15)
        err = result["r"]
        assert isinstance(err, SessionMigratedError)
        out = dst.collect(pending.dest_rid, timeout=20)
        assert out == _chain_oracle(prompt, n)  # exactly once
        assert src.stats()["migrations_out"] == 1
        src._allocator.check_invariants()
        dst._allocator.check_invariants()
    finally:
        src.stop()
        dst.stop()


def _frozen_state(eng, rid):
    """(tok, pos, out) of a fenced decoding row, as the loop holds
    them."""
    with eng._cv:
        row = eng._find_rid_locked(rid)
        assert row is not None and row.frozen
        assert eng._rows[row.slot] is row
        # whatever the loop has in flight, this row does not ride it
        assert all(step[row.slot] is None for step in eng._inflight)
        return (
            int(eng._tok[row.slot]), int(eng._pos[row.slot]),
            list(row.out),
        )


def test_a_fence_finds_its_row_settled(depth):
    """Freeze, export and unfreeze one session again and again while
    it decodes (at depth 1 every fence meets a step in flight): when
    the fence has dropped ``(tok, pos)`` say exactly what the row's
    pages hold, nothing moves until it is lifted, no sample is lost,
    and the client reads the oracle."""
    arena, src = _make_pod()
    try:
        prompt, n = list(range(3, 14)), 48
        plen = len(prompt)
        result = {}
        t = _submit_async(src, prompt, n, result)
        rid = _wait_mid_decode(src, min_out=3)
        fences = 0
        while t.is_alive() and fences < 6:
            try:
                src.freeze(rid)
            except MigrationError:
                break  # the session finished first
            tok, pos, out = _frozen_state(src, rid)
            assert (tok, pos) == (out[-1], plen + len(out) - 1)
            snap = src.export_frozen(rid)
            assert snap.out == out and snap.kv_end == pos
            seq = prompt + out
            held = {
                v * P + off: cell
                for v, cells in snap.pages for off, cell in cells.items()
            }
            # every position behind the row's next one, and what the
            # sequence has there: the step in flight at the fence was
            # applied, not dropped, so nothing is written ahead of
            # ``pos`` that ``out`` does not account for
            assert {q: held[q] for q in range(pos)} == dict(
                enumerate(seq[:pos])
            )
            time.sleep(0.02)  # five steps' worth: a fenced row stands
            assert _frozen_state(src, rid) == (tok, pos, out)
            src.unfreeze(rid)
            fences += 1
            time.sleep(0.01)
        assert fences >= 2
        t.join(timeout=15)
        assert result["r"] == [_chain_oracle(prompt, n)]
        src._allocator.check_invariants()
        assert src.stats()["loop"]["ahead_discarded_rows"] == 0
    finally:
        src.stop()


@pytest.mark.parametrize("end", ["activate", "abort"])
def test_splice_into_a_destination_that_is_decoding(depth, end):
    """The destination's own request has a step in flight while a
    session is spliced in and then activated, or aborted at cutover:
    both requests read their oracle either way, and an abort leaves
    the destination's arena as it was."""
    _sa, src = _make_pod()
    _da, dst = _make_pod()
    try:
        own_prompt, own_n = [8, 8, 2, 1], 44
        own = {}
        t_own = _submit_async(dst, own_prompt, own_n, own)
        _wait_mid_decode(dst, min_out=2)
        prompt, n = [3, 1, 4, 1, 5, 9, 2, 6], 30
        result = {}
        t = _submit_async(src, prompt, n, result)
        rid = _wait_mid_decode(src)

        def chaos(at):
            if end == "abort" and at == "cutover":
                raise RuntimeError("killed at cutover")

        if end == "abort":
            with pytest.raises(RuntimeError, match="cutover"):
                migrate_session(src, dst, rid, dest_name="dst",
                                chaos=chaos)
            t.join(timeout=15)
            assert result["r"] == [_chain_oracle(prompt, n)]
            assert dst.stats()["migrations_in"] == 0
        else:
            record = migrate_session(src, dst, rid, dest_name="dst")
            assert record.ok
            t.join(timeout=15)
            out = dst.collect(result["r"].dest_rid, timeout=20)
            assert out == _chain_oracle(prompt, n)
        t_own.join(timeout=15)
        assert own["r"] == [_chain_oracle(own_prompt, own_n)]
        for pod in (src, dst):
            pod._allocator.check_invariants()
            assert pod.sessions() == []
    finally:
        src.stop()
        dst.stop()


# -- splice transactionality (hypothesis) ------------------------------


def _fabricated_snapshot(tokens, out, max_new, fill):
    """A wire-faithful snapshot for splice-admission properties: the
    page payloads carry the chain cells a real export would."""
    from dcos_commons_tpu.serve.paging import pages_for

    plen = len(tokens)
    fill_pos = min(fill, plen)
    kv_end = plen + len(out) - 1 if fill_pos >= plen and out \
        else fill_pos
    seq = list(tokens) + list(out)
    pages = []
    for v in range(pages_for(kv_end, P) if kv_end > 0 else 0):
        cells = {
            pos - v * P: seq[pos]
            for pos in range(v * P, min((v + 1) * P, kv_end))
        }
        pages.append((v, cells))
    return SessionSnapshot(
        rid=0, tokens=list(tokens), max_new=max_new, temperature=0.0,
        eos=None, seed=1, out=list(out), fill_pos=fill_pos,
        kv_end=kv_end, page_tokens=P, pages=pages,
    )


def _engine_private_pages(eng):
    """Every page privately owned by a live engine row (slotted,
    prefilling, or parked by splice) — the ``private_pages`` argument
    the allocator's conservation check expects."""
    with eng._cv:
        rows = {r for r in eng._rows if r is not None}
        rows |= set(eng._prefilling)
        rows |= set(eng._spliced.values())
        rows |= set(eng._migrated.values())
        return [p for r in rows for p in r.private_pages]


def test_splice_preserves_allocator_invariants(depth):
    """Property: any sequence of splice/abort against a pod under
    arbitrary fabricated-session geometry preserves the allocator
    invariants at EVERY step, and a full abort pass restores the free
    count exactly — splice admission is transactional (a denied
    admission or missing page leaves no residue)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    session = st.tuples(
        st.lists(st.integers(0, _V - 1), min_size=1, max_size=24),
        st.lists(st.integers(0, _V - 1), min_size=1, max_size=12),
        st.integers(1, 20),
        st.integers(0, 40),
    )

    @hyp.settings(max_examples=20, deadline=None)
    @hyp.given(st.lists(session, min_size=1, max_size=5),
               st.integers(6, 30))
    def run(sessions, arena_pages):
        _a, pod = _make_pod(pages=arena_pages)
        try:
            free0 = pod.stats()["kv_pages_free"]
            spliced = []
            for tokens, out, extra, fill in sessions:
                snap = _fabricated_snapshot(
                    tokens, out, max_new=len(out) + extra, fill=fill
                )
                if len(tokens) + snap.max_new > 64:
                    continue
                try:
                    spliced.append(pod.splice(snap))
                except MigrationError:
                    pass  # denied admission must leave no residue
                pod._allocator.check_invariants(
                    _engine_private_pages(pod))
            for rid in spliced:
                pod.abort_splice(rid)
                pod._allocator.check_invariants(
                    _engine_private_pages(pod))
            assert pod.stats()["kv_pages_free"] == free0
        finally:
            pod.stop()

    run()


def test_splice_abort_sweep_restores_arena(depth):
    """Deterministic complement to the hypothesis property (runs even
    where hypothesis is absent): a seeded sweep of splice/abort under
    varied geometry and arena pressure leaves zero residue."""
    import random

    rng = random.Random(7)
    for arena_pages in (6, 12, 30):
        _a, pod = _make_pod(pages=arena_pages)
        try:
            free0 = pod.stats()["kv_pages_free"]
            spliced = []
            for _ in range(12):
                plen = rng.randint(1, 24)
                n_out = rng.randint(1, 12)
                snap = _fabricated_snapshot(
                    [rng.randrange(_V) for _ in range(plen)],
                    [rng.randrange(_V) for _ in range(n_out)],
                    max_new=n_out + rng.randint(1, 20),
                    fill=rng.randint(0, plen),
                )
                if plen + snap.max_new > 64:
                    continue
                try:
                    spliced.append(pod.splice(snap))
                except MigrationError:
                    pass
                pod._allocator.check_invariants(
                    _engine_private_pages(pod))
            for rid in spliced:
                pod.abort_splice(rid)
                pod._allocator.check_invariants(
                    _engine_private_pages(pod))
            assert pod.stats()["kv_pages_free"] == free0
        finally:
            pod.stop()


def test_splice_rejects_incompatible_snapshots_cleanly(depth):
    _a, pod = _make_pod(pages=10)
    try:
        free0 = pod.stats()["kv_pages_free"]
        # geometry mismatch
        bad = _fabricated_snapshot([1, 2, 3], [4], max_new=4, fill=3)
        bad.page_tokens = 8
        with pytest.raises(MigrationError, match="geometry"):
            pod.splice(bad)
        # missing page payloads
        holey = _fabricated_snapshot(list(range(9)), [4, 5],
                                     max_new=6, fill=9)
        holey.pages = holey.pages[:1]
        with pytest.raises(MigrationError, match="missing pages"):
            pod.splice(holey)
        # too big for the whole arena
        huge = _fabricated_snapshot(list(range(40)), [1],
                                    max_new=20, fill=40)
        with pytest.raises(MigrationError):
            pod.splice(huge)
        assert pod.stats()["kv_pages_free"] == free0
        pod._allocator.check_invariants()
    finally:
        pod.stop()


# -- drain-with-migration ----------------------------------------------


def test_drain_sessions_moves_every_live_session(depth):
    _sa, src = _make_pod()
    _d1, dst_big = _make_pod(pages=40)
    _d2, dst_small = _make_pod(pages=12)
    try:
        prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [4, 4, 4, 4]]
        n = 28
        results = [{} for _ in prompts]
        threads = [
            _submit_async(src, p, n, r)
            for p, r in zip(prompts, results)
        ]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and (
            len(src.sessions()) < len(prompts)
            or any(s["state"] != "decode" for s in src.sessions())
        ):
            time.sleep(0.005)
        report = drain_sessions(
            src, {"big": dst_big, "small": dst_small},
        )
        assert len(report) == len(prompts)
        assert all(row["ok"] for row in report), report
        # the report carries the prompt for claim re-pointing
        assert sorted(tuple(r["tokens"]) for r in report) == \
            sorted(tuple(p) for p in prompts)
        for t in threads:
            t.join(timeout=15)
        by_prompt = {
            tuple(r["tokens"]): r for r in report
        }
        dests = {"big": dst_big, "small": dst_small}
        for prompt, result in zip(prompts, results):
            err = result["r"]
            assert isinstance(err, SessionMigratedError), err
            out = dests[err.moved_to].collect(err.dest_rid, timeout=20)
            assert out == _chain_oracle(prompt, n)
            assert by_prompt[tuple(prompt)]["dest"] == err.moved_to
        assert src.sessions() == []
        assert src.stats()["migrations_out"] == len(prompts)
        for pod in (src, dst_big, dst_small):
            pod._allocator.check_invariants()
    finally:
        src.stop()
        dst_big.stop()
        dst_small.stop()


def test_drain_with_no_viable_destination_resumes_sessions(depth):
    """A drain that cannot place a session reports ok=False and the
    legacy wait-out covers it — migration never strands a client."""
    _sa, src = _make_pod()
    _da, tiny = _make_pod(pages=3)  # cannot admit anything real
    try:
        prompt = list(range(12))
        n = 20
        result = {}
        t = _submit_async(src, prompt, n, result)
        _wait_mid_decode(src)
        report = drain_sessions(src, {"tiny": tiny})
        assert len(report) == 1 and not report[0]["ok"]
        t.join(timeout=15)
        assert result["r"] == [_chain_oracle(prompt, n)]
        src._allocator.check_invariants()
        tiny._allocator.check_invariants()
    finally:
        src.stop()
        tiny.stop()


# -- the router consumers ----------------------------------------------


def _router(send, **kw):
    r = RequestRouter(send, page_tokens=P, **kw)
    return r


def test_router_drain_with_migration_repoints_claims():
    r = _router(lambda n, a, req: [[1]])
    r.update_pods({"pod-0": {"address": "h0:1"},
                   "pod-1": {"address": "h1:1"}})
    prompt = list(range(16))
    # park claims on pod-0 through the public request path
    for p in ("pod-0", "pod-1"):
        r.observe_stats(p, {"queue_depth": 0, "stats_age_s": 0.0,
                            "t": time.time()})
    while r.route(prompt) != "pod-0":
        r.update_pods({"pod-0": {"address": "h0:1"},
                       "pod-1": {"address": "h1:1"}},
                      generation=None)
        break
    r.submit(prompt, 4)
    owner = r._affinity.claims_by_pod()
    (claimed_pod,) = owner
    other = "pod-1" if claimed_pod == "pod-0" else "pod-0"
    claims = owner[claimed_pod]
    # drain WITH migration: claims re-point to the destination
    assert r.drain(claimed_pod, migrated_to=other)
    assert r._affinity.claims_by_pod() == {other: claims}
    assert r.stats()["router_chain_repoints"] == claims
    # the drained pod no longer takes traffic
    assert r.route(prompt) == other
    # legacy drain (no destination): the other pod's claims die
    assert r.drain(other)
    assert r._affinity.claims_by_pod() == {}


def test_router_repoint_prompt_moves_one_chain():
    r = _router(lambda n, a, req: [[1]])
    r.update_pods({"pod-0": {"address": "h0:1"},
                   "pod-1": {"address": "h1:1"}})
    prompt = list(range(12))
    r.submit(prompt, 4)
    moved = r.repoint_prompt(prompt, "pod-1")
    assert moved > 0
    assert r._affinity.claims_by_pod() == {"pod-1": moved}


def test_router_follows_migrated_session():
    """A pod answering 409-migrated mid-request: the router collects
    from the destination and the client sees one seamless reply."""
    calls = []

    def send(name, address, request):
        calls.append((name, dict(request)))
        if "collect" in request:
            assert name == "pod-1"
            assert request["collect"] == 55
            return [[7, 8, 9]]
        raise SessionMigratedError(3, "pod-1", 55)

    r = _router(send)
    r.update_pods({"pod-0": {"address": "h0:1"},
                   "pod-1": {"address": "h1:1"}})
    # make pod-0 the routed target (fresh, lower load)
    r.observe_stats("pod-0", {"queue_depth": 0, "stats_age_s": 0.0,
                              "t": time.time()})
    out = r.submit([1, 2, 3], 8)
    assert out == [7, 8, 9]
    assert r.stats()["router_migration_follows"] == 1
    assert calls[-1][1] == {"collect": 55}


def test_router_routes_long_prompts_to_prefill_pods():
    sent = []
    r = _router(lambda n, a, req: sent.append(n) or [[1]])
    r.update_pods({
        "prefill-0": {"address": "p0:1", "role": "prefill"},
        "decode-0": {"address": "d0:1", "role": "decode"},
        "decode-1": {"address": "d1:1", "role": "decode"},
    })
    long_prompt = list(range(4 * P))   # the auto threshold
    short_prompt = [1, 2, 3]
    assert r.route(long_prompt) == "prefill-0"
    assert r.route(short_prompt).startswith("decode-")
    assert r.stats()["router_prefill_pods"] == 1
    # inert without prefill capacity: roles all-unified change nothing
    r2 = _router(lambda n, a, req: [[1]])
    r2.update_pods({"pod-0": {"address": "h0:1"},
                    "pod-1": {"address": "h1:1"}})
    assert r2.route(long_prompt) in ("pod-0", "pod-1")


def test_router_role_follows_pod_stats():
    """A pod's own serving_role gauge refines the discovery role —
    the pod is authoritative about its posture."""
    r = _router(lambda n, a, req: [[1]])
    r.update_pods({"pod-0": {"address": "h0:1"},
                   "pod-1": {"address": "h1:1"}})
    r.observe_stats("pod-0", {"serving_role": "prefill",
                              "stats_age_s": 0.0, "t": time.time()})
    assert r.describe()["pods"]["pod-0"]["role"] == "prefill"
    assert r.route(list(range(4 * P))) == "pod-0"


def test_rebalance_suggestion_flags_prefix_hotspot():
    r = _router(lambda n, a, req: [[1]])
    r.update_pods({"hot": {"address": "h0:1"},
                   "cold": {"address": "h1:1"}})
    now = time.time()
    r.observe_stats("hot", {"queue_depth": 9, "stats_age_s": 0.0,
                            "t": now})
    r.observe_stats("cold", {"queue_depth": 0, "stats_age_s": 0.0,
                             "t": now})
    # weld claims onto the hot pod
    for i in range(10):
        r._affinity.record([i + 1], "hot")
    suggestion = r.rebalance_suggestion(min_claims=8, min_skew=2.0)
    assert suggestion is not None
    assert suggestion["from"] == "hot" and suggestion["to"] == "cold"
    assert suggestion["claims"] >= 8 and suggestion["load_gap"] > 0
    # balanced fleet: no suggestion
    for i in range(10):
        r._affinity.record([100 + i], "cold")
    r.observe_stats("cold", {"queue_depth": 9, "stats_age_s": 0.0,
                             "t": time.time()})
    assert r.rebalance_suggestion(min_claims=8, min_skew=2.0) is None


# -- role-aware health -------------------------------------------------


def test_prefill_pod_judged_on_backlog_not_occupancy():
    slo = ServingSloWatcher(kv_occupancy_slo=0.9,
                            kv_pages_free_slo=8,
                            prefill_backlog_slo=64,
                            stale_stats_s=0.0)
    # a prefill pod transiently pinning pages between handoffs: its
    # decode-occupancy gauges are meaningless and must not breach
    events = slo.observe({"serve-0-node": {
        "serving_role": "prefill", "kv_occupancy": 0.99,
        "kv_pages_free": 1, "prefill_chunk_backlog": 500,
    }})
    signals = {e["signal"] for e in events}
    assert signals == {"prefill_chunk_backlog"}, events
    # the same gauges on a unified pod breach both kv signals
    slo2 = ServingSloWatcher(kv_occupancy_slo=0.9,
                             kv_pages_free_slo=8,
                             prefill_backlog_slo=64,
                             stale_stats_s=0.0)
    events = slo2.observe({"serve-0-node": {
        "serving_role": "unified", "kv_occupancy": 0.99,
        "kv_pages_free": 1, "prefill_chunk_backlog": 500,
    }})
    assert {e["signal"] for e in events} == {
        "kv_occupancy", "kv_pages_free", "prefill_chunk_backlog"
    }


def test_quiet_watcher_ignores_prefill_idle_decode_gauges():
    """The flap fix: a prefill pod saturated with prompt work is NOT
    quiet (its backlog says so), even though its decode gauges sit at
    idle values by design; a genuinely idle prefill pod IS quiet."""
    slo = ServingSloWatcher(kv_occupancy_slo=0.9,
                            prefill_backlog_slo=64,
                            stale_stats_s=0.0)
    quiet = QuietPodWatcher(slo, quiet_factor=0.25)
    busy = {"serving_role": "prefill", "kv_occupancy": 0.0,
            "prefill_chunk_backlog": 500}
    assert quiet._is_quiet(busy, {}) is False
    idle = {"serving_role": "prefill", "kv_occupancy": 0.0,
            "prefill_chunk_backlog": 0}
    assert quiet._is_quiet(idle, {}) is True
    # a unified pod's occupancy still attests load the usual way
    loaded = {"serving_role": "unified", "kv_occupancy": 0.8,
              "prefill_chunk_backlog": 0}
    assert quiet._is_quiet(loaded, {}) is False


# -- prefill/decode disaggregation -------------------------------------


def test_prefill_handoff_streams_finished_pages_to_decode_pool(depth):
    pods = {}
    handoff = PrefillHandoff(lambda: pods)
    _pa, prefill = _make_pod(role="prefill", handoff=handoff)
    _d1, decode_a = _make_pod(role="decode", pages=40)
    _d2, decode_b = _make_pod(role="decode", pages=12)
    pods["decode-a"] = decode_a
    pods["decode-b"] = decode_b
    try:
        prompt = list(range(1, 14))
        n = 30
        with pytest.raises(SessionMigratedError) as exc:
            prefill.submit([prompt], n)
        err = exc.value
        # ranked by free pages: the big pool wins
        assert err.moved_to == "decode-a"
        out = pods[err.moved_to].collect(err.dest_rid, timeout=20)
        assert out == _chain_oracle(prompt, n)
        assert handoff.handoffs == 1 and handoff.fallbacks == 0
        assert prefill.stats()["serving_role"] == "prefill"
        assert prefill.sessions() == []
        for pod in (prefill, decode_a, decode_b):
            pod._allocator.check_invariants()
    finally:
        prefill.stop()
        decode_a.stop()
        decode_b.stop()


def test_prefill_pod_degrades_to_local_decode_without_pool(depth):
    """No decode pod answers: the handoff falls back and the prefill
    pod decodes locally — disaggregation degrades to unified, never
    to a failed request."""
    handoff = PrefillHandoff(lambda: {})
    _pa, prefill = _make_pod(role="prefill", handoff=handoff)
    try:
        prompt = [5, 4, 3, 2, 1]
        n = 16
        out = prefill.submit([prompt], n)
        assert out == [_chain_oracle(prompt, n)]
        assert handoff.fallbacks == 1 and handoff.handoffs == 0
        prefill._allocator.check_invariants()
    finally:
        prefill.stop()
