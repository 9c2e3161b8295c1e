"""racecheck: thread-ownership static analysis + happens-before races.

Per-rule fixtures for the static half (violation caught, suppression
honored, and the legal idioms — common lock, queue handoff, *_locked
convention, `# racecheck: handoff=` annotation — stay quiet), plus
the dynamic vector-clock checker: a seeded two-thread race is flagged
with both stacks, every ordering edge (lock, queue, start/join,
Event, Condition) suppresses the pair, and the PR 16 bug class
(foreign-thread splice installing a row mid-decode-tick) has a
dedicated regression: the pre-fix shape races, the real PagedEngine
protocol runs clean under full instrumentation.

The repo-wide gate (zero findings, empty baseline) lives in
tests/test_lint_gate.py next to the other analyzers' gates.
"""

import os
import queue
import textwrap
import threading
import time

import numpy as np

from dcos_commons_tpu.analysis import lockcheck, racecheck
from dcos_commons_tpu.analysis.racecheck import (
    RULE_CALLBACK,
    RULE_CHECK_THEN_ACT,
    RULE_COLLECTIVE,
    RULE_LOCK_CYCLE,
    RULE_UNGUARDED,
    RULE_UNORDERED,
    race_rule_catalog,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _race_fixture(tmp_path, source, rule_id=None,
                  rel="dcos_commons_tpu/mod.py"):
    """Analyze one fixture file placed at ``rel`` under a fake repo
    root; returns the RaceResult plus (findings, suppressed) filtered
    to ``rule_id``."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    result = racecheck.analyze_paths([str(path)], str(tmp_path))
    pick = lambda fs: [f for f in fs if rule_id is None or f.rule == rule_id]  # noqa: E731
    return result, pick(result.findings), pick(result.suppressed)


# -- race-unguarded-shared-write --------------------------------------


_PUMP = """
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self.level = 0
        self._t = None

    def start(self):
        self._t = threading.Thread(target=self._loop, name="pump-loop")
        self._t.start()

    def _loop(self):
        self.level = 1

    def set_level(self, n):
        self.level = n
"""


def test_rule_unguarded_shared_write(tmp_path):
    result, findings, _ = _race_fixture(tmp_path, _PUMP, RULE_UNGUARDED)
    assert len(findings) == 1
    assert "Pump.level" in findings[0].message
    assert "pump-loop" in findings[0].message
    # the flagged attr is in the dynamic probe set
    assert "level" in result.shared_attrs.get("Pump", [])
    # ...and the discovered thread role is surfaced for the trend keys
    assert "pump-loop" in result.roles.get("Pump", [])
    # sdklint suppression on the write line is honored
    suppressed_src = _PUMP.replace(
        "        self.level = 1",
        "        self.level = 1  "
        "# sdklint: disable=race-unguarded-shared-write — fixture",
    )
    result, findings, suppressed = _race_fixture(
        tmp_path, suppressed_src, RULE_UNGUARDED
    )
    assert not findings and len(suppressed) == 1
    # a triaged attr leaves the probe set: the rationale, not a lock,
    # orders those writes — the dynamic checker must not re-flag it
    assert "level" not in result.shared_attrs.get("Pump", [])


def test_rule_unguarded_common_lock_is_clean(tmp_path):
    guarded = _PUMP.replace(
        "        self.level = 1",
        "        with self._lock:\n            self.level = 1",
    ).replace(
        "        self.level = n",
        "        with self._lock:\n            self.level = n",
    )
    result, findings, _ = _race_fixture(tmp_path, guarded, RULE_UNGUARDED)
    assert not findings
    # guarded sharing stays in the probe set (the dynamic half checks
    # the lock is actually sufficient at runtime)
    assert "level" in result.shared_attrs.get("Pump", [])


def test_rule_unguarded_handoff_annotation_exempts(tmp_path):
    annotated = _PUMP.replace(
        "        self.level = 1",
        "        # racecheck: handoff=monotonic flip, readers tolerate"
        " either value\n        self.level = 1",
    )
    result, findings, suppressed = _race_fixture(
        tmp_path, annotated, RULE_UNGUARDED
    )
    assert not findings and len(suppressed) == 1
    assert "level" not in result.shared_attrs.get("Pump", [])


def test_rule_unguarded_queue_handoff_is_clean(tmp_path):
    src = """
    import queue
    import threading

    class Mailbox:
        def __init__(self):
            self._inbox = queue.Queue()

        def start(self):
            t = threading.Thread(target=self._loop, name="mail-loop")
            t.start()

        def post(self, msg):
            self._inbox.put(msg)

        def _loop(self):
            while True:
                self._inbox.get()
    """
    _, findings, _ = _race_fixture(tmp_path, src, RULE_UNGUARDED)
    assert not findings


def test_rule_unguarded_locked_convention_is_clean(tmp_path):
    src = """
    import threading

    class Board:
        def __init__(self):
            self._lock = threading.Lock()
            self._view = ()
            self._cells = {}

        def start(self):
            t = threading.Thread(target=self._loop, name="board-loop")
            t.start()

        def _loop(self):
            with self._lock:
                self._cells["tick"] = 1
                self._rebuild_locked()

        def put(self, k, v):
            with self._lock:
                self._cells[k] = v
                self._rebuild_locked()

        def _rebuild_locked(self):
            self._view = tuple(self._cells)

        def view(self):
            return self._view
    """
    result, findings, _ = _race_fixture(tmp_path, src, RULE_UNGUARDED)
    assert not findings
    # both shared attrs probe-eligible; the snapshot read needs no lock
    assert set(result.shared_attrs.get("Board", [])) == {
        "_cells", "_view",
    }


# -- race-callback-thread ---------------------------------------------


_METER = """
import threading

class Meter:
    def __init__(self):
        self._events = []
        self._t = None

    def start(self, registry):
        self._t = threading.Thread(target=self._loop, name="meter-loop")
        self._t.start()
        registry.subscribe(lambda e: self._events.append(e))

    def _loop(self):
        pass
"""


def test_rule_callback_thread(tmp_path):
    _, findings, _ = _race_fixture(tmp_path, _METER, RULE_CALLBACK)
    assert len(findings) == 1
    assert "self._events" in findings[0].message
    suppressed_src = _METER.replace(
        "        registry.subscribe(lambda e: self._events.append(e))",
        "        registry.subscribe(lambda e: self._events.append(e))  "
        "# sdklint: disable=race-callback-thread — registry is "
        "single-threaded",
    )
    _, findings, suppressed = _race_fixture(
        tmp_path, suppressed_src, RULE_CALLBACK
    )
    assert not findings and len(suppressed) == 1


# -- race-collective-offloop ------------------------------------------


_TRAINER = """
import threading
from jax import lax

class Trainer:
    def start(self):
        t = threading.Thread(target=self._loop, name="train-loop")
        t.start()

    def _loop(self):
        lax.psum(1, "dp")
"""


def test_rule_collective_offloop(tmp_path):
    _, findings, _ = _race_fixture(tmp_path, _TRAINER, RULE_COLLECTIVE)
    assert len(findings) == 1
    assert "psum" in findings[0].message
    assert "train-loop" in findings[0].message
    suppressed_src = _TRAINER.replace(
        '        lax.psum(1, "dp")',
        '        lax.psum(1, "dp")  '
        "# sdklint: disable=race-collective-offloop — single-host tool",
    )
    _, findings, suppressed = _race_fixture(
        tmp_path, suppressed_src, RULE_COLLECTIVE
    )
    assert not findings and len(suppressed) == 1


# -- race-check-then-act ----------------------------------------------


_LEDGER = """
import threading

class Ledger:
    def __init__(self):
        self._lock = threading.Lock()
        self._balance = 100

    def start(self):
        t = threading.Thread(target=self._drain, name="ledger-drain")
        t.start()

    def _drain(self):
        with self._lock:
            balance = self._balance
        fee = balance // 10
        with self._lock:
            self._balance = balance - fee
"""


def test_rule_check_then_act(tmp_path):
    _, findings, _ = _race_fixture(tmp_path, _LEDGER, RULE_CHECK_THEN_ACT)
    assert len(findings) == 1
    assert "`balance`" in findings[0].message
    assert "_balance" in findings[0].message
    # merging the critical sections is the fix — and is clean
    merged = """
    import threading

    class Ledger:
        def __init__(self):
            self._lock = threading.Lock()
            self._balance = 100

        def start(self):
            t = threading.Thread(target=self._drain, name="ledger-drain")
            t.start()

        def _drain(self):
            with self._lock:
                balance = self._balance
                self._balance = balance - balance // 10
    """
    _, findings, _ = _race_fixture(tmp_path, merged, RULE_CHECK_THEN_ACT)
    assert not findings
    suppressed_src = _LEDGER.replace(
        "            self._balance = balance - fee",
        "            self._balance = balance - fee  "
        "# sdklint: disable=race-check-then-act — drain is the only "
        "writer",
    )
    _, findings, suppressed = _race_fixture(
        tmp_path, suppressed_src, RULE_CHECK_THEN_ACT
    )
    assert not findings and len(suppressed) == 1


# -- no false positives on the legal idioms together ------------------


def test_clean_threaded_module_has_zero_findings(tmp_path):
    """A realistic server using every legal idiom at once — queue
    handoff in, common-lock stats, *_locked snapshot rebuild, lock-free
    snapshot reads — produces not one finding."""
    src = """
    import queue
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()
            self._stats = {}
            self._snapshot = ()

        def start(self):
            t = threading.Thread(target=self._loop, name="server-loop")
            t.start()

        def submit(self, item):
            self._q.put(item)
            with self._lock:
                self._stats["submitted"] = 1
                self._publish_locked()

        def _loop(self):
            while True:
                item = self._q.get()
                with self._lock:
                    self._stats["served"] = item
                    self._publish_locked()

        def _publish_locked(self):
            self._snapshot = tuple(self._stats)

        def peek(self):
            return self._snapshot
    """
    result, findings, _ = _race_fixture(tmp_path, src)
    assert not findings, [f.render() for f in findings]
    assert set(result.shared_attrs.get("Server", [])) == {
        "_snapshot", "_stats",
    }
    assert "server-loop" in result.roles.get("Server", [])


def test_race_rule_catalog_lists_every_rule():
    catalog = race_rule_catalog()
    for rid in (RULE_UNGUARDED, RULE_CALLBACK, RULE_COLLECTIVE,
                RULE_CHECK_THEN_ACT, RULE_LOCK_CYCLE, RULE_UNORDERED):
        assert rid in catalog


def test_env_var_and_lockcheck_alias(monkeypatch):
    """SDKLINT_LOCKCHECK stays a working alias for the unified
    checker: same switch, same report."""
    monkeypatch.delenv("SDKLINT_RACECHECK", raising=False)
    monkeypatch.delenv("SDKLINT_LOCKCHECK", raising=False)
    assert not racecheck.env_requested()
    monkeypatch.setenv("SDKLINT_LOCKCHECK", "1")
    assert racecheck.env_requested()
    assert lockcheck.env_requested()
    monkeypatch.setenv("SDKLINT_RACECHECK", "1")
    monkeypatch.delenv("SDKLINT_LOCKCHECK")
    assert racecheck.env_requested()
    assert lockcheck.ENV_VAR == "SDKLINT_LOCKCHECK"
    assert lockcheck.install is racecheck.install
    assert lockcheck.report is racecheck.report


# -- dynamic half: vector clocks --------------------------------------


def _dyn(case):
    """Run one scenario under instrumentation; returns the report.
    Mirrors the lockcheck_guard idiom: when the session checker is
    active, leave it installed."""
    already = racecheck.is_enabled()
    racecheck.install()
    racecheck.reset()
    try:
        case()
        return racecheck.report()
    finally:
        racecheck.unwatch_types()
        if not already:
            racecheck.uninstall()
        racecheck.reset()


class _Box:
    def __init__(self):
        self.n = 0


def test_dynamic_seeded_two_thread_race_reports_both_stacks():
    box = _Box()

    def case():
        racecheck.watch_type(_Box, ("n",))

        def writer(v):
            box.n = v

        t1 = threading.Thread(target=writer, args=(1,), daemon=True)
        t2 = threading.Thread(target=writer, args=(2,), daemon=True)
        t1.start(); t2.start()
        t1.join(timeout=5); t2.join(timeout=5)

    rep = _dyn(case)
    assert rep.races, rep.describe()
    rec = rep.races[0]
    assert rec.cls == "_Box" and rec.attr == "n"
    assert rec.thread_a != rec.thread_b
    # both writes carry their stacks, pointing back into this test
    assert "test_racecheck" in rec.stack_a
    assert "test_racecheck" in rec.stack_b
    assert RULE_UNORDERED in rep.describe()


def test_dynamic_ordering_edges_suppress_the_pair():
    """The same two-writer shape, ordered four different ways — lock,
    queue handoff, start/join fork, Condition — never races."""
    box = _Box()

    def locked():
        racecheck.watch_type(_Box, ("n",))
        guard = threading.Lock()

        def writer(v):
            with guard:
                box.n = v

        ts = [threading.Thread(target=writer, args=(v,), daemon=True)
              for v in (1, 2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=5)

    def queued():
        racecheck.watch_type(_Box, ("n",))
        q = queue.Queue()

        def producer():
            box.n = 1
            q.put("go")

        def consumer():
            q.get()
            box.n = 2

        t1 = threading.Thread(target=producer, daemon=True)
        t2 = threading.Thread(target=consumer, daemon=True)
        t1.start(); t2.start()
        t1.join(timeout=5); t2.join(timeout=5)

    def forked():
        racecheck.watch_type(_Box, ("n",))
        box.n = 1
        t = threading.Thread(
            target=lambda: setattr(box, "n", 2), daemon=True
        )
        t.start(); t.join(timeout=5)
        box.n = 3

    def notified():
        racecheck.watch_type(_Box, ("n",))
        cv = threading.Condition(threading.Lock())
        ready = []

        def early():
            with cv:
                box.n = 1
                ready.append(True)
                cv.notify()

        def late():
            with cv:
                while not ready:
                    cv.wait(timeout=5)
                box.n = 2

        t2 = threading.Thread(target=late, daemon=True)
        t1 = threading.Thread(target=early, daemon=True)
        t2.start(); t1.start()
        t1.join(timeout=5); t2.join(timeout=5)

    for case in (locked, queued, forked, notified):
        rep = _dyn(case)
        assert not rep.races, (case.__name__, rep.describe())


def test_dynamic_lock_cycle_is_the_race_lock_cycle_rule():
    """PR 2's deadlock detection lives on inside racecheck, reported
    under the race-lock-cycle rule id."""
    def case():
        a = threading.Lock()
        b = threading.Lock()

        def order_ab():
            with a:
                with b:
                    pass

        def order_ba():
            with b:
                with a:
                    pass

        t1 = threading.Thread(target=order_ab, daemon=True)
        t1.start(); t1.join(timeout=5)
        t2 = threading.Thread(target=order_ba, daemon=True)
        t2.start(); t2.join(timeout=5)

    rep = _dyn(case)
    assert len(rep.cycles) == 1, rep.describe()
    assert RULE_LOCK_CYCLE in rep.describe()
    assert not rep.races


# -- the PR 16 regression: foreign-thread splice mid-tick -------------


class _ToyRow:
    def __init__(self):
        self.last_token = 0


def test_pr16_prefix_shape_foreign_splice_races():
    """The bug class PR 16 fixed, reduced to its shape: a decode-tick
    thread samples into a row it picked up OUTSIDE any identity
    snapshot, while a migration thread splice-installs state into the
    same row.  Without the dispatched-row discipline the two writes
    are unordered — the checker flags them with both stacks."""
    row = _ToyRow()

    def case():
        racecheck.watch_type(_ToyRow, ("last_token",))

        def tick_loop():
            for i in range(50):
                row.last_token = i  # pre-fix: no identity check, no cv

        def splice():
            time.sleep(0.001)
            row.last_token = 999  # foreign-thread install mid-tick

        t1 = threading.Thread(target=tick_loop, daemon=True)
        t2 = threading.Thread(target=splice, daemon=True)
        t1.start(); t2.start()
        t1.join(timeout=5); t2.join(timeout=5)

    rep = _dyn(case)
    assert rep.races, rep.describe()
    rec = rep.races[0]
    assert rec.attr == "last_token"
    assert rec.stack_a and rec.stack_b


_P = 4  # page tokens for the toy arena


class _Arena:
    """Content-free device half for the real-engine drive: decode is
    tok+1, prefill stores tokens so page export/import has payload."""

    def __init__(self):
        self.cells = {}
        self.lock = threading.Lock()

    def prefill_chunk(self, padded, slot, table, start, true_len,
                      temp, seed):
        with self.lock:
            for i in range(true_len):
                pos = start + i
                page = int(table[pos // _P])
                self.cells.setdefault(page, {})[pos % _P] = int(
                    padded[0, i]
                )
        return 1

    def decode(self, tok, pos, temps, seeds, tables, n_active):
        time.sleep(0.002)
        return np.asarray(
            [(int(t) + 1) % 50 for t in tok], np.int32
        )

    def read_page(self, page):
        with self.lock:
            return dict(self.cells.get(page, {}))

    def write_page(self, page, payload):
        with self.lock:
            self.cells[page] = dict(payload)


def test_pr16_real_engine_splice_mid_tick_is_ordered():
    """The fixed protocol under full instrumentation: a live
    PagedEngine decodes while migrate_session freezes, streams, and
    cutover-activates the session on a peer from a foreign thread.
    Every engine-state write the static pass calls shared must be
    ordered by the cv — zero unordered pairs, and the migrated
    session still completes."""
    from dcos_commons_tpu.serve.engine import PagedEngine
    from dcos_commons_tpu.serve.migration import (
        SessionMigratedError,
        migrate_session,
    )

    def make_pod(role):
        arena = _Arena()
        eng = PagedEngine(
            arena.prefill_chunk, arena.decode, 3, 64, 48,
            page_tokens=_P, pages=40, chunk_tokens=8,
            prefix_cache=True, role=role,
            read_page=arena.read_page, write_page=arena.write_page,
            queue_timeout_s=30,
        )
        return eng

    outcome = {}

    def case():
        shared = racecheck.shared_write_map(REPO)
        attrs = shared.get(PagedEngine.__name__)
        if attrs:
            racecheck.watch_type(PagedEngine, attrs)
        src = make_pod("source")
        dst = make_pod("dest")
        try:
            result = {}

            def client():
                try:
                    result["r"] = src.submit([[3, 1, 4, 1, 5]], 24)
                except BaseException as e:  # noqa: BLE001 — assertion target
                    result["r"] = e

            t = threading.Thread(target=client, daemon=True)
            t.start()
            deadline = time.monotonic() + 10
            rid = None
            while time.monotonic() < deadline:
                sess = src.sessions()
                if sess and sess[0]["state"] == "decode" \
                        and src.stats()["tokens_out"] >= 4:
                    rid = sess[0]["rid"]
                    break
                time.sleep(0.005)
            assert rid is not None, "session never reached mid-decode"
            record = migrate_session(src, dst, rid, dest_name="dst")
            assert record.ok, record
            t.join(timeout=15)
            err = result["r"]
            assert isinstance(err, SessionMigratedError), err
            outcome["out"] = dst.collect(err.dest_rid, timeout=20)
        finally:
            src.stop()
            dst.stop()

    rep = _dyn(case)
    assert not rep.races, rep.describe()
    assert len(outcome["out"]) == 24
