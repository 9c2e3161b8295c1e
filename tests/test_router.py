"""Serving front door (ISSUE 12): routing, affinity, drain, failover.

Four layers of coverage:

* AFFINITY units (no engines): the page-aligned chain keys mirror the
  paging intern construction (full pages only, last page capped), and
  the bounded affinity map LRU-evicts and drops a dead pod's claims.

* CORE ROUTER against scripted pods (no engines): least-loaded
  placement off fresh gauges, the staleness gate (a wedged pod's
  last-good numbers never steer placement), drain exclusion,
  affinity-follows-the-cache with the load-slack override, honest
  retry budgets, and application errors passing through un-retried.

* FAILOVER against REAL engines (the satellite): kill a pod
  mid-stream — queued and in-flight requests complete on survivors,
  every greedy continuation arrives exactly once (no duplicates), and
  the dead/drained pod receives zero new admissions.

* FRONT DOOR over real sockets: discovery against a scripted
  endpoint body (generation-stamped refresh skips quiet rebuilds),
  /generate proxying with pod-error pass-through, /stats gauges, and
  the drain verbs.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from dcos_commons_tpu.router import (
    AffinityMap,
    NoPodAvailableError,
    PodTransportError,
    RequestRouter,
    prefix_chain_keys,
)
from dcos_commons_tpu.serve.engine import PagedEngine
from dcos_commons_tpu.testing.chain_model import (
    ChainModel,
    chain_oracle as _chain_oracle,
)



def _chain_engine(model, slots, **kw):
    """A real engine over the chain model, a whole prompt a chunk and
    every row resident: a request waits for a row, never a page."""
    return PagedEngine(
        model.prefill_chunk, model.decode, slots, 64, 32,
        page_tokens=model.page_tokens,
        pages=slots * (64 // model.page_tokens), chunk_tokens=32,
        prefix_cache=False, **kw,
    )


# -- affinity units ----------------------------------------------------


def test_prefix_chain_keys_page_aligned_and_capped():
    p = 4
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    # 9 tokens / page 4: limit = (9-1)//4 = 2 full pages participate
    keys = prefix_chain_keys(a, p)
    assert len(keys) == 2
    # identical page-aligned prefix -> identical chain
    b = [1, 2, 3, 4, 5, 6, 7, 8, 42]
    assert prefix_chain_keys(b, p) == keys
    # divergence in the SECOND page breaks only the deeper key
    c = [1, 2, 3, 4, 9, 9, 9, 9, 1]
    keys_c = prefix_chain_keys(c, p)
    assert keys_c[0] == keys[0] and keys_c[1] != keys[1]
    # an exactly-one-page prompt is capped to ZERO keys (>= 1 token
    # always prefills privately — the paging hit cap, mirrored)
    assert prefix_chain_keys([1, 2, 3, 4], p) == []
    assert prefix_chain_keys([1, 2, 3, 4, 5], p) != []
    assert prefix_chain_keys([], p) == []


def test_affinity_map_records_lru_evicts_and_drops_dead_pods():
    m = AffinityMap(capacity=3)
    m.record([101, 102], "pod-a")
    m.record([201], "pod-b")
    assert m.lookup([101, 102]) == ("pod-a", 2)
    assert m.lookup([101, 999]) == ("pod-a", 1)  # deepest known wins
    assert m.lookup([999]) == (None, 0)
    # capacity 3 full; recording a 4th evicts the LRU entry (201 was
    # refreshed by its lookup? no — 101/102 were looked up later)
    m.record([301], "pod-c")
    assert len(m) == 3
    assert m.lookup([201]) == (None, 0)  # the oldest claim evicted
    # a dead pod's claims vanish wholesale
    assert m.evict_pod("pod-a") == 2
    assert m.lookup([101, 102]) == (None, 0)


# -- core router against scripted pods ---------------------------------


def _router(send, pods=("a", "b"), policy="affinity", **kw):
    r = RequestRouter(send, page_tokens=4, policy=policy,
                      stale_after_s=5.0, **kw)
    r.update_pods({name: {"address": f"host-{name}:80"}
                   for name in pods}, generation="g1")
    return r


def _fresh(queue_depth=0, active=0, **kw):
    out = {"queue_depth": queue_depth, "active_slots": active,
           "free_slots": 8, "stats_age_s": 0.0}
    out.update(kw)
    return out


def test_router_least_loaded_placement_on_fresh_gauges():
    r = _router(lambda n, a, req: [[0]], policy="least-loaded")
    r.observe_stats("a", _fresh(queue_depth=5, active=3))
    r.observe_stats("b", _fresh(queue_depth=0, active=1))
    assert r.route([1, 2, 3]) == "b"
    r.observe_stats("b", _fresh(queue_depth=9, active=8))
    assert r.route([1, 2, 3]) == "a"


def test_router_staleness_gate_demotes_wedged_pod():
    """A pod whose engine loop stopped ticking reports a growing
    stats_age_s with last-good (idle-looking) gauges: it must rank
    behind any fresh pod regardless of those numbers."""
    r = _router(lambda n, a, req: [[0]], policy="least-loaded")
    # pod a LOOKS idle but its loop is wedged; pod b is honestly busy
    r.observe_stats("a", _fresh(queue_depth=0, active=0,
                                stats_age_s=60.0))
    r.observe_stats("b", _fresh(queue_depth=6, active=8))
    assert r.route([1, 2, 3]) == "b"
    stats = r.stats()
    assert stats["router_stale_routing_rounds"] == 0
    # ...and a poll that went dark ages out the same way
    r2 = _router(lambda n, a, req: [[0]], policy="least-loaded")
    r2.observe_stats("a", _fresh(queue_depth=0), now=time.monotonic() - 60)
    r2.observe_stats("b", _fresh(queue_depth=6))
    assert r2.route([1]) == "b"


def test_router_drain_excludes_new_admissions():
    picks = []
    r = _router(lambda n, a, req: picks.append(n) or [[0]])
    r.observe_stats("a", _fresh())
    r.observe_stats("b", _fresh())
    assert r.drain("a")
    for _ in range(4):
        r.submit([1, 2], 2)
    assert set(picks) == {"b"}
    stats = r.stats()
    assert stats["router_pods_draining"] == 1
    # undrain re-admits
    r.undrain("a")
    picks.clear()
    r.observe_stats("a", _fresh(queue_depth=0))
    r.observe_stats("b", _fresh(queue_depth=9))
    r.submit([1, 2], 2)
    assert picks == ["a"]
    # draining EVERY pod is a clean 503, not a hang
    r.drain("a"), r.drain("b")
    with pytest.raises(NoPodAvailableError):
        r.submit([1, 2], 2)


def test_router_affinity_follows_shared_prefix_and_yields_to_load():
    picks = []
    r = _router(lambda n, a, req: picks.append(n) or [[0]],
                affinity_slack=4.0)
    r.observe_stats("a", _fresh())
    r.observe_stats("b", _fresh())
    sys_prefix = list(range(1, 9))  # two full pages of 4
    first = sys_prefix + [50]
    r.submit(first, 2)
    owner = picks[0]
    # every shared-prefix request follows the owner...
    for i in range(5):
        r.submit(sys_prefix + [60 + i], 2)
    assert set(picks) == {owner}
    assert r.stats()["router_affinity_hits"] >= 5
    # ...until the owner is overloaded past the slack: load wins
    other = "b" if owner == "a" else "a"
    r.observe_stats(owner, _fresh(queue_depth=20, active=8))
    r.observe_stats(other, _fresh(queue_depth=0))
    picks.clear()
    r.submit(sys_prefix + [99], 2)
    assert picks == [other]
    assert r.stats()["router_affinity_overridden"] >= 1


def test_router_failover_honest_budget_and_app_error_passthrough():
    calls = []

    def send(name, address, request):
        calls.append(name)
        if name == "a":
            raise PodTransportError("connection reset")
        return [[7, 7]]

    r = _router(send, retry_budget=2)
    r.observe_stats("a", _fresh(queue_depth=0))
    r.observe_stats("b", _fresh(queue_depth=5))
    # a is least-loaded and picked first; its death fails over to b
    assert r.submit([1, 2], 2) == [7, 7]
    assert calls == ["a", "b"]
    stats = r.stats()
    assert stats["router_failovers"] == 1
    assert stats["router_pods_failed"] == 1
    # a stays off the rotation until a FRESH snapshot readmits it
    assert r.route([1, 2]) == "b"
    r.observe_stats("a", _fresh())
    assert r.stats()["router_pods_failed"] == 0

    # budget exhaustion surfaces the transport error (502), honestly
    def always_dead(name, address, request):
        raise PodTransportError("down")

    r2 = _router(always_dead, retry_budget=1)
    r2.observe_stats("a", _fresh())
    r2.observe_stats("b", _fresh())
    with pytest.raises(PodTransportError, match="budget 1 exhausted"):
        r2.submit([1, 2], 2)

    # an application error (the pod ANSWERED) is never retried
    attempts = []

    def app_error(name, address, request):
        attempts.append(name)
        raise ValueError("prompt too long")

    r3 = _router(app_error, retry_budget=2)
    r3.observe_stats("a", _fresh())
    r3.observe_stats("b", _fresh())
    with pytest.raises(ValueError):
        r3.submit([1, 2], 2)
    assert len(attempts) == 1


def test_router_generation_stamped_refresh_skips_quiet_rebuilds():
    r = RequestRouter(lambda n, a, req: [[0]], page_tokens=4)
    assert r.update_pods({"a": {"address": "h:1"}}, generation="g1")
    assert not r.update_pods({"a": {"address": "h:1"}}, generation="g1")
    assert r.update_pods({"a": {"address": "h:1"},
                          "b": {"address": "h:2"}}, generation="g2")
    assert r.pods() == ["a", "b"]
    # discovery-driven drain: a pausing backend stops admitting
    r.update_pods({"a": {"address": "h:1", "draining": True},
                   "b": {"address": "h:2"}}, generation="g3")
    r.observe_stats("a", _fresh())
    r.observe_stats("b", _fresh())
    assert r.route([1]) == "b"
    # a vanished pod leaves the set (and its affinity claims)
    r.update_pods({"b": {"address": "h:2"}}, generation="g4")
    assert r.pods() == ["b"]


def test_router_operator_drain_survives_discovery_refresh():
    """An operator drain is STICKY: a discovery refresh reporting
    the pod healthy (it IS still TASK_RUNNING scheduler-side while
    the runbook waits for in-flight work to finish) must not quietly
    re-admit it mid-decommission.  Only undrain() clears the verb."""
    r = _router(lambda n, a, req: [[0]])
    r.observe_stats("a", _fresh())
    r.observe_stats("b", _fresh())
    assert r.drain("a")
    # discovery refresh: scheduler still reports a healthy, undrained
    # backend set under a NEW generation (unrelated fleet churn)
    r.update_pods({"a": {"address": "host-a:80", "draining": False},
                   "b": {"address": "host-b:80"}}, generation="g2")
    r.observe_stats("a", _fresh())
    assert r.route([1, 2]) == "b"
    assert r.stats()["router_pods_draining"] == 1
    # the bare-address fallback (no generation: EVERY poll rebuilds)
    # must not undo it either
    r.update_pods({"a": {"address": "host-a:80"},
                   "b": {"address": "host-b:80"}})
    assert r.route([1, 2]) == "b"
    # only the operator verb clears the operator flag
    r.undrain("a")
    r.observe_stats("a", _fresh(queue_depth=0))
    r.observe_stats("b", _fresh(queue_depth=9))
    assert r.route([1, 2]) == "a"


# -- failover against REAL engines (the satellite test) ----------------


class EnginePod:
    """One in-process 'serve pod': an engine over the chain model,
    dialable through a send() that can be killed mid-stream."""

    def __init__(self, name, slots=4):
        self.name = name
        self.model = ChainModel()
        self.engine = _chain_engine(
            self.model, slots, queue_timeout_s=60
        )
        self.killed = threading.Event()
        self.admitted = 0
        self.completed = 0
        self._lock = threading.Lock()

    def send(self, request):
        if self.killed.is_set():
            raise PodTransportError(f"{self.name} is dead")
        with self._lock:
            self.admitted += 1
        result = self.engine.submit(
            request["tokens"], request["max_new_tokens"],
            temperature=request.get("temperature", 0.0),
            eos_id=request.get("eos"),
        )
        if self.killed.is_set():
            # died before the response left the pod: the bytes never
            # reached the router — exactly the mid-stream kill case
            raise PodTransportError(f"{self.name} died mid-stream")
        with self._lock:
            self.completed += 1
        return result

    def stop(self):
        self.engine.stop()


def test_router_pod_kill_mid_stream_completes_on_survivors():
    """The satellite: kill a pod mid-stream; queued + in-flight
    requests all complete on the survivors, each greedy continuation
    arrives exactly once, and the dead pod gets zero admissions after
    the kill."""
    pods = {name: EnginePod(name) for name in ("a", "b", "c")}
    router = RequestRouter(
        lambda name, addr, req: pods[name].send(req),
        page_tokens=4, stale_after_s=5.0, retry_budget=2,
    )
    router.update_pods(
        {n: {"address": f"{n}:80"} for n in pods}, generation="g1"
    )
    for name, pod in pods.items():
        router.observe_stats(name, pod.engine.stats())

    n_requests = 24
    jobs = [([i + 1, i + 2, i + 3], 6) for i in range(n_requests)]
    results = [None] * n_requests
    errors = []
    kill_at = threading.Event()

    def client(i):
        if i == n_requests // 2:
            kill_at.set()
        try:
            results[i] = router.submit(jobs[i][0], jobs[i][1])
        except Exception as e:  # noqa: BLE001 — surfaced via assert
            errors.append((i, e))

    def killer():
        assert kill_at.wait(30)
        pods["a"].killed.set()  # mid-stream: in-flight sends now die

    threads = [threading.Thread(target=killer)] + [
        threading.Thread(target=client, args=(i,))
        for i in range(n_requests)
    ]
    try:
        for t in threads:
            t.start()
            time.sleep(0.002)  # staggered: some in flight at the kill
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        # every continuation correct, exactly once
        for (prompt, n), result in zip(jobs, results):
            assert result == _chain_oracle(prompt, n)
        # no silent duplication: completions across pods == requests
        completed = sum(p.completed for p in pods.values())
        assert completed == n_requests
        # the dead pod is out of rotation: admissions stopped at the
        # kill (failed mark), and new traffic avoids it entirely
        admitted_at_kill = pods["a"].admitted
        for i in range(4):
            router.submit([90 + i], 3)
        assert pods["a"].admitted == admitted_at_kill
        stats = router.stats()
        assert stats["router_failovers"] >= 1
        assert stats["requests_completed"] == n_requests + 4
    finally:
        for pod in pods.values():
            pod.stop()


# -- the HTTP front door over real sockets -----------------------------


class HttpPod:
    """A minimal real-socket serve pod: /generate + /stats."""

    def __init__(self, name):
        self.name = name
        self.model = ChainModel()
        self.engine = _chain_engine(self.model, 4, queue_timeout_s=30)
        engine = self.engine

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def _reply(self, code, body):
                payload = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):
                self._reply(200, engine.stats())

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length))
                try:
                    out = engine.submit(
                        body["tokens"], body["max_new_tokens"],
                    )
                except ValueError as e:
                    self._reply(400, {"error": str(e)})
                    return
                self._reply(200, {"tokens": out})

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()

    @property
    def address(self):
        host, port = self.server.server_address[:2]
        return f"{host}:{port}"

    def stop(self):
        self.server.shutdown()
        self.server.server_close()
        self.engine.stop()


def test_frontdoor_end_to_end_over_http(tmp_path):
    from dcos_commons_tpu.router.frontdoor import RouterServer

    pods = [HttpPod("pod-0"), HttpPod("pod-1")]
    discovery_calls = [0]

    def discover():
        discovery_calls[0] += 1
        return {
            "name": "vip:inference",
            "generation": "gen-1",
            "address": sorted(p.address for p in pods),
            "backends": [
                {"address": p.address, "task": p.name,
                 "state": "TASK_RUNNING", "ready": True,
                 "draining": False}
                for p in pods
            ],
        }

    stats_path = str(tmp_path / "servestats.json")
    server = RouterServer(
        "http://unused", discover=discover, port=0,
        host="127.0.0.1", poll_interval_s=0.2,
        stats_path=stats_path, page_tokens=4, log=None,
    )
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        # generate through the front door: greedy == direct oracle
        body = json.dumps(
            {"tokens": [[1, 2, 3], [4, 5]], "max_new_tokens": 5}
        ).encode()
        req = urllib.request.Request(
            f"{base}/generate", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        assert out["tokens"] == [
            _chain_oracle([1, 2, 3], 5), _chain_oracle([4, 5], 5),
        ]
        # router gauges over HTTP, watcher-compatible keys included
        with urllib.request.urlopen(f"{base}/stats", timeout=10) as resp:
            stats = json.loads(resp.read())
        assert stats["router_pods"] == 2
        assert stats["requests_completed"] == 2
        assert "stats_age_s" in stats and "t" in stats
        assert stats["http_port"] == server.port
        # generation-stamped refresh: polls happened, ONE rebuild
        deadline = time.monotonic() + 5
        while discovery_calls[0] < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert discovery_calls[0] >= 3
        assert server.router.stats()["router_generation"] == "gen-1"
        # pod application errors pass through with their status
        bad = json.dumps(
            {"tokens": [[1] * 99], "max_new_tokens": 5}
        ).encode()
        req = urllib.request.Request(
            f"{base}/generate", data=bad, method="POST",
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 400
        # drain verb: the drained pod stops admitting
        req = urllib.request.Request(
            f"{base}/drain?pod=pod-0", data=b"", method="POST"
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read())["draining"] is True
        with urllib.request.urlopen(f"{base}/pods", timeout=10) as resp:
            pods_body = json.loads(resp.read())
        assert pods_body["pods"]["pod-0"]["draining"] is True
        # the router's sandbox mirror exists for the scheduler merge
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                with open(stats_path) as f:
                    mirrored = json.load(f)
                if mirrored.get("router_pods") == 2:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.05)
        assert mirrored["router_pods"] == 2
    finally:
        server.stop()
        for pod in pods:
            pod.stop()


# -- engine stats_age_s (the ISSUE 12 serve-side stamp) ----------------


def test_engine_stats_age_tracks_loop_liveness():
    gate = threading.Event()  # never set: decode wedges

    class WedgedModel(ChainModel):
        def decode(self, *args):
            assert gate.wait(30)
            return super().decode(*args)

    engine = _chain_engine(WedgedModel(), 2, queue_timeout_s=60)
    try:
        # idle: trivially responsive, age pinned at zero
        assert engine.stats()["stats_age_s"] == 0.0
        worker = threading.Thread(
            target=lambda: engine.submit([[1, 2]], 4), daemon=True
        )
        worker.start()
        deadline = time.monotonic() + 10
        while engine.stats()["active_slots"] < 1 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.25)  # the loop is now stuck inside decode
        age = engine.stats()["stats_age_s"]
        assert age >= 0.2, f"wedged loop not aging: {age}"
        gate.set()
        worker.join(timeout=30)
        assert not worker.is_alive()
        assert engine.stats()["stats_age_s"] == 0.0  # idle again
    finally:
        gate.set()
        engine.stop()


def test_engine_extra_stats_annotation_rides_every_snapshot():
    engine = _chain_engine(
        ChainModel(), 2, extra_stats={"http_port": 4242}
    )
    try:
        assert engine.stats()["http_port"] == 4242
        engine.annotate_stats(zone="z1")
        stats = engine.stats()
        assert stats["http_port"] == 4242 and stats["zone"] == "z1"
    finally:
        engine.stop()
