"""Multi-host SHARDED SERVING gang end to end with real processes.

The serving half of the flagship at gang scale, driven for real: a
tp=2 serving gang deploys over agent daemon processes, each worker a
REAL ``frameworks/jax`` serve_gang_worker that rendezvouses via
jax.distributed and holds HALF the tensor-parallel-sharded model;
worker 0 answers POST /generate by broadcasting each request so the
whole gang executes ONE pjit'd generate.  Killing a daemon flips the
WHOLE gang to recovery; the replacement gang re-rendezvouses off the
dead host and greedy replies are TOKEN-IDENTICAL before and after —
sharded serving survives host loss with no answer drift.

Reference bar: sim-level behavior coverage for every workload shape
(sdk/testing/.../ServiceTestRunner.java:38); the reference never
serves models, so the gang/SPMD serving shape is the TPU-first
addition this test pins down.
"""

import json
import os
import urllib.request

import pytest

from dcos_commons_tpu.testing.integration import (
    AgentProcess,
    SchedulerProcess,
    reap_orphan_tasks,
    wait_for,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_topology(path, agents):
    """One slice, a 2x2 host grid of 1-chip hosts: the 1x2 gang fits
    in either column, so losing one host leaves a full column free."""
    grids = [(0, 0), (0, 1), (1, 0), (1, 1)]
    lines = ["hosts:"]
    for agent, (gx, gy) in zip(agents, grids):
        lines += [
            f"  - host_id: {agent.host_id}",
            f"    agent_url: {agent.url}",
            "    hostname: 127.0.0.1",
            "    slice_id: s0",
            "    generation: v5e",
            f"    grid: [{gx}, {gy}]",
            "    chip_block: [1, 1]",
            "    cpus: 4.0",
            "    memory_mb: 8192",
        ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def _post(port, payload, timeout=90):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.mark.slow
@pytest.mark.parametrize("quant", ["native", "int8"])
def test_sharded_serving_gang_failover_token_identical(tmp_path, quant):
    agents = [
        AgentProcess(f"s{i}", str(tmp_path / f"agent-{i}"), REPO)
        for i in range(4)
    ]
    svc = tmp_path / "svc.yml"
    with open(
        os.path.join(REPO, "frameworks", "jax", "svc_serve_gang.yml")
    ) as f:
        svc.write_text(f.read())
    topology = tmp_path / "topology.yml"
    _write_topology(str(topology), agents)
    scheduler = SchedulerProcess(
        str(svc), str(topology), str(tmp_path / "sched"),
        env={
            "ENABLE_BACKOFF": "false",
            "PERMANENT_FAILURE_TIMEOUT_S": "1",
            "JAX_FRAMEWORK_DIR": os.path.join(REPO, "frameworks", "jax"),
            "TASKCFG_ALL_JAX_PLATFORMS": "cpu",
            # tiny flagship: 2-process Gloo mesh compiles in seconds
            "VOCAB": "64",
            "D_MODEL": "32",
            "N_LAYERS": "2",
            "D_FF": "64",
            "SEQ_LEN": "64",
            "MAX_LEN": "48",
            "MAX_NEW_TOKENS": "8",
            "SERVE_BATCH": "2",
            # parametrized: "native" covers the operator-default gang;
            # "int8" runs the FULL serving quantization stack sharded
            # (weights quantize AFTER placement — GSPMD-derived int8 +
            # scale shardings — and the cache stores int8).  Every
            # assertion below is served-vs-served self-consistency, so
            # both gangs must hold them all, across failover
            "WEIGHT_DTYPE": quant,
            "KV_DTYPE": quant,
        },
        repo_root=REPO,
    )
    try:
        client = scheduler.client()
        client.wait_for_completed_deployment(timeout_s=240)

        def gang_infos():
            return {
                i["name"]: i
                for idx in (0, 1)
                for i in client.get(f"/v1/pod/server-{idx}/info")
            }

        infos = gang_infos()
        assert set(infos) == {"server-0-api", "server-1-api"}
        port = int(infos["server-0-api"]["env"]["PORT_HTTP"])

        # the sharded gang answers; greedy is deterministic
        first = _post(port, {"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8})
        assert len(first["tokens"][0]) == 8
        assert first == _post(
            port, {"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8}
        )
        # concurrent MIXED-length clients: each gets its own correct
        # greedy continuation (the gang micro-batches them into shared
        # dispatches via the per-row true_len broadcast)
        import threading

        prompts = [[1, 2, 3, 4], [9, 8], [5, 6, 7, 2, 1]]
        sequential = [
            _post(port, {"tokens": [p], "max_new_tokens": 8})["tokens"][0]
            for p in prompts
        ]
        concurrent = [None] * len(prompts)
        conc_errors = []

        def one_client(i):
            try:
                concurrent[i] = _post(
                    port, {"tokens": [prompts[i]], "max_new_tokens": 8}
                )["tokens"][0]
            except Exception as e:  # noqa: BLE001
                conc_errors.append(e)

        threads = [
            threading.Thread(target=one_client, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not conc_errors, conc_errors
        assert concurrent == sequential
        # ONE multi-row request with MIXED lengths pins the per-row
        # lens path deterministically (the concurrent phase above only
        # merges when thread timing races the requests into one tick)
        mixed = _post(
            port,
            {"tokens": [prompts[0], prompts[1]], "max_new_tokens": 8},
        )
        assert mixed["tokens"] == [sequential[0], sequential[1]]
        # worker 0's log proves the request ran the GANG path
        rank0_host = infos["server-0-api"]["agent_id"]
        rank0_agent = next(a for a in agents if a.host_id == rank0_host)
        stdout = os.path.join(
            rank0_agent.workdir, "sandboxes", "server-0-api", "stdout"
        )
        with open(stdout, errors="replace") as f:
            log = f.read()
        # the request ran the GANG path: a tp-sharded server over the
        # union of both processes' devices (device count per process
        # follows the test env's virtual-device flag)
        assert "serving sharded generate" in log and " tp=" in log

        # kill the host serving worker 1: ONE host loss must flip the
        # WHOLE gang to recovery (SPMD serving cannot limp on half a
        # model)
        old_ids = {n: i["task_id"] for n, i in infos.items()}
        victim_host = infos["server-1-api"]["agent_id"]
        victim = next(a for a in agents if a.host_id == victim_host)
        victim.kill()

        def gang_replaced():
            try:
                now = gang_infos()
            except Exception:
                return None
            if set(now) != set(old_ids):
                return None
            if any(now[n]["task_id"] == old_ids[n] for n in now):
                return None  # gang-atomic: BOTH workers replaced
            if any(i["agent_id"] == victim_host for i in now.values()):
                return None  # nothing lands on the dead host
            return now

        replaced = wait_for(gang_replaced, 180.0, interval_s=2.0,
                            what="whole serving gang replaced")

        # the REPLACEMENT gang serves the IDENTICAL greedy continuation
        new_port = int(replaced["server-0-api"]["env"]["PORT_HTTP"])

        def serves_again():
            try:
                return _post(
                    new_port,
                    {"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8},
                    timeout=30,
                )
            except Exception:
                return None

        answer = wait_for(serves_again, 240.0, interval_s=3.0,
                          what="replacement gang serving")
        assert answer == first, (
            f"failover changed the greedy reply: {first} -> {answer}"
        )
    finally:
        scheduler.terminate()
        for agent in agents:
            agent.stop()
        reap_orphan_tasks(agents)
