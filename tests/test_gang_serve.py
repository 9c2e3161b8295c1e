"""Multi-host gang training END TO END with real processes.

The flagship claim driven for real: a gang pod deploys over agent
daemon processes, each worker is a REAL ``frameworks/jax``
train_worker that rendezvouses via jax.distributed at the
scheduler-issued coordinator and trains a pjit mesh (CPU backend
here — same code path the TPU fleet runs); killing a daemon flips the
WHOLE gang to recovery (SURVEY hard-part 3: gang semantics the
reference never needed), and the replacement gang RESUMES from the
orbax-style checkpoint instead of step 0 (SURVEY 5.4: re-place +
restore is PERMANENT recovery's workload half).
"""

import os

import pytest

from dcos_commons_tpu.testing.integration import (
    AgentProcess,
    SchedulerProcess,
    reap_orphan_tasks,
    wait_for,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GANG_SVC = """
name: gangtrain
pods:
  trainer:
    count: 2
    gang: true
    tpu:
      generation: v5e
      chips-per-host: 1
      topology: 1x2
    tasks:
      worker:
        goal: RUNNING
        cmd: >-
          JAX_PLATFORMS=cpu
          CHECKPOINT_DIR={{CKPT_DIR}} DATA_DIR={{DATA_DIR}}
          VOCAB=128 D_MODEL=64 N_LAYERS=2 SEQ_LEN=64 TRAIN_STEPS=4000
          python {{REPO_ROOT}}/frameworks/jax/train_worker.py
        cpus: 1.0
        memory: 2048
"""


def _write_topology(path, agents):
    """One slice, a 2x2 host grid of 1-chip hosts: the 1x2 gang fits
    in either column, so losing one host leaves a full column free."""
    grids = [(0, 0), (0, 1), (1, 0), (1, 1)]
    lines = ["hosts:"]
    for agent, (gx, gy) in zip(agents, grids):
        lines += [
            f"  - host_id: {agent.host_id}",
            f"    agent_url: {agent.url}",
            "    hostname: 127.0.0.1",  # the dialable DCN address
            "    slice_id: s0",
            "    generation: v5e",
            f"    grid: [{gx}, {gy}]",
            "    chip_block: [1, 1]",
            "    cpus: 4.0",
            "    memory_mb: 8192",
        ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def _worker_logs(agents):
    """task-name -> (host_id, stdout text) for every trainer sandbox."""
    out = {}
    for agent in agents:
        for idx in (0, 1):
            path = os.path.join(
                agent.workdir, "sandboxes", f"trainer-{idx}-worker", "stdout"
            )
            if os.path.exists(path):
                with open(path, errors="replace") as f:
                    out.setdefault(f"trainer-{idx}-worker", []).append(
                        (agent.host_id, f.read())
                    )
    return out


@pytest.mark.slow
def test_gang_trains_and_resumes_from_checkpoint_after_host_loss(tmp_path):
    import numpy as np

    from dcos_commons_tpu.data import write_token_shard

    agents = [
        AgentProcess(f"g{i}", str(tmp_path / f"agent-{i}"), REPO)
        for i in range(4)
    ]
    svc = tmp_path / "svc.yml"
    svc.write_text(GANG_SVC)
    topology = tmp_path / "topology.yml"
    _write_topology(str(topology), agents)
    ckpt_dir = tmp_path / "ckpt"
    # a REAL corpus: the gang trains from memmap shards (disjoint per
    # worker via the env contract), not synthetic tokens
    data_dir = tmp_path / "corpus"
    data_dir.mkdir()
    rng = np.random.default_rng(0)
    for i in range(4):
        write_token_shard(
            str(data_dir / f"shard-{i}.tokens"),
            rng.integers(0, 128, 8000),
        )
    scheduler = SchedulerProcess(
        str(svc), str(topology), str(tmp_path / "sched"),
        env={
            "ENABLE_BACKOFF": "false",
            "PERMANENT_FAILURE_TIMEOUT_S": "1",
            "REPO_ROOT": REPO,
            "CKPT_DIR": str(ckpt_dir),
            "DATA_DIR": str(data_dir),
        },
        repo_root=REPO,
    )
    try:
        client = scheduler.client()
        client.wait_for_completed_deployment(timeout_s=120)

        # both workers rendezvous (2-process Gloo mesh), load DISJOINT
        # corpus shards, and make real training steps; worker 0 writes
        # checkpoints every 20 steps
        def progressed():
            logs = _worker_logs(agents)
            loaded = sum(
                1 for entries in logs.values()
                for _, text in entries if "data: " in text
            )
            stepped = sum(
                1 for entries in logs.values()
                for _, text in entries if "step 20 " in text
            )
            return (loaded >= 2 and stepped >= 1) or None

        wait_for(progressed, 240.0, interval_s=2.0,
                 what="gang made 20+ real training steps")

        def checkpoint_past_20():
            if not ckpt_dir.exists():
                return None
            steps = [
                int(f[len("step_"):-len(".npz")])
                for f in os.listdir(ckpt_dir)
                if f.startswith("step_") and f.endswith(".npz")
            ]
            return max(steps) if steps and max(steps) >= 21 else None

        wait_for(checkpoint_past_20, 120.0, interval_s=2.0,
                 what="checkpoint at step >= 21 written")

        # find the daemon hosting worker 1 and kill it: ONE host loss
        # must flip the WHOLE gang to recovery
        infos = {
            i["name"]: i
            for idx in (0, 1)
            for i in client.get(f"/v1/pod/trainer-{idx}/info")
        }
        old_ids = {n: i["task_id"] for n, i in infos.items()}
        victim_host = infos["trainer-1-worker"]["agent_id"]
        victim = next(a for a in agents if a.host_id == victim_host)
        victim.kill()

        def gang_replaced():
            try:
                now = {
                    i["name"]: i
                    for idx in (0, 1)
                    for i in client.get(f"/v1/pod/trainer-{idx}/info")
                }
            except Exception:
                return None
            if set(now) != set(old_ids):
                return None
            # BOTH workers get new task ids (gang-atomic recovery),
            # and nothing lands on the dead host
            if any(now[n]["task_id"] == old_ids[n] for n in now):
                return None
            if any(i["agent_id"] == victim_host for i in now.values()):
                return None
            return now

        replaced = wait_for(gang_replaced, 180.0, interval_s=2.0,
                            what="whole gang replaced off the dead host")
        new_hosts = {i["agent_id"] for i in replaced.values()}
        old_hosts = {i["agent_id"] for i in infos.values()}
        assert victim_host not in new_hosts

        # the replacement gang RESUMES from the checkpoint: on a FRESH
        # host (one the original gang never touched, so its sandbox log
        # starts with the replacement) the first logged step must be
        # >= 40 — train_worker logs every 20th step, and a restored
        # start of >= 21 makes 40 the first loggable step; a
        # from-scratch run would log step 0 first
        fresh_hosts = new_hosts - old_hosts
        assert fresh_hosts, (
            f"replacement reused every original host: {new_hosts}"
        )

        def resumed():
            logs = _worker_logs(agents)
            for entries in logs.values():
                for host, text in entries:
                    if host not in fresh_hosts:
                        continue
                    first = next(
                        (ln for ln in text.splitlines()
                         if ln.startswith("step ") and " loss=" in ln),
                        None,
                    )
                    if first is not None:
                        step = int(first.split()[1])
                        assert step >= 40, (
                            f"replacement on {host} started at step "
                            f"{step} — did not resume from checkpoint"
                        )
                        return True
            return None

        wait_for(resumed, 240.0, interval_s=2.0,
                 what="replacement gang resumed from checkpoint")
    finally:
        scheduler.terminate()
        for agent in agents:
            agent.stop()
        reap_orphan_tasks(agents)
