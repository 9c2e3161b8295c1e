"""Benchmark: the BASELINE.md headline on real TPU hardware.

Phase 1 — BASELINE.json configs through the real control plane with a
real process-launching agent:
  #1 frameworks/helloworld simple.yml single-pod deploy
  #2 frameworks/helloworld max_per_host.yml (constraint respected)
  #3 frameworks/jax svc_mnist.yml — a REAL JAX training subprocess on
     the TPU; install -> plan COMPLETE wall-clock is the headline.
The reference publishes no numbers (BASELINE.md), so vs_baseline is
measured against the 60 s target budget recorded there (>1.0 = faster
than budget).

Phase 2 (extras) — flagship transformer train-step throughput on the
chip (tokens/s + model FLOPs utilisation), the forward-looking perf
number the multi-host pod scales from.

Prints exactly ONE JSON line.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

DEPLOY_BUDGET_S = 60.0


def flagship_config():
    """The one flagship TransformerConfig both bench_transformer and
    bench_profile measure — sized for one v5e chip (16 GB): 872M
    params, FA2 backward kernels, 512/512 attention tiles, batch 12
    with ONE stored-activation layer (bench_mfu_frontier sweeps the
    (batch, no_remat_layers) frontier around it).  Tokens/s and MFU at
    this point: not measured on the current toolchain."""
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab=32768,
        d_model=2048,
        n_layers=12,
        n_heads=16,
        n_kv_heads=16,
        d_ff=8192,
        max_seq=2048,
        dtype=jnp.bfloat16,
        remat=True,
        no_remat_layers=int(os.environ.get("BENCH_NO_REMAT_LAYERS", "1")),
        attn_block_q=512,
        attn_block_k=512,
    )


def _run_deploy(yaml_path: str, env: dict, hosts, budget_s: float = 600.0):
    """Deploy one service YAML through the full control plane with a
    real process-launching agent; returns (elapsed, completed,
    scheduler, agent, workdir)."""
    import tempfile

    from dcos_commons_tpu.agent import LocalProcessAgent
    from dcos_commons_tpu.offer.inventory import SliceInventory
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.storage import FileWalPersister

    workdir = tempfile.mkdtemp(prefix="bench-")
    from dcos_commons_tpu.specification import from_yaml_file

    spec = from_yaml_file(yaml_path, env)
    builder = SchedulerBuilder(
        spec,
        SchedulerConfig(
            sandbox_root=os.path.join(workdir, "sandboxes"),
            backoff_enabled=False,
        ),
        FileWalPersister(os.path.join(workdir, "state"), fsync=False),
    )
    builder.set_inventory(SliceInventory(list(hosts)))
    agent = LocalProcessAgent(os.path.join(workdir, "sandboxes"))
    builder.set_agent(agent)
    scheduler = builder.build()

    t0 = time.monotonic()
    deadline = t0 + budget_s
    completed = False
    while time.monotonic() < deadline:
        scheduler.run_cycle()
        if scheduler.deploy_manager.get_plan().is_complete:
            completed = True
            break
        time.sleep(0.1)
    elapsed = time.monotonic() - t0
    return elapsed, completed, scheduler, agent, workdir


def _cpu_hosts(n: int):
    from dcos_commons_tpu.offer.inventory import TpuHost

    return [
        TpuHost(host_id=f"host-{i}", cpus=8.0, memory_mb=16384)
        for i in range(n)
    ]


def bench_helloworld() -> dict:
    """BASELINE configs #1 and #2: helloworld CPU deploys through the
    control plane (reference: frameworks/helloworld simple +
    MAX_PER_HOST scenarios)."""
    import shutil

    results = {}
    # config 1: single-pod deploy
    elapsed, completed, scheduler, agent, workdir = _run_deploy(
        os.path.join(REPO, "frameworks/helloworld/simple.yml"),
        {"SLEEP_DURATION": "1000"},
        _cpu_hosts(1),
        budget_s=60.0,
    )
    results["helloworld_simple_deploy_s"] = round(elapsed, 3)
    results["helloworld_simple_completed"] = completed
    agent.shutdown()
    shutil.rmtree(workdir, ignore_errors=True)

    # config 2: 3 instances, max-per-host:1 over 3 hosts
    elapsed, completed, scheduler, agent, workdir = _run_deploy(
        os.path.join(REPO, "frameworks/helloworld/max_per_host.yml"),
        {"SLEEP_DURATION": "1000"},
        _cpu_hosts(3),
        budget_s=60.0,
    )
    placed_hosts = set()
    for info in scheduler.state_store.fetch_tasks():
        placed_hosts.add(info.labels.get("offer_hostname", info.agent_id))
    results["helloworld_max_per_host_deploy_s"] = round(elapsed, 3)
    results["helloworld_max_per_host_completed"] = completed
    results["helloworld_max_per_host_distinct_hosts"] = len(placed_hosts)
    agent.shutdown()
    shutil.rmtree(workdir, ignore_errors=True)
    return results


def bench_mfu_frontier() -> dict:
    """Dense-flagship (batch, no_remat_layers) frontier at S=2048:
    either a point beats the remat-full batch-24 tokens/s, or this
    records the measured proof that trading batch for less recompute
    is tokens/s-worse.  Points that OOM report as OOM — the frontier
    INCLUDES the infeasible region's boundary."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.models import init_params, make_train_step
    from dcos_commons_tpu.utils import param_count, synthetic_tokens

    steps = int(os.environ.get("BENCH_FRONTIER_STEPS", "6"))
    base = flagship_config()
    peak = _peak_bf16_tflops(jax.devices()[0]) * 1e12
    points = [
        # (batch, no_remat_layers): the three points nearest the HBM
        # boundary (each is a fresh compile, so the full boundary scan
        # is not re-paid per run; not measured on the current
        # toolchain); override with BENCH_FRONTIER_POINTS="b:k,b:k,..."
        # to rescan.
        (16, 0), (12, 1), (8, 2),
    ]
    env_points = os.environ.get("BENCH_FRONTIER_POINTS", "")
    if env_points:
        points = [
            tuple(int(v) for v in p.split(":"))
            for p in env_points.split(",")
        ]
    out = {}
    frontier = []
    for batch, k in points:
        tag = f"b{batch}_nr{k}"
        cfg = dataclasses.replace(
            base, no_remat_layers=k, remat=k < base.n_layers,
        )
        try:
            params = init_params(cfg, jax.random.key(0))
            optimizer = optax.adamw(3e-4)
            opt_state = optimizer.init(params)
            step_fn = make_train_step(cfg, optimizer, donate=True)
            tokens, targets = synthetic_tokens(
                jax.random.key(1), batch, cfg.max_seq, cfg.vocab
            )
            params, opt_state, loss = step_fn(
                params, opt_state, tokens, targets
            )
            jax.block_until_ready(loss)
            params, opt_state, loss = step_fn(
                params, opt_state, tokens, targets
            )
            jax.block_until_ready(loss)
            t0 = time.monotonic()
            for _ in range(steps):
                params, opt_state, loss = step_fn(
                    params, opt_state, tokens, targets
                )
            float(jax.device_get(jnp.sum(loss)))
            dt = time.monotonic() - t0
            toks = batch * cfg.max_seq * steps / dt
            mfu = toks * 6 * param_count(params) / peak
            frontier.append(f"{tag}: {round(toks)} tok/s mfu {mfu:.3f}")
            out[f"frontier_{tag}_tokens_per_s"] = round(toks)
            out[f"frontier_{tag}_mfu"] = round(mfu, 3)
            del params, opt_state
        except Exception as e:  # OOM boundary is a RESULT here
            frontier.append(f"{tag}: infeasible ({repr(e)[:60]})")
            out[f"frontier_{tag}_tokens_per_s"] = 0
    out["frontier_notes"] = "; ".join(frontier)
    return out


def bench_scheduler_scale() -> dict:
    """Scheduler-loop latency at FLEET scale: a 100-pod service over a
    64-host inventory with a placement constraint, through the full
    offer-evaluation pipeline (fake agent — this measures the
    SCHEDULER, not process spawns).  The regression fence for an
    accidental O(n^2) in offer/evaluate.py — the reference's whole
    reason for decline/suppress machinery
    (framework/OfferProcessor.java:133,142)."""
    import statistics

    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.offer.inventory import SliceInventory, TpuHost
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    n_hosts, n_pods = 64, 100
    spec = from_yaml(
        "name: scalesvc\n"
        "pods:\n"
        "  app:\n"
        f"    count: {n_pods}\n"
        "    placement: 'max-per-host:2'\n"
        "    tasks:\n"
        "      server:\n"
        "        goal: RUNNING\n"
        "        cmd: sleep 1000\n"
        "        cpus: 4\n"
        "        memory: 1024\n"
        "plans:\n"
        "  deploy:\n"
        "    strategy: serial\n"
        "    phases:\n"
        "      app:\n"
        "        strategy: parallel\n"
        "        pod: app\n"
    )
    hosts = [
        TpuHost(host_id=f"h{i:03d}", cpus=16.0, memory_mb=65536)
        for i in range(n_hosts)
    ]
    builder = SchedulerBuilder(
        spec,
        SchedulerConfig(backoff_enabled=False, revive_capacity=10**9),
        MemPersister(),
    )
    builder.set_inventory(SliceInventory(hosts))
    agent = FakeAgent()
    builder.set_agent(agent)
    scheduler = builder.build()

    cycle_ms = []
    acked = set()
    t0 = time.monotonic()
    deadline = t0 + 300.0
    completed = False
    while time.monotonic() < deadline:
        c0 = time.monotonic()
        scheduler.run_cycle()
        cycle_ms.append((time.monotonic() - c0) * 1e3)
        # ack every newly launched task as RUNNING (the fleet's agents
        # answering; launch->RUNNING latency is not the scheduler's)
        for info in agent.launched:
            if info.task_id not in acked:
                acked.add(info.task_id)
                agent.send(TaskStatus(
                    task_id=info.task_id, state=TaskState.RUNNING,
                    ready=True,
                ))
        if scheduler.deploy_manager.get_plan().is_complete:
            completed = True
            break
    deploy_s = time.monotonic() - t0
    # steady state: every pod RUNNING, nothing to place — the
    # decline/suppress path the fleet idles on
    idle_ms = []
    for _ in range(50):
        c0 = time.monotonic()
        scheduler.run_cycle()
        idle_ms.append((time.monotonic() - c0) * 1e3)
    quantiles = statistics.quantiles(cycle_ms, n=100)
    return {
        "sched_scale_hosts": n_hosts,
        "sched_scale_pods": n_pods,
        "sched_scale_completed": completed,
        "sched_scale_deploy_s": round(deploy_s, 3),
        "sched_scale_cycles": len(cycle_ms),
        "sched_scale_cycle_p50_ms": round(quantiles[49], 2),
        "sched_scale_cycle_p99_ms": round(quantiles[98], 2),
        "sched_scale_idle_cycle_ms": round(
            statistics.median(idle_ms), 2
        ),
    }


def bench_offer_cycle() -> dict:
    """Offer-cycle fast path microbench (ISSUE 1): a 16-step serial
    deploy over a 64-host TPU fleet through run_forever with the
    production 0.5 s fallback interval.  Two numbers are fenced:

    * snapshot rebuild reduction — the generation-stamped cache must
      cut per-host snapshot rebuilds >= 5x vs the rebuild-every-
      request baseline (requests / misses);
    * event-driven wall-clock — statuses nudge the loop, so the
      deploy must complete in well under steps x interval_s (the old
      loop paid >= one 0.5 s sleep per step)."""
    import threading

    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.offer.inventory import (
        SliceInventory,
        make_test_fleet,
    )
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    n_steps, interval_s = 16, 0.5
    hosts = []
    for s in range(4):  # 4 slices x 16 hosts = 64 TPU hosts
        hosts.extend(make_test_fleet(
            slice_id=f"pod-{s}", host_grid=(4, 4), chip_block=(2, 2),
            cpus=32.0, memory_mb=131072,
        ))
    spec = from_yaml(
        "name: offercycle\n"
        "pods:\n"
        "  app:\n"
        f"    count: {n_steps}\n"
        "    placement: 'max-per-host:1'\n"
        "    tasks:\n"
        "      server:\n"
        "        goal: RUNNING\n"
        "        cmd: sleep 1000\n"
        "        cpus: 2\n"
        "        memory: 1024\n"
        "plans:\n"
        "  deploy:\n"
        "    strategy: serial\n"
        "    phases:\n"
        "      app:\n"
        "        strategy: serial\n"
        "        pod: app\n"
    )
    builder = SchedulerBuilder(
        spec,
        SchedulerConfig(backoff_enabled=False, revive_capacity=10**9),
        MemPersister(),
    )
    inventory = SliceInventory(hosts)
    builder.set_inventory(inventory)
    agent = FakeAgent()
    builder.set_agent(agent)
    scheduler = builder.build()

    acked = set()
    stop = threading.Event()

    def responder():  # the fleet's agents acking RUNNING
        while not stop.is_set():
            for info in list(agent.launched):
                if info.task_id not in acked:
                    acked.add(info.task_id)
                    agent.send(TaskStatus(
                        task_id=info.task_id, state=TaskState.RUNNING,
                        ready=True, agent_id=info.agent_id,
                    ))
            time.sleep(0.002)

    responder_thread = threading.Thread(target=responder, daemon=True)
    responder_thread.start()
    t0 = time.monotonic()
    loop_thread = scheduler.run_forever(interval_s=interval_s)
    deadline = t0 + 60.0
    completed = False
    while time.monotonic() < deadline:
        if scheduler.deploy_manager.get_plan().is_complete:
            completed = True
            break
        time.sleep(0.01)
    elapsed = time.monotonic() - t0
    scheduler.stop()
    loop_thread.join(timeout=5)
    stop.set()
    responder_thread.join(timeout=5)
    requests = inventory.cache_hits + inventory.cache_misses
    rebuild_reduction = requests / max(1, inventory.cache_misses)
    return {
        "offer_cycle_hosts": len(hosts),
        "offer_cycle_steps": n_steps,
        "offer_cycle_completed": completed,
        "offer_cycle_deploy_s": round(elapsed, 3),
        "offer_cycle_serial_budget_s": round(n_steps * interval_s, 1),
        "offer_cycle_snapshot_requests": requests,
        "offer_cycle_snapshot_rebuilds": inventory.cache_misses,
        "offer_cycle_rebuild_reduction_x": round(rebuild_reduction, 1),
        "offer_cycle_nudges": int(
            scheduler.metrics.counters().get("cycle.nudges", 0)
        ),
    }


def bench_fleet_scale() -> dict:
    """Fleet-scale offer cycle (ISSUE 9): dirty-host incremental
    snapshot sync + indexed placement pre-filtering + requirement
    memo vs the PR-1 full-copy path, at 1k and 10k simulated hosts.

    Scenario per fleet size: a 32-pod TPU deploy (parallel phase),
    then 50 steady-state IDLE cycles, then 6 CHURN rounds (restart one
    pod -> drive to recovered).  Fences, at 10k hosts:

    * steady-state (idle / single-status churn) cycle must be >= 10x
      faster than the full-rebuild path (median per-round);
    * the fast path stays inside absolute budgets (idle cycle and
      churn round) so a regression cannot hide behind the baseline
      getting slower too;
    * idle cycles report dirty_hosts == 0 — cycle cost scales with
      dirty hosts, not fleet size.
    """
    import statistics

    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.offer.inventory import (
        SliceInventory,
        make_test_fleet,
    )
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    n_pods, idle_cycles, churn_rounds = 32, 50, 6

    def build_world(n_hosts, fast):
        hosts = []
        n_slices = n_hosts // 16
        for s in range(n_slices):
            hosts.extend(make_test_fleet(
                slice_id=f"pod-{s:04d}", host_grid=(4, 4),
                chip_block=(2, 2), cpus=32.0, memory_mb=131072,
            ))
        spec = from_yaml(
            "name: fleetscale\n"
            "pods:\n"
            "  app:\n"
            f"    count: {n_pods}\n"
            "    placement: 'max-per-host:1'\n"
            "    tpu:\n"
            "      generation: v5e\n"
            "      chips-per-host: 4\n"
            "    tasks:\n"
            "      worker:\n"
            "        goal: RUNNING\n"
            "        cmd: sleep 1000\n"
            "        cpus: 2\n"
            "        memory: 1024\n"
            "plans:\n"
            "  deploy:\n"
            "    strategy: serial\n"
            "    phases:\n"
            "      app:\n"
            "        strategy: parallel\n"
            "        pod: app\n"
        )
        builder = SchedulerBuilder(
            spec,
            SchedulerConfig(backoff_enabled=False, revive_capacity=10**9),
            MemPersister(),
        )
        inventory = SliceInventory(hosts)
        builder.set_inventory(inventory)
        agent = FakeAgent()
        builder.set_agent(agent)
        scheduler = builder.build()
        scheduler.evaluator.fast_path = fast
        return scheduler, agent, inventory

    def drive(scheduler, agent, acked, deadline_s=120.0):
        """run_cycle + inline RUNNING acks until no work pending."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            scheduler.run_cycle()
            for info in list(agent.launched):
                if info.task_id not in acked:
                    acked.add(info.task_id)
                    agent.send(TaskStatus(
                        task_id=info.task_id, state=TaskState.RUNNING,
                        ready=True, agent_id=info.agent_id,
                    ))
            if not scheduler.work_pending():
                return True
        return False

    out = {}
    ratios = {}
    for n_hosts in (1024, 10240):
        tag = f"{n_hosts // 1024}k" if n_hosts < 10000 else "10k"
        for fast in (True, False):
            mode = "fast" if fast else "rebuild"
            scheduler, agent, inventory = build_world(n_hosts, fast)
            acked = set()
            t0 = time.monotonic()
            completed = drive(scheduler, agent, acked)
            deploy_s = time.monotonic() - t0
            assert completed and \
                scheduler.deploy_manager.get_plan().is_complete, (
                    f"{mode}@{tag}: 32-pod deploy did not complete"
                )
            idle_ms = []
            idle_misses_before = inventory.cache_misses
            for _ in range(idle_cycles):
                c0 = time.monotonic()
                scheduler.run_cycle()
                idle_ms.append((time.monotonic() - c0) * 1e3)
            idle_rebuilds = inventory.cache_misses - idle_misses_before
            churn_s = []
            # churn-phase evaluation cost: the steady-state
            # "single-status cycle" number the 10x fence compares —
            # cycle.evaluate spans snapshot sync + placement for one
            # requirement
            eval_n0 = scheduler.metrics.timer_count("cycle.evaluate")
            for round_i in range(churn_rounds):
                c0 = time.monotonic()
                scheduler.restart_pod("app", round_i % n_pods)
                recovered = drive(scheduler, agent, acked)
                churn_s.append(time.monotonic() - c0)
                assert recovered, f"{mode}@{tag}: churn round wedged"
            eval_samples = scheduler.metrics.timer_samples(
                "cycle.evaluate", since_count=eval_n0
            )
            # fail LOUDLY on an empty window: a renamed/relocated
            # cycle.evaluate timer would otherwise make the 10x fence
            # vacuous (0.0 fast -> huge ratio) or spuriously fail it
            assert eval_samples, (
                f"{mode}@{tag}: no cycle.evaluate samples in the "
                "churn window — timer renamed or churn did not evaluate?"
            )
            churn_eval_ms = statistics.median(eval_samples) * 1e3
            out[f"fleet_scale_{tag}_{mode}_deploy_s"] = round(deploy_s, 3)
            out[f"fleet_scale_{tag}_{mode}_idle_cycle_ms"] = round(
                statistics.median(idle_ms), 3
            )
            out[f"fleet_scale_{tag}_{mode}_churn_round_ms"] = round(
                statistics.median(churn_s) * 1e3, 2
            )
            out[f"fleet_scale_{tag}_{mode}_churn_eval_ms"] = round(
                churn_eval_ms, 3
            )
            if fast:
                out[f"fleet_scale_{tag}_idle_rebuilds"] = idle_rebuilds
                out[f"fleet_scale_{tag}_shortcircuits"] = int(
                    scheduler.metrics.counters().get(
                        "offers.eval.shortcircuit", 0
                    )
                )
                out[f"fleet_scale_{tag}_index_hits"] = int(
                    scheduler.metrics.counters().get("offers.index.hit", 0)
                )
        for dim in ("idle_cycle_ms", "churn_round_ms", "churn_eval_ms",
                    "deploy_s"):
            fast_v = out[f"fleet_scale_{tag}_fast_{dim}"]
            slow_v = out[f"fleet_scale_{tag}_rebuild_{dim}"]
            ratios[f"fleet_scale_{tag}_{dim}_speedup_x"] = round(
                slow_v / max(fast_v, 1e-6), 1
            )
    out.update(ratios)
    # fences (10k): steady-state >= 10x vs full rebuild, inside
    # absolute budgets, and idle cycles touch zero hosts
    assert out["fleet_scale_10k_idle_rebuilds"] == 0, \
        "idle cycles re-synthesized host snapshots — dirty tracking broken"
    eval_speedup = ratios["fleet_scale_10k_churn_eval_ms_speedup_x"]
    assert eval_speedup >= 10.0, (
        f"steady-state evaluated-cycle speedup at 10k is "
        f"{eval_speedup}x (< 10x): the incremental path is not "
        "sublinear in fleet size"
    )
    # generous absolute budgets for shared CI boxes (measured: idle
    # well under 1 ms, churn rounds tens of ms)
    assert out["fleet_scale_10k_fast_idle_cycle_ms"] < 50.0, \
        f"10k-host idle cycle {out['fleet_scale_10k_fast_idle_cycle_ms']}ms"
    assert out["fleet_scale_10k_fast_churn_round_ms"] < 2000.0, \
        f"10k-host churn round {out['fleet_scale_10k_fast_churn_round_ms']}ms"
    return out


def bench_trace_overhead() -> dict:
    """traceview recorder overhead bound (ISSUE 5): the PR 1 offer-
    cycle scenario (serial deploy over 64 TPU hosts) driven
    synchronously — run_cycle until complete, FakeAgent acking RUNNING
    inline — with the flight recorder DISABLED (trace_capacity=0) and
    ENABLED in LOCKSTEP: two identical worlds alternate cycles, each
    cycle timed individually, and the overhead is the median of the
    per-cycle-index enabled/disabled ratios.  Pairing at ~1ms cycle
    granularity cancels host drift, and the median rejects preemption
    spikes — a shared CI box cannot fake a systematic ratio.  The
    assertion enforces the tentpole's bound: per-event spans must cost
    <5% of the offer-cycle figure."""
    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.offer.inventory import (
        SliceInventory,
        make_test_fleet,
    )
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    # 32 serial steps (2x the PR 1 scenario): ~70 busy cycles per
    # deploy = enough paired samples for a stable median
    n_steps = 32
    yaml_text = (
        "name: traceoverhead\n"
        "pods:\n"
        "  app:\n"
        f"    count: {n_steps}\n"
        "    placement: 'max-per-host:1'\n"
        "    tasks:\n"
        "      server:\n"
        "        goal: RUNNING\n"
        "        cmd: sleep 1000\n"
        "        cpus: 2\n"
        "        memory: 1024\n"
        "plans:\n"
        "  deploy:\n"
        "    strategy: serial\n"
        "    phases:\n"
        "      app:\n"
        "        strategy: serial\n"
        "        pod: app\n"
    )

    def build_world(trace_capacity: int):
        hosts = []
        for s in range(4):
            hosts.extend(make_test_fleet(
                slice_id=f"pod-{s}", host_grid=(4, 4), chip_block=(2, 2),
                cpus=32.0, memory_mb=131072,
            ))
        builder = SchedulerBuilder(
            from_yaml(yaml_text),
            SchedulerConfig(
                backoff_enabled=False, revive_capacity=10**9,
                trace_capacity=trace_capacity,
            ),
            MemPersister(),
        )
        builder.set_inventory(SliceInventory(hosts))
        agent = FakeAgent()
        builder.set_agent(agent)
        return builder.build(), agent, set()

    def tick(scheduler, agent, acked):
        """One timed cycle + inline RUNNING acks; returns seconds."""
        t0 = time.monotonic()
        scheduler.run_cycle()
        elapsed = time.monotonic() - t0
        for info in list(agent.launched):
            if info.task_id not in acked:
                acked.add(info.task_id)
                agent.send(TaskStatus(
                    task_id=info.task_id, state=TaskState.RUNNING,
                    ready=True, agent_id=info.agent_id,
                ))
        return elapsed

    import gc

    # warm both code paths, then run the two worlds in lockstep: the
    # same cycle index does the same work in both, so per-index
    # ratios pair ~1ms regions executed back to back.  GC is parked
    # so a collection landing in one world's cycle doesn't masquerade
    # as recorder overhead.
    for warm_capacity in (0, 2048):
        scheduler, agent, acked = build_world(warm_capacity)
        for _ in range(10 * n_steps):
            tick(scheduler, agent, acked)
            if scheduler.deploy_manager.get_plan().is_complete:
                break
    sched_off, agent_off, acked_off = build_world(0)
    sched_on, agent_on, acked_on = build_world(2048)
    off_times, on_times = [], []
    gc.collect()
    gc.disable()
    try:
        for _ in range(10 * n_steps):
            off_times.append(tick(sched_off, agent_off, acked_off))
            on_times.append(tick(sched_on, agent_on, acked_on))
            if sched_off.deploy_manager.get_plan().is_complete and \
                    sched_on.deploy_manager.get_plan().is_complete:
                break
    finally:
        gc.enable()
    assert sched_off.deploy_manager.get_plan().is_complete
    assert sched_on.deploy_manager.get_plan().is_complete
    ratios = sorted(
        on / max(off, 1e-9) for off, on in zip(off_times, on_times)
    )
    overhead = ratios[len(ratios) // 2] - 1.0
    # the tentpole's bound: tracing must cost <5% of the offer-cycle
    # figure
    assert overhead < 0.05, (
        f"trace recorder overhead {overhead * 100:.1f}% exceeds the 5% "
        f"bound (median per-cycle ratio over {len(ratios)} lockstep "
        f"cycles; totals {sum(on_times):.4f}s traced vs "
        f"{sum(off_times):.4f}s)"
    )
    return {
        "trace_overhead_deploy_s_disabled": round(sum(off_times), 4),
        "trace_overhead_deploy_s_enabled": round(sum(on_times), 4),
        "trace_overhead_pct": round(overhead * 100, 2),
        "trace_overhead_cycles": len(ratios),
        "trace_overhead_spans": len(sched_on.tracer.snapshot()),
        "trace_overhead_dropped": sched_on.tracer.dropped,
    }


def bench_health_overhead() -> dict:
    """Fleet health plane overhead bound (ISSUE 10): the trace-bench
    scenario (serial deploy over 64 TPU hosts, 32 steps = 2x the issue
    scenario for stable medians) with the health plane DISABLED
    (health_enabled=False -> NullHealthMonitor) vs ENABLED in
    LOCKSTEP — same pairing/median discipline as bench_trace_overhead.
    The enabled arm pays the full per-cycle bill: detector pass every
    cycle (straggler median-ratio over a seeded 64-host steplog fan-in,
    SLO watch, lease-churn watch), plan-transition journaling with
    per-dirty-cycle flushes through the store, and metric-history
    sampling at the production 1s cadence.  Tracing is OFF in both
    arms so the ratio isolates the health plane.  The assertion
    enforces the acceptance criterion: detectors + journal must cost
    <5% of the offer-cycle figure."""
    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.offer.inventory import (
        SliceInventory,
        make_test_fleet,
    )
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    n_steps = 32
    yaml_text = (
        "name: healthoverhead\n"
        "pods:\n"
        "  app:\n"
        f"    count: {n_steps}\n"
        "    placement: 'max-per-host:1'\n"
        "    tasks:\n"
        "      server:\n"
        "        goal: RUNNING\n"
        "        cmd: sleep 1000\n"
        "        cpus: 2\n"
        "        memory: 1024\n"
        "plans:\n"
        "  deploy:\n"
        "    strategy: serial\n"
        "    phases:\n"
        "      app:\n"
        "        strategy: serial\n"
        "        pod: app\n"
    )

    def steplog_of(task_name, agent_id=None):
        # the shape a real gang-skew steplog has: 8 trailing records
        # per task, one implicit straggler (app-7's host shows 10x own
        # time), so the enabled arm's detector does real scoring work
        own = 1.0 if task_name.startswith("app-7-") else 0.1
        return [
            {"step": i, "t": 100.0 + i, "wall_s": 1.0,
             "blocked_s": round(1.0 - own, 3), "tokens": 4096}
            for i in range(8)
        ]

    def build_world(enabled: bool):
        hosts = []
        for s in range(4):
            hosts.extend(make_test_fleet(
                slice_id=f"pod-{s}", host_grid=(4, 4), chip_block=(2, 2),
                cpus=32.0, memory_mb=131072,
            ))
        builder = SchedulerBuilder(
            from_yaml(yaml_text),
            SchedulerConfig(
                backoff_enabled=False, revive_capacity=10**9,
                trace_capacity=0, health_enabled=enabled,
            ),
            MemPersister(),
        )
        builder.set_inventory(SliceInventory(hosts))
        agent = FakeAgent()
        agent.steplog_of = steplog_of
        builder.set_agent(agent)
        scheduler = builder.build()
        # charge steplog fan-in + detector scoring at 20 Hz — 100x
        # the production 5s cadence (sub-ms sim cycles would otherwise
        # outrun the throttle and never exercise the detectors): the
        # measured ratio upper-bounds what an operator pays
        if enabled:
            scheduler.health.telemetry_interval_s = 0.05
        return scheduler, agent, set()

    def tick(scheduler, agent, acked):
        t0 = time.monotonic()
        scheduler.run_cycle()
        elapsed = time.monotonic() - t0
        for info in list(agent.launched):
            if info.task_id not in acked:
                acked.add(info.task_id)
                agent.send(TaskStatus(
                    task_id=info.task_id, state=TaskState.RUNNING,
                    ready=True, agent_id=info.agent_id,
                ))
        return elapsed

    import gc

    for warm_enabled in (False, True):
        scheduler, agent, acked = build_world(warm_enabled)
        for _ in range(10 * n_steps):
            tick(scheduler, agent, acked)
            if scheduler.deploy_manager.get_plan().is_complete:
                break
    sched_off, agent_off, acked_off = build_world(False)
    sched_on, agent_on, acked_on = build_world(True)
    off_times, on_times = [], []
    gc.collect()
    gc.disable()
    try:
        for _ in range(10 * n_steps):
            off_times.append(tick(sched_off, agent_off, acked_off))
            on_times.append(tick(sched_on, agent_on, acked_on))
            if sched_off.deploy_manager.get_plan().is_complete and \
                    sched_on.deploy_manager.get_plan().is_complete:
                break
    finally:
        gc.enable()
    assert sched_off.deploy_manager.get_plan().is_complete
    assert sched_on.deploy_manager.get_plan().is_complete
    # sanity: the enabled arm actually did health work (journal
    # carries the deploy's plan transitions; a vacuous arm would make
    # the 5% bound meaningless)
    journaled = sched_on.journal.last_seq
    assert journaled >= n_steps, f"journal only reached seq {journaled}"
    assert not sched_off.journal.enabled
    # ...and the detectors actually scored the seeded straggler
    assert sched_on.health.straggler.suspects, "straggler never scored"
    ratios = sorted(
        on / max(off, 1e-9) for off, on in zip(off_times, on_times)
    )
    overhead = ratios[len(ratios) // 2] - 1.0
    assert overhead < 0.05, (
        f"health plane overhead {overhead * 100:.1f}% exceeds the 5% "
        f"bound (median per-cycle ratio over {len(ratios)} lockstep "
        f"cycles; totals {sum(on_times):.4f}s enabled vs "
        f"{sum(off_times):.4f}s)"
    )
    return {
        "health_overhead_deploy_s_disabled": round(sum(off_times), 4),
        "health_overhead_deploy_s_enabled": round(sum(on_times), 4),
        "health_overhead_pct": round(overhead * 100, 2),
        "health_overhead_cycles": len(ratios),
        "health_overhead_journal_seq": journaled,
        "health_overhead_suspects": len(sched_on.health.straggler.suspects),
    }


def bench_failover() -> dict:
    """HA failover latency (ISSUE 8): a 64-host/32-pod deploy is
    driven halfway by leader scheduler A, which is then hard-killed
    (renewals simply stop — the SIGKILL analogue).  A hot standby
    candidates for the lease; the measured numbers are the phases an
    operator actually waits through:

      failover_lease_wait_s   kill -> standby holds the lease (bounded
                              by TTL + one candidate poll)
      failover_rebuild_s      lease -> scheduler rebuilt over the
                              shared store (config/plan/ledger load)
      failover_first_cycle_s  rebuild -> first working cycle DONE
                              (includes the rehydrate.replay pass)
      failover_total_s        kill -> first new working cycle
      failover_resume_s       kill -> the interrupted deploy COMPLETE

    The takeover must adopt every in-flight launch (no re-issue storm:
    failover_reissued == 0 here — A died between cycles, not inside
    one) and finish the rollout without restarting completed pods."""
    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.ha.election import LeaderLease
    from dcos_commons_tpu.offer.inventory import (
        SliceInventory,
        make_test_fleet,
    )
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    n_pods, ttl_s = 32, 0.6
    hosts = []
    for s in range(4):  # 64 TPU hosts
        hosts.extend(make_test_fleet(
            slice_id=f"pod-{s}", host_grid=(4, 4), chip_block=(2, 2),
            cpus=32.0, memory_mb=131072,
        ))
    yaml_text = (
        "name: failover\n"
        "pods:\n"
        "  app:\n"
        f"    count: {n_pods}\n"
        "    placement: 'max-per-host:1'\n"
        "    tasks:\n"
        "      server:\n"
        "        goal: RUNNING\n"
        "        cmd: sleep 1000\n"
        "        cpus: 2\n"
        "        memory: 1024\n"
        "plans:\n"
        "  deploy:\n"
        "    strategy: serial\n"
        "    phases:\n"
        "      app:\n"
        "        strategy: serial\n"
        "        pod: app\n"
    )
    persister = MemPersister()
    agent = FakeAgent()
    acked = set()

    def build(lease):
        builder = SchedulerBuilder(
            from_yaml(yaml_text),
            SchedulerConfig(backoff_enabled=False, revive_capacity=10**9),
            persister,
        )
        builder.set_inventory(SliceInventory(hosts))
        builder.set_agent(agent)
        builder.set_leader_lease(lease)
        return builder.build()

    def ack():
        for info in list(agent.launched):
            if info.task_id not in acked:
                acked.add(info.task_id)
                agent.send(TaskStatus(
                    task_id=info.task_id, state=TaskState.RUNNING,
                    ready=True, agent_id=info.agent_id,
                ))

    lease_a = LeaderLease(persister, "failover", "sched-a", ttl_s=ttl_s)
    assert lease_a.try_acquire()
    sched_a = build(lease_a)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        sched_a.run_cycle()
        ack()
        lease_a.renew()
        if len(agent.launched) >= n_pods // 2:
            break
    launched_at_kill = len(agent.launched)
    running_ids = {
        info.name: info.task_id
        for info in sched_a.state_store.fetch_tasks()
    }

    t_kill = time.monotonic()  # A is gone: no more cycles, no renewals
    lease_b = LeaderLease(persister, "failover", "sched-b", ttl_s=ttl_s)
    while not lease_b.try_acquire():
        time.sleep(ttl_s / 3.0)  # the candidate poll cadence
    t_lease = time.monotonic()
    sched_b = build(lease_b)
    t_built = time.monotonic()
    sched_b.run_cycle()  # rehydrate.replay + first working cycle
    t_first_cycle = time.monotonic()
    completed = False
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        ack()
        sched_b.run_cycle()
        lease_b.renew()
        if sched_b.deploy_manager.get_plan().is_complete:
            completed = True
            break
    rehydration = sched_b.last_rehydration or {}
    # the takeover adopted the running fleet instead of relaunching it
    survivors = {
        info.name: info.task_id
        for info in sched_b.state_store.fetch_tasks()
        if info.name in running_ids
    }
    adoption_clean = all(
        survivors.get(name) == task_id
        for name, task_id in running_ids.items()
    )
    return {
        "failover_hosts": len(hosts),
        "failover_pods": n_pods,
        "failover_lease_ttl_s": ttl_s,
        "failover_launched_at_kill": launched_at_kill,
        "failover_lease_wait_s": round(t_lease - t_kill, 3),
        "failover_rebuild_s": round(t_built - t_lease, 3),
        "failover_first_cycle_s": round(t_first_cycle - t_built, 3),
        "failover_total_s": round(t_first_cycle - t_kill, 3),
        "failover_resume_s": round(time.monotonic() - t_kill, 3),
        "failover_completed": completed,
        "failover_epoch": lease_b.epoch,
        "failover_adopted": rehydration.get("adopted", 0),
        "failover_reissued": rehydration.get("reissued", 0),
        "failover_adoption_clean": adoption_clean,
    }


def bench_slo_recovery() -> dict:
    """Closed health->action loop latency (ISSUE 15): seeded serving
    SLO breach under open-loop load -> time to the scale-out plan and
    time to recovered SLO, then a quiet period -> scale-in with the
    pre-kill drain, zero flap asserted over the whole run.

    The load model is open-loop at the control-plane boundary: each
    serving pod mirrors ``queue_depth = offered / live_pods`` — the
    gauge every pod already exports — so the breach clears exactly
    when the scale-out's new instances reach RUNNING and take their
    share.  Offered load 48 vs a queue-depth SLO of 16: one pod
    breaches 3x (severity 3 -> a 2-instance step), three pods sit at
    the threshold (recovered).  FakeAgent: this measures the
    scheduler loop — detection latency, plan synthesis, deploy-through
    -offer-cycle — not model serving.

      slo_recovery_scale_plan_s   breach injected -> scale-out plan
                                  journaled (detection + hysteresis
                                  hold + governor)
      slo_recovery_recovered_s    breach injected -> SLO clear event
                                  (new pods RUNNING, load spread)
      slo_recovery_scale_in_s     quiet injected -> scale-in plan
                                  complete (incl. the router drain
                                  grace before the kill)
      slo_recovery_zero_flap      1 = exactly one scale-out and one
                                  scale-in, in that order, no
                                  opposite-direction overlap

    Tracked like failover_*: regressions here mean the loop got
    slower to react or started flapping."""
    from dcos_commons_tpu.common import TaskState, TaskStatus
    from dcos_commons_tpu.offer.inventory import SliceInventory, TpuHost
    from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
    from dcos_commons_tpu.specification import from_yaml
    from dcos_commons_tpu.storage import MemPersister
    from dcos_commons_tpu.testing import FakeAgent

    yaml_text = (
        "name: slo\n"
        "pods:\n"
        "  serve:\n"
        "    count: 1\n"
        "    tasks:\n"
        "      server:\n"
        "        goal: RUNNING\n"
        "        cmd: serve\n"
        "        cpus: 1\n"
        "        memory: 512\n"
    )
    config = SchedulerConfig(
        backoff_enabled=False,
        revive_capacity=10**9,
        health_autoscale=True,
        health_queue_depth_slo=16.0,
        autoscale_max_instances=4,
        autoscale_breach_hold_s=0.05,
        autoscale_quiet_hold_s=0.05,
        autoscale_cooldown_out_s=0.5,
        autoscale_cooldown_in_s=0.5,
        autoscale_drain_grace_s=0.1,
    )
    hosts = [TpuHost(host_id=f"host-{i}", cpus=8.0, memory_mb=8192)
             for i in range(4)]
    agent = FakeAgent()
    builder = SchedulerBuilder(
        from_yaml(yaml_text), config, MemPersister()
    )
    builder.set_inventory(SliceInventory(hosts))
    builder.set_agent(agent)
    scheduler = builder.build()
    monitor = scheduler.health
    # the bench injects gauges directly (the sandbox/wire fan-in is
    # bench_health_overhead's subject): park collection
    monitor.telemetry_interval_s = 1e9
    monitor._last_telemetry = 1e18
    acked = set()

    def ack():
        for info in list(agent.launched):
            if info.task_id not in acked:
                acked.add(info.task_id)
                agent.send(TaskStatus(
                    task_id=info.task_id, state=TaskState.RUNNING,
                    ready=True, agent_id=info.agent_id,
                ))

    def running_serve_tasks():
        out = []
        for name, status in scheduler.state_store.fetch_statuses().items():
            if status.state is TaskState.RUNNING and \
                    name.startswith("serve-"):
                out.append(name)
        return out

    def inject(offered: float):
        live = running_serve_tasks()
        depth = offered / max(1, len(live))
        monitor._serving_stats = {
            name: {"queue_depth": depth} for name in live
        }
        monitor._serving_env = {name: {} for name in live}
        monitor._telemetry_seq += 1

    def health_events():
        return scheduler.journal.events(kinds=("health",))

    def spin(offered: float, until, timeout_s: float, label: str):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            inject(offered)
            scheduler.run_cycle()
            ack()
            if until():
                return
        raise RuntimeError(f"slo bench: {label} not reached "
                           f"in {timeout_s}s")

    # deploy the single pod
    spin(0.0, lambda: scheduler.deploy_manager.get_plan().is_complete,
         30.0, "initial deploy")

    # phase 1: the breach
    t_breach = time.monotonic()
    spin(
        48.0,
        lambda: any(e.get("stage") == "start" for e in health_events()),
        30.0, "scale-out plan",
    )
    t_plan = time.monotonic()
    spin(
        48.0,
        lambda: any(
            e.get("detector") == "slo" and e.get("cleared")
            for e in scheduler.journal.events(kinds=("alert",))
        ) and scheduler.actions.manager.phase_for("serve") is None,
        60.0, "recovered SLO",
    )
    t_recovered = time.monotonic()
    count_after_out = scheduler.spec.pod("serve").count

    # phase 2: the quiet period
    t_quiet = time.monotonic()
    spin(
        0.5,
        lambda: any(
            e.get("verb") == "scale-in" and e.get("stage") == "complete"
            for e in health_events()
        ),
        60.0, "scale-in complete",
    )
    t_scaled_in = time.monotonic()

    stages = [
        (e["verb"], e["stage"]) for e in health_events()
        if e.get("stage") in ("start", "complete")
    ]
    outs = [s for s in stages if s[0] == "scale-out"]
    ins = [s for s in stages if s[0] == "scale-in"]
    # zero flap: one scale-out episode, then scale-in(s) — never an
    # out after an in, never overlapping opposite directions (starts
    # strictly alternate with their completes)
    first_in = stages.index(("scale-in", "start")) if ins else len(stages)
    zero_flap = (
        outs == [("scale-out", "start"), ("scale-out", "complete")]
        and all(s[0] == "scale-in" for s in stages[first_in:])
        and stages[:2] == outs
    )
    assert zero_flap, stages
    assert count_after_out == 3, count_after_out
    scale_plan_s = t_plan - t_breach
    recovered_s = t_recovered - t_breach
    scale_in_s = t_scaled_in - t_quiet
    assert scale_plan_s < 10.0, scale_plan_s
    assert recovered_s < 30.0, recovered_s
    assert scale_in_s < 30.0, scale_in_s
    return {
        "slo_recovery_scale_plan_s": round(scale_plan_s, 3),
        "slo_recovery_recovered_s": round(recovered_s, 3),
        "slo_recovery_scale_in_s": round(scale_in_s, 3),
        "slo_recovery_count_after_out": count_after_out,
        "slo_recovery_count_final": scheduler.spec.pod("serve").count,
        "slo_recovery_zero_flap": 1 if zero_flap else 0,
        "slo_recovery_events": len(stages),
    }


def bench_preemption_recovery() -> dict:
    """Preemption -> gang recovery latency (ISSUE 13) at 64 hosts.

    Two scenarios over a 16-slice/64-host fleet with one 4-host
    tpu-gang trainer (FakeAgent — control-plane latency, no jax):

      preemption_resume_s       single gang-host kill -> the WHOLE
                                gang relaunched and RUNNING again
                                (kill survivors, unreserve the broken
                                sub-slice, re-place honoring torus
                                adjacency on a spare slice, statuses
                                acked) — "time to training resumed"
                                at the scheduler's granularity
      preemption_storm_s        a 4-kill storm (2 at once, a third
                                mid-recovery, a fourth at a plan-
                                transition boundary) -> converged
                                with the storm invariants held (zero
                                double-reservations, zero orphaned
                                reservations on preempted hosts,
                                exactly one gang incarnation running)

    Wall budgets are generous CI fences (shared boxes swing), not
    perf claims: the point is that recovery converges in control-
    plane time, not operator time."""
    from dcos_commons_tpu.offer.inventory import make_test_fleet
    from dcos_commons_tpu.testing.chaos import (
        RECOVERY_ACTIVE,
        STORM_START,
        PreemptSpec,
        PreemptionStorm,
    )

    def fleet():
        hosts = []
        for s in range(16):  # 64 TPU hosts, 16 placeable slices
            hosts.extend(make_test_fleet(
                slice_id=f"pod-{s}", host_grid=(2, 2), chip_block=(2, 2),
                cpus=16.0, memory_mb=65536,
            ))
        return hosts

    # single gang-host preemption
    storm = PreemptionStorm(
        [PreemptSpec(at=STORM_START, hosts=1)], hosts=fleet(),
    )
    t0 = time.monotonic()
    report = storm.run(timeout_s=60.0)
    single_s = time.monotonic() - t0
    single_cycles = report.cycles
    storm.shutdown()

    # 4-kill storm: 2 simultaneous, 1 mid-recovery, 1 at a span
    # boundary the recovery work itself causes
    storm = PreemptionStorm(
        [
            PreemptSpec(at=STORM_START, hosts=2),
            PreemptSpec(at=RECOVERY_ACTIVE, occurrence=1, hosts=1),
            PreemptSpec(at="mid-plan-transition", occurrence=2, hosts=1),
        ],
        hosts=fleet(),
    )
    t0 = time.monotonic()
    storm_report = storm.run(timeout_s=120.0)
    storm_s = time.monotonic() - t0
    storm.shutdown()

    assert report.converged and storm_report.converged
    assert single_s < 10.0, f"single-kill resume took {single_s:.1f}s"
    assert storm_s < 30.0, f"4-kill storm took {storm_s:.1f}s"
    return {
        "preemption_hosts": 64,
        "preemption_resume_s": round(single_s, 3),
        "preemption_resume_cycles": single_cycles,
        "preemption_storm_kills": len(storm_report.preempted),
        "preemption_storm_s": round(storm_s, 3),
        "preemption_storm_cycles": storm_report.cycles,
        "preemption_storm_converged": storm_report.converged,
    }


def bench_multislice() -> dict:
    """Multi-slice gang lifecycle (ISSUE 20) on a 10,000-host world.

    One 2-slice x 4x4 elastic trainer gang (8 hosts over DCN) on a
    fleet of 2,500 slices where exactly TWO slices match the gang's
    generation — so a whole-slice preemption cannot re-place at full
    width and MUST take the elastic whole-slice shrink path
    (FakeAgent — control-plane latency, no jax):

      multislice_deploy_s         spec PUT -> 8 workers RUNNING with
                                  the cross-slice coordinator contract
                                  (TPU_SLICE_COORDS et al) claimed —
                                  slice-set placement over 10k hosts
      multislice_shrink_resume_s  one whole slice preempted,
                                  physically, statuses never arrive ->
                                  converged at 1 slice (kill
                                  survivors, unreserve, re-place
                                  shrunken, trim) — "time to training
                                  resumed at reduced width"
      multislice_regrow_s         the dead slice's hosts return ->
                                  converged back at declared width
                                  (the manager's elastic-regrow
                                  choreography)

    Wall budgets are generous CI fences (shared boxes swing), not
    perf claims: the point is that whole-slice elasticity converges
    in control-plane time even on a 10k-host world."""
    from dcos_commons_tpu.offer.inventory import make_test_fleet
    from dcos_commons_tpu.testing.chaos import (
        CHAOS_MULTISLICE_YAML,
        PreemptSpec,
        PreemptionStorm,
        STORM_START,
    )

    def fleet():
        hosts = []
        for s in range(2):  # the only slices matching the gang
            hosts.extend(make_test_fleet(
                slice_id=f"gang-{s}", host_grid=(2, 2),
                chip_block=(2, 2), generation="v5p",
                cpus=16.0, memory_mb=65536,
            ))
        for s in range(2498):  # 9,992 filler hosts, wrong generation
            hosts.extend(make_test_fleet(
                slice_id=f"filler-{s}", host_grid=(2, 2),
                chip_block=(2, 2), generation="v5e",
                cpus=16.0, memory_mb=65536,
            ))
        return hosts

    storm = PreemptionStorm(
        [PreemptSpec(at=STORM_START, hosts=1, whole_slice=True)],
        yaml_text=CHAOS_MULTISLICE_YAML.replace(
            "generation: v5e", "generation: v5p"
        ),
        hosts=fleet(),
    )
    scheduler = storm.harness.build_scheduler()
    storm.scheduler = scheduler
    n_hosts = len(storm.harness.hosts)

    # phase 1: the 2-slice deploy
    t0 = time.monotonic()
    deadline = t0 + 300.0
    while time.monotonic() < deadline:
        scheduler.run_cycle()
        storm._ack_staging(scheduler)
        if scheduler.deploy_manager.get_plan().is_complete:
            break
    deploy_s = time.monotonic() - t0
    assert scheduler.deploy_manager.get_plan().is_complete, \
        "2-slice deploy never completed"

    # phase 2: one whole slice preempted mid-training -> shrink
    t0 = time.monotonic()
    storm.preempt_now(1, whole_slice=True)
    shrink_cycles = 0
    while time.monotonic() < deadline:
        scheduler.run_cycle()
        shrink_cycles += 1
        for host_id in sorted(storm._unnotified):
            scheduler.note_host_preempted(host_id)
            storm._unnotified.discard(host_id)
        storm._ack_staging(scheduler)
        if storm._gang_converged(scheduler):
            break
    shrink_s = time.monotonic() - t0
    stored = [
        info for info in scheduler.state_store.fetch_tasks()
        if info.pod_type == "trainer"
    ]
    assert len(stored) == 4, \
        f"expected a 1-slice shrunken gang, got {len(stored)} workers"
    verbs = [
        e.get("verb")
        for e in scheduler.journal.events(kinds=("recovery",))
    ]
    assert "elastic-shrink" in verbs, verbs

    # phase 3: the dead slice returns -> regrow to declared width
    for host_id in list(storm.report.preempted):
        scheduler.inventory.mark_up(host_id)
    t0 = time.monotonic()
    regrow_cycles = 0
    regrown = False
    while time.monotonic() < deadline:
        scheduler.run_cycle()
        regrow_cycles += 1
        storm._ack_staging(scheduler)
        stored = [
            info for info in scheduler.state_store.fetch_tasks()
            if info.pod_type == "trainer"
        ]
        if len(stored) == 8 and storm._gang_converged(scheduler):
            regrown = True
            break
    regrow_s = time.monotonic() - t0
    assert regrown, "gang never regrew to declared width"
    verbs = [
        e.get("verb")
        for e in scheduler.journal.events(kinds=("recovery",))
    ]
    assert "elastic-regrow" in verbs, verbs
    storm.shutdown()

    assert deploy_s < 120.0, f"2-slice deploy took {deploy_s:.1f}s"
    assert shrink_s < 60.0, f"shrink-resume took {shrink_s:.1f}s"
    assert regrow_s < 60.0, f"regrow took {regrow_s:.1f}s"
    return {
        "multislice_hosts": n_hosts,
        "multislice_deploy_s": round(deploy_s, 3),
        "multislice_shrink_resume_s": round(shrink_s, 3),
        "multislice_shrink_cycles": shrink_cycles,
        "multislice_regrow_s": round(regrow_s, 3),
        "multislice_regrow_cycles": regrow_cycles,
    }


def bench_router_scale() -> dict:
    """Serving front door (ISSUE 12), CPU-runnable and jax-free: an
    open-loop load sweep through the multi-pod RequestRouter over 1,
    2 and 4 in-process "pods" — each a REAL PagedEngine (page-
    budgeted admission, chunked prefill, refcounted prefix cache)
    over a deterministic chain model whose decode tick costs a fixed
    calibrated sleep, so pod service time is held constant and the
    sweep measures the ROUTING layer: placement quality, affinity,
    drain/failover.  (In production each pod is its own host; the
    sleep stands in for the chip tick.)  Four fences:

    * GREEDY EQUALITY, every round — continuations through the
      router are token-identical to direct-to-pod (the chain
      oracle), including through prefix-cache hits and mid-sweep
      failover: the router must never corrupt or duplicate a reply;
    * NEAR-LINEAR SCALING — aggregate tokens/s at 4 pods >= 3x the
      single-pod run under proportionally-scaled offered load;
    * AFFINITY BEATS SPRAY — under a shared-system-prompt session
      workload, prefix-affinity routing must beat round-robin on the
      pods' aggregate prefix_cache_hit_rate (random spray makes
      every pod re-prefill every session: the 1/N dilution);
    * BOUNDED DRAIN — a mid-sweep drain + kill of one pod loses no
      request (in-flight fails over within the retry budget, the
      drained pod takes zero new admissions) and p95 completion
      latency stays within a fenced ratio of the steady-state round.

    Open-loop throughout: arrivals ride a fixed schedule, never
    completions — a saturating tier cannot slow its offered load.
    """
    import random
    import statistics
    import threading

    import numpy as np

    from dcos_commons_tpu.router import PodTransportError, RequestRouter
    from dcos_commons_tpu.serve.engine import PagedEngine

    _V = 997

    def _chain_first(prompt):
        return (sum(prompt) * 31 + len(prompt)) % _V

    def _chain_next(tok, pos):
        return (tok * 7 + pos * 3 + 1) % _V

    def _oracle(prompt, n):
        out = [_chain_first(prompt)]
        pos = len(prompt)
        while len(out) < n:
            out.append(_chain_next(out[-1], pos))
            pos += 1
        return out

    # pod geometry: pages of 4 so an 8-token session prefix is two
    # cacheable full pages; the decode tick's sleep is the modeled
    # chip time (dominates the host bookkeeping by ~100x)
    P_TOK, CHUNK, MAX_LEN, PROMPT_LEN = 4, 8, 32, 24
    SLOTS, STEP_S = 8, 0.01
    PAGES = SLOTS * (MAX_LEN // P_TOK)
    MAX_NEW = 8

    class ChainArena:
        """The fake device half of a paged pod: every prefilled
        token is written into its (page, offset) cell, so a prefix-
        cache-served prefix is RECONSTRUCTED from the arena exactly
        like real attention would gather it — first tokens depend on
        the full prompt regardless of how much the cache served, and
        greedy equality survives any hit depth."""

        def __init__(self):
            self.cells = {}  # page -> {offset: token}
            self.lock = threading.Lock()

        def prefill_chunk(self, padded, slot, table, start, true_len,
                          temp, seed):
            time.sleep(STEP_S * 0.5)  # the modeled prefill dispatch
            with self.lock:
                buf = [
                    self.cells[int(table[pos // P_TOK])][pos % P_TOK]
                    for pos in range(start)
                ]
                for i in range(true_len):
                    pos = start + i
                    page = int(table[pos // P_TOK])
                    tok = int(padded[0, i])
                    self.cells.setdefault(page, {})[pos % P_TOK] = tok
                    buf.append(tok)
            return _chain_first(buf)

        def decode(self, tok, pos, temps, seeds, tables, n_active):
            time.sleep(STEP_S)  # the modeled decode tick
            return np.asarray(
                [_chain_next(int(t), int(q))
                 for t, q in zip(tok, pos)],
                np.int32,
            )

    class BenchPod:
        def __init__(self, name):
            self.name = name
            self.arena = ChainArena()
            self.engine = PagedEngine(
                self.arena.prefill_chunk, self.arena.decode, SLOTS,
                MAX_LEN, PROMPT_LEN, page_tokens=P_TOK, pages=PAGES,
                chunk_tokens=CHUNK, prefix_cache=True,
                queue_timeout_s=600,
            )
            self.killed = threading.Event()
            self.admitted = 0

        def send(self, request):
            if self.killed.is_set():
                raise PodTransportError(f"{self.name} is dead")
            self.admitted += 1
            out = self.engine.submit(
                request["tokens"], request["max_new_tokens"],
            )
            if self.killed.is_set():
                # the reply died on the wire: the failover trigger
                raise PodTransportError(f"{self.name} died mid-reply")
            return out

        def stop(self):
            self.engine.stop()

    def build_workload(n_pods, rng):
        """Per-pod-scaled session traffic: 6-request sessions sharing
        an 8-token (two-full-page) prefix, plus unshared one-offs —
        arrivals saturate the tier at ~1.3x its service rate so the
        makespan measures sustained routing throughput."""
        n_sessions = 10 * n_pods
        reqs = []
        for s in range(n_sessions):
            prefix = [rng.randrange(_V) for _ in range(8)]
            for i in range(6):
                reqs.append({
                    "prompt": prefix + [
                        rng.randrange(_V) for _ in range(1 + i % 4)
                    ],
                    "n": [2, 4, MAX_NEW, MAX_NEW, 4, 6][i % 6],
                })
        for _ in range(12 * n_pods):
            reqs.append({
                "prompt": [rng.randrange(_V)
                           for _ in range(2 + rng.randrange(8))],
                "n": [2, 4, MAX_NEW][rng.randrange(3)],
            })
        rng.shuffle(reqs)
        useful = sum(r["n"] for r in reqs)
        # offered rate = 1.5x the tier's token service rate: deep
        # enough saturation that every pod's decode rows stay full
        capacity_tps = n_pods * SLOTS / STEP_S
        span = useful / (1.5 * capacity_tps)
        arrivals = sorted(rng.uniform(0.0, span) for _ in reqs)
        return reqs, arrivals, useful

    def run_round(n_pods, policy, rng, drain_script=None):
        """One open-loop load through a fresh router + fresh pods.
        Returns (metrics dict, pods) — pods still warm for gauge
        reads; caller stops them."""
        pods = {f"p{i}": BenchPod(f"p{i}") for i in range(n_pods)}
        router = RequestRouter(
            lambda name, addr, req: pods[name].send(req),
            page_tokens=P_TOK, policy=policy, stale_after_s=5.0,
            retry_budget=2,
            # a tight slack keeps session pinning from imbalancing
            # the tier: a hot pod sheds affinity traffic early
            affinity_slack=2.0,
        )
        router.update_pods(
            {n: {"address": f"{n}:0"} for n in pods}, generation="g1"
        )
        stop_poll = threading.Event()

        def poller():
            while not stop_poll.is_set():
                for name, pod in pods.items():
                    if not pod.killed.is_set():
                        router.observe_stats(name, pod.engine.stats())
                stop_poll.wait(0.025)

        reqs, arrivals, useful = build_workload(n_pods, rng)
        results = [None] * len(reqs)
        done_s = [0.0] * len(reqs)
        errors = []
        t0 = time.monotonic()

        def client(i):
            delay = arrivals[i] - (time.monotonic() - t0)
            if delay > 0:
                time.sleep(delay)
            t_req = time.monotonic()
            try:
                results[i] = router.submit(
                    reqs[i]["prompt"], reqs[i]["n"]
                )
                done_s[i] = time.monotonic() - t_req
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        poll_thread = threading.Thread(target=poller, daemon=True)
        poll_thread.start()
        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(len(reqs))
        ]
        span = arrivals[-1] if arrivals else 0.0
        script_thread = None
        if drain_script is not None:
            script_thread = threading.Thread(
                target=drain_script, args=(router, pods, t0, span),
                daemon=True,
            )
            script_thread.start()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        makespan = time.monotonic() - t0
        stop_poll.set()
        poll_thread.join(timeout=5)
        if script_thread is not None:
            script_thread.join(timeout=5)
        assert not errors, errors[:3]
        # correctness before speed, EVERY round: token-identical to
        # direct-to-pod, through cache hits and failovers alike
        for req, result in zip(reqs, results):
            assert result == _oracle(req["prompt"], req["n"]), (
                "router changed a greedy continuation"
            )
        hits = lookups = 0
        for pod in pods.values():
            s = pod.engine.stats()
            hits += s["prefix_cache_hits"]
            lookups += s["prefix_cache_lookups"]
        metrics = {
            "tps": useful / makespan,
            "p95": statistics.quantiles(done_s, n=20)[-1]
            if len(done_s) >= 2 else done_s[0],
            "hit_rate": hits / lookups if lookups else 0.0,
            "router": router.stats(),
        }
        return metrics, pods

    out = {
        "router_scale_step_s": STEP_S,
        "router_scale_slots": SLOTS,
        "router_scale_page_tokens": P_TOK,
    }

    # ---- the 1 -> 2 -> 4 pod sweep (affinity policy, the default)
    sweep = {}
    for n_pods in (1, 2, 4):
        m, pods = run_round(n_pods, "affinity", random.Random(n_pods))
        for pod in pods.values():
            pod.stop()
        sweep[n_pods] = m
        out[f"router_scale_tokens_per_s_{n_pods}p"] = round(m["tps"], 1)
        out[f"router_scale_p95_s_{n_pods}p"] = round(m["p95"], 4)
    scale_x = sweep[4]["tps"] / sweep[1]["tps"]
    out["router_scale_x_4p"] = round(scale_x, 2)

    # ---- prefix affinity vs round-robin spray (4 pods, same seed:
    # identical session workload, only the placement policy differs)
    aff, aff_pods = run_round(4, "affinity", random.Random(99))
    for pod in aff_pods.values():
        pod.stop()
    rr, rr_pods = run_round(4, "round-robin", random.Random(99))
    for pod in rr_pods.values():
        pod.stop()
    out["router_affinity_prefix_hit_rate"] = round(aff["hit_rate"], 4)
    out["router_roundrobin_prefix_hit_rate"] = round(rr["hit_rate"], 4)
    out["router_affinity_tokens_per_s"] = round(aff["tps"], 1)
    out["router_roundrobin_tokens_per_s"] = round(rr["tps"], 1)
    out["router_affinity_hit_rate_gain"] = round(
        aff["hit_rate"] - rr["hit_rate"], 4
    )

    # ---- mid-sweep drain + kill: graceful drain at 40% of the
    # arrival span, hard kill at 70% — in-flight work fails over
    def drain_script(router, pods, t0, span):
        deadline = t0 + 0.4 * span
        wait = deadline - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        router.drain("p3")
        wait = t0 + 0.7 * span - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        pods["p3"].killed.set()

    drain, drain_pods = run_round(
        4, "affinity", random.Random(7), drain_script=drain_script
    )
    drained_admitted = drain_pods["p3"].admitted
    for pod in drain_pods.values():
        pod.stop()
    out["router_drain_p95_s"] = round(drain["p95"], 4)
    drain_ratio = drain["p95"] / max(sweep[4]["p95"], 1e-9)
    out["router_drain_p95_ratio"] = round(drain_ratio, 2)
    out["router_drain_failovers"] = drain["router"]["router_failovers"]
    out["router_drain_completed"] = drain["router"]["requests_completed"]

    print(
        f"[router-scale] tokens/s 1p {sweep[1]['tps']:.0f} -> 2p "
        f"{sweep[2]['tps']:.0f} -> 4p {sweep[4]['tps']:.0f} "
        f"({scale_x:.2f}x), prefix hit rate affinity "
        f"{aff['hit_rate']:.0%} vs round-robin {rr['hit_rate']:.0%}, "
        f"drain p95 ratio {drain_ratio:.2f} "
        f"({drain['router']['router_failovers']} failover(s))",
        file=sys.stderr, flush=True,
    )
    # the headline fences
    assert scale_x >= 3.0, (
        f"aggregate tokens/s at 4 pods only {scale_x:.2f}x one pod "
        "(near-linear fence is 3.0x)"
    )
    assert aff["hit_rate"] > rr["hit_rate"], (
        f"prefix affinity ({aff['hit_rate']:.2%}) did not beat "
        f"round-robin spray ({rr['hit_rate']:.2%}) on prefix cache "
        "hit rate"
    )
    # every drain-round request completed (none lost) and the drained
    # pod took zero admissions after its drain point is implied by
    # the equality + failed-send accounting; the p95 collar bounds
    # the failover detour
    assert drain_ratio <= 4.0, (
        f"p95 completion latency through a pod drain blew out "
        f"{drain_ratio:.1f}x vs steady state (fence 4.0x)"
    )
    assert drained_admitted < drain["router"]["requests_admitted"], (
        "drain round routed every request at the drained pod"
    )
    return out


def bench_disagg() -> dict:
    """Disaggregated prefill/decode + live KV page migration
    (ISSUE 16), CPU-runnable and jax-free: the same calibrated-sleep
    chain pods as ``bench_router_scale``, arranged two ways under an
    identical long-prefill-heavy mix —

    * UNIFIED: two unified pods; long prompts' chunked prefill
      interleaves with every pod's decode ticks, so short requests
      pay head-of-line TTFT behind long prefills;
    * DISAGGREGATED: one prefill-role pod + one decode-role pod; the
      router sends long prompts to prefill capacity, the prefill pod
      streams finished pages to the decode pool (the migration
      protocol), and short requests land on a pod that never runs a
      long prefill.

    Fences: greedy equality on EVERY request in both topologies
    (zero token loss through handoff + collect-follow), disaggregated
    short-request p95 TTFT strictly better than unified, and
    drain-with-migration strictly faster than waiting out the
    generations.  Also reported, unfenced: decode tick jitter per
    topology and the bytes/duration of one mid-generation move over
    the simulated DCN transport.
    """
    import random
    import statistics
    import threading

    import numpy as np

    from dcos_commons_tpu.router import RequestRouter
    from dcos_commons_tpu.serve.engine import PagedEngine
    from dcos_commons_tpu.serve.migration import (
        PrefillHandoff,
        SessionMigratedError,
        SimulatedDcnTransport,
        drain_sessions,
        migrate_session,
    )

    _V = 997

    def _chain_first(prompt):
        return (sum(prompt) * 31 + len(prompt)) % _V

    def _chain_next(tok, pos):
        return (tok * 7 + pos * 3 + 1) % _V

    def _oracle(prompt, n):
        out = [_chain_first(prompt)]
        pos = len(prompt)
        while len(out) < n:
            out.append(_chain_next(out[-1], pos))
            pos += 1
        return out

    P_TOK, CHUNK, MAX_LEN, PROMPT_LEN = 4, 8, 64, 48
    SLOTS, STEP_S, PAGES = 8, 0.01, 160
    LONG = 40  # >= the router's 4*page_tokens prefill-route floor

    class ChainArena:
        """Content-faithful fake device (the test_migration arena):
        every token lands in its (page, offset) cell so a migrated
        page's payload is the real export/import contract, and
        prefill resume after a move reads the spliced cells."""

        def __init__(self):
            self.cells = {}
            self.lock = threading.Lock()
            self.ticks = []  # decode dispatch timestamps (jitter)

        def prefill_chunk(self, padded, slot, table, start, true_len,
                          temp, seed):
            # a full-width chunk costs about a decode tick on real
            # chips; the 5-chunk long prompts are the head-of-line
            # hazard this bench measures
            time.sleep(STEP_S)
            with self.lock:
                buf = [
                    self.cells[int(table[pos // P_TOK])][pos % P_TOK]
                    for pos in range(start)
                ]
                for i in range(true_len):
                    pos = start + i
                    page = int(table[pos // P_TOK])
                    tok = int(padded[0, i])
                    self.cells.setdefault(page, {})[pos % P_TOK] = tok
                    buf.append(tok)
            return _chain_first(buf)

        def decode(self, tok, pos, temps, seeds, tables, n_active):
            time.sleep(STEP_S)  # the modeled decode tick
            with self.lock:
                self.ticks.append(time.monotonic())
                for s in range(len(tok)):
                    if int(pos[s]) > 0:
                        page = int(tables[s][int(pos[s]) // P_TOK])
                        if page != 0:
                            self.cells.setdefault(page, {})[
                                int(pos[s]) % P_TOK
                            ] = int(tok[s])
            return np.asarray(
                [_chain_next(int(t), int(q))
                 for t, q in zip(tok, pos)],
                np.int32,
            )

        def read_page(self, page):
            with self.lock:
                return dict(self.cells.get(page, {}))

        def write_page(self, page, payload):
            with self.lock:
                self.cells[page] = dict(payload)

    class BenchPod:
        def __init__(self, name, role="unified", handoff=None):
            self.name = name
            self.arena = ChainArena()
            self.engine = PagedEngine(
                self.arena.prefill_chunk, self.arena.decode, SLOTS,
                MAX_LEN, PROMPT_LEN, page_tokens=P_TOK, pages=PAGES,
                chunk_tokens=CHUNK, prefix_cache=True, role=role,
                read_page=self.arena.read_page,
                write_page=self.arena.write_page, handoff=handoff,
                queue_timeout_s=600,
            )

        def send(self, request):
            if "collect" in request:
                # the router following a migrated session
                return [self.engine.collect(
                    int(request["collect"]), timeout=120
                )]
            return self.engine.submit(
                request["tokens"], request["max_new_tokens"]
            )

        def stop(self):
            self.engine.stop()

    def build_mix(rng):
        """Long-prefill-heavy: 36 long prompts (5 prefill chunks
        each), 48 decode-load shorts, and 24 one-token PROBES whose
        client-side completion time IS their TTFT (queue + prefill +
        first sample; no decode tail to blur it)."""
        reqs = []
        for _ in range(36):
            reqs.append({
                "prompt": [rng.randrange(_V) for _ in range(LONG)],
                "n": 4, "probe": False,
            })
        for _ in range(44):
            reqs.append({
                "prompt": [rng.randrange(_V)
                           for _ in range(4 + rng.randrange(8))],
                "n": 6, "probe": False,
            })
        for _ in range(32):
            reqs.append({
                "prompt": [rng.randrange(_V)
                           for _ in range(4 + rng.randrange(4))],
                "n": 1, "probe": True,
            })
        rng.shuffle(reqs)
        arrivals = sorted(rng.uniform(0.0, 2.4) for _ in reqs)
        return reqs, arrivals

    def run_topology(disagg):
        """One open-loop mix through a fresh router + fresh pods;
        identical workload seed either way, only the topology
        differs.  Returns (probe p95 TTFT, decode tick jitter ms,
        handoff counters)."""
        handoff = None
        if disagg:
            pods = {}
            pods["dc0"] = BenchPod("dc0", role="decode")
            handoff = PrefillHandoff(
                lambda: {"dc0": pods["dc0"].engine}
            )
            pods["pf0"] = BenchPod(
                "pf0", role="prefill", handoff=handoff
            )
            entries = {
                "pf0": {"address": "pf0:0", "role": "prefill"},
                "dc0": {"address": "dc0:0", "role": "decode"},
            }
            decode_arenas = [pods["dc0"].arena]
        else:
            pods = {n: BenchPod(n) for n in ("u0", "u1")}
            entries = {n: {"address": f"{n}:0"} for n in pods}
            decode_arenas = [p.arena for p in pods.values()]
        router = RequestRouter(
            lambda name, addr, req: pods[name].send(req),
            page_tokens=P_TOK, policy="affinity",
            stale_after_s=5.0, retry_budget=2,
        )
        router.update_pods(entries, generation="g1")
        stop_poll = threading.Event()

        def poller():
            while not stop_poll.is_set():
                for name, pod in pods.items():
                    router.observe_stats(name, pod.engine.stats())
                stop_poll.wait(0.025)

        rng = random.Random(16)
        reqs, arrivals = build_mix(rng)
        results = [None] * len(reqs)
        done_s = [0.0] * len(reqs)
        errors = []
        t0 = time.monotonic()

        def client(i):
            delay = arrivals[i] - (time.monotonic() - t0)
            if delay > 0:
                time.sleep(delay)
            t_req = time.monotonic()
            try:
                results[i] = router.submit(
                    reqs[i]["prompt"], reqs[i]["n"]
                )
                done_s[i] = time.monotonic() - t_req
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        poll_thread = threading.Thread(target=poller, daemon=True)
        poll_thread.start()
        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(len(reqs))
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        stop_poll.set()
        poll_thread.join(timeout=5)
        assert not errors, errors[:3]
        # zero token loss, EVERY request: identical to direct-to-pod,
        # through prefill handoff + collect-follow included
        for req, result in zip(reqs, results):
            assert result == _oracle(req["prompt"], req["n"]), (
                "topology changed a greedy continuation"
            )
        probes = [d for r, d in zip(reqs, done_s) if r["probe"]]
        p95 = statistics.quantiles(probes, n=20)[-1]
        gaps = []
        for arena in decode_arenas:
            with arena.lock:
                ticks = list(arena.ticks)
            gaps.extend(
                b - a for a, b in zip(ticks, ticks[1:])
                if b - a <= 10 * STEP_S  # drop idle-loop stretches
            )
        jitter_ms = (
            statistics.pstdev(gaps) * 1e3 if len(gaps) >= 2 else 0.0
        )
        counters = (
            (handoff.handoffs, handoff.fallbacks) if handoff
            else (0, 0)
        )
        for pod in pods.values():
            pod.stop()
        return p95, jitter_ms, counters

    out = {
        "disagg_step_s": STEP_S,
        "disagg_long_prompt_tokens": LONG,
    }

    # ---- unified vs disaggregated under the same mix
    uni_p95, uni_jit, _ = run_topology(disagg=False)
    dis_p95, dis_jit, (handoffs, fallbacks) = run_topology(
        disagg=True
    )
    out["disagg_unified_ttft_p95_s"] = round(uni_p95, 4)
    out["disagg_split_ttft_p95_s"] = round(dis_p95, 4)
    out["disagg_ttft_gain_x"] = round(uni_p95 / max(dis_p95, 1e-9), 2)
    out["disagg_unified_tick_jitter_ms"] = round(uni_jit, 3)
    out["disagg_decode_tick_jitter_ms"] = round(dis_jit, 3)
    out["disagg_handoffs"] = handoffs
    out["disagg_handoff_fallbacks"] = fallbacks

    # ---- drain a loaded pod: wait out the generations vs migrate
    def load_sessions(src):
        """Six mid-generation sessions on ``src``; returns (threads,
        results, prompts, n) once every session is decoding."""
        rng = random.Random(7)
        prompts = [
            [rng.randrange(_V) for _ in range(8)] for _ in range(6)
        ]
        n = 48
        results = [None] * len(prompts)

        def run(i):
            try:
                results[i] = src.engine.submit([prompts[i]], n)[0]
            except SessionMigratedError as e:
                results[i] = e
        threads = [
            threading.Thread(target=run, args=(i,), daemon=True)
            for i in range(len(prompts))
        ]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            sess = src.engine.sessions()
            if (len(sess) == len(prompts)
                    and all(s["state"] == "decode" for s in sess)
                    and src.engine.stats()["tokens_out"]
                    >= 4 * len(prompts)):
                break
            time.sleep(0.005)
        else:
            raise AssertionError("sessions never reached mid-decode")
        return threads, results, prompts, n

    # without migration: drain = stop admitting, wait for the tail
    src = BenchPod("src")
    threads, results, prompts, n = load_sessions(src)
    t0 = time.monotonic()
    for th in threads:
        th.join(timeout=120)
    legacy_s = time.monotonic() - t0
    for got, prompt in zip(results, prompts):
        assert got == _oracle(prompt, n)
    src.stop()

    # with migration: the same tail moves to a peer in one pass
    src, dst = BenchPod("src"), BenchPod("dst")
    threads, results, prompts, n = load_sessions(src)
    t0 = time.monotonic()
    report = drain_sessions(src.engine, {"dst": dst.engine})
    migrate_s = time.monotonic() - t0
    assert all(row["ok"] for row in report), report
    assert src.engine.sessions() == []
    for th in threads:
        th.join(timeout=120)
    for got, prompt in zip(results, prompts):
        assert isinstance(got, SessionMigratedError), got
        assert dst.engine.collect(got.dest_rid, timeout=120) \
            == _oracle(prompt, n), "migration lost or doubled tokens"
    out["disagg_drain_legacy_s"] = round(legacy_s, 3)
    out["disagg_drain_migrate_s"] = round(migrate_s, 3)
    out["disagg_drain_speedup_x"] = round(
        legacy_s / max(migrate_s, 1e-9), 1
    )
    src.stop()
    dst.stop()

    # ---- one forced mid-generation move over the modeled DCN
    src, dst = BenchPod("src"), BenchPod("dst")
    rng = random.Random(11)
    prompt = [rng.randrange(_V) for _ in range(16)]
    n = 24
    moved = {}

    def mover():
        try:
            moved["r"] = src.engine.submit([prompt], n)[0]
        except SessionMigratedError as e:
            moved["r"] = e
    th = threading.Thread(target=mover, daemon=True)
    th.start()
    deadline = time.monotonic() + 30
    rid = None
    while time.monotonic() < deadline:
        sess = src.engine.sessions()
        if (sess and sess[0]["state"] == "decode"
                and src.engine.stats()["tokens_out"] >= 8):
            rid = sess[0]["rid"]
            break
        time.sleep(0.005)
    assert rid is not None, "session never reached mid-decode"
    record = migrate_session(
        src.engine, dst.engine, rid, dest_name="dst",
        transport=SimulatedDcnTransport(),
    )
    th.join(timeout=120)
    err = moved["r"]
    assert isinstance(err, SessionMigratedError), err
    assert dst.engine.collect(err.dest_rid, timeout=120) \
        == _oracle(prompt, n), "mid-generation move lost tokens"
    assert src.engine.stats()["migrations_out"] == 1
    assert dst.engine.stats()["migrations_in"] == 1
    out["disagg_migration_kbytes"] = round(record.bytes / 1024, 1)
    out["disagg_migration_ms"] = round(record.duration_s * 1e3, 1)
    out["disagg_migration_pages"] = record.pages
    out["disagg_migration_greedy_equal"] = 1
    src.stop()
    dst.stop()

    print(
        f"[disagg] probe TTFT p95 unified {uni_p95 * 1e3:.0f}ms -> "
        f"split {dis_p95 * 1e3:.0f}ms "
        f"({out['disagg_ttft_gain_x']:.2f}x), tick jitter "
        f"{uni_jit:.2f} -> {dis_jit:.2f}ms, drain {legacy_s:.2f}s -> "
        f"{migrate_s:.2f}s ({out['disagg_drain_speedup_x']:.0f}x), "
        f"{handoffs} handoff(s) / {fallbacks} fallback(s)",
        file=sys.stderr, flush=True,
    )
    # the headline fences
    assert dis_p95 < uni_p95, (
        f"disaggregation did not improve short-request p95 TTFT "
        f"({dis_p95 * 1e3:.0f}ms vs unified {uni_p95 * 1e3:.0f}ms)"
    )
    assert handoffs >= 1, (
        "the prefill pod never handed a session to the decode pool"
    )
    assert migrate_s < legacy_s, (
        f"drain-with-migration ({migrate_s:.2f}s) was not faster "
        f"than waiting out the generations ({legacy_s:.2f}s)"
    )
    return out


def bench_train_step() -> dict:
    """The worker step-time fast path vs the loop it replaced
    (ISSUE 7), CPU-runnable.  Two loops over identical data from an
    identical init, same checkpoint cadence:

    * LEGACY — the pre-PR worker verbatim: donate=False step, block
      on every step's loss, stop-the-world save_checkpoint on the
      save steps;
    * FAST — the new worker defaults: donated buffers, bounded
      in-flight dispatch window (trace/steplog.py InflightWindow),
      AsyncCheckpointer saves (async device-side snapshot + background
      writer), with the writer drained INSIDE the measured makespan
      (the tail write is the async path's only serial cost).

    Fences, in order of importance: (1) LOSS EQUIVALENCE — the fast
    loop must reproduce the legacy loop's loss sequence EXACTLY under
    this deterministic config (donation, dispatch order, and snapshot
    copies may move buffers, never values — PR 6's token-equality
    discipline); (2) the fast loop must WIN the median of alternating
    legacy/fast pairs (ratios inside an adjacent pair mostly cancel
    this host's 2-3x load swings); (3) the COST-MODEL GATE — shardcheck.stepcompare holds
    the fast loop's measured p50 step time (records from the SAVE
    rounds) against the calibrated no-save device floor + wire model
    (0 wire on one chip): a save that stopped the world, or any step
    regression past TRAIN_STEP_GATE_PCT (default 50%%), trips it;
    (4) the async path's last checkpoint must restore bit-identically
    to the run's true final params (a snapshot aliasing a donated
    buffer would have been overwritten while the writer drained).

    Honesty note: this container's CPU backend executes jit
    computations INLINE at dispatch (measured: dispatch carries the
    whole step, block_until_ready returns in ~50us), so the dispatch
    window cannot hide host work HERE and the measured win comes from
    the non-blocking checkpoint path + donation.  On accelerator
    backends with real async dispatch the same loop structure also
    overlaps per-step host work with device compute; the window's
    accounting contract is fenced by tests/test_train_overlap.py
    either way."""
    import statistics
    import tempfile

    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.analysis.shardcheck import stepcompare
    from dcos_commons_tpu.models import (
        TransformerConfig,
        init_params,
        make_train_step,
    )
    from dcos_commons_tpu.trace.steplog import InflightWindow
    from dcos_commons_tpu.utils import (
        AsyncCheckpointer,
        restore_checkpoint,
        save_checkpoint,
    )

    # big enough that a step clears timer noise and a checkpoint is a
    # real file (~12 MB: params + adam moments), small enough that the
    # section fits a CI window
    config = TransformerConfig(
        vocab=256, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=352, max_seq=64, dtype=jnp.float32, remat=False,
    )
    optimizer = optax.adamw(3e-4)
    # save_every=2 makes the save path the dominant structural term:
    # twelve ~35ms stop-the-world saves on a ~20ms step give the
    # legacy arm a handicap the fast arm genuinely does not pay
    # (measured 1.4x median pairwise on the 2-core CI box, every
    # round >1.2) — far above this host's pairwise-residual noise
    steps, batch, inflight, save_every = 24, 4, 2, 2
    gate_pct = float(os.environ.get("TRAIN_STEP_GATE_PCT", "50"))
    legacy_fn = make_train_step(config, optimizer, donate=False)
    fast_fn = make_train_step(config, optimizer, donate=True)

    # deterministic per-step host batches, shared by both arms
    corpus = np.random.RandomState(0).randint(
        0, config.vocab, size=(steps, batch, config.max_seq + 1),
        dtype=np.int32,
    )

    def init_state():
        params = init_params(config, jax.random.key(0))
        return params, optimizer.init(params)

    class _Recorder:
        def __init__(self):
            self.records = []

        def record(self, step, **fields):
            self.records.append(dict(step=step, **fields))

    def run_loop(fast, ckpt_dir=None, staged=None):
        """One measured loop.  ``fast`` picks the whole arm: step fn,
        window size, save path.  Returns (losses by step, steplog
        records, makespan s, final params)."""
        params, opt_state = init_state()
        jax.block_until_ready(params)
        recorder = _Recorder()
        window = InflightWindow(recorder, inflight if fast else 0)
        checkpointer = None
        if fast and ckpt_dir is not None:
            checkpointer = AsyncCheckpointer(
                ckpt_dir, keep=2, max_pending=2
            )
        step_fn = fast_fn if fast else legacy_fn
        losses = {}
        t_start = time.monotonic()
        for i in range(steps):
            t0 = time.time()
            if staged is not None:
                tokens, targets = staged
            else:
                tokens = jnp.asarray(corpus[i, :, :-1])
                targets = jnp.asarray(corpus[i, :, 1:])
            params, opt_state, loss = step_fn(
                params, opt_state, tokens, targets
            )
            if ckpt_dir is not None and (i + 1) % save_every == 0:
                state = {"params": params, "opt_state": opt_state}
                if checkpointer is not None:
                    # async device-side snapshot, enqueued before the
                    # next dispatch donates these buffers
                    checkpointer.save(i + 1, state)
                else:
                    save_checkpoint(ckpt_dir, i + 1, state, keep=2)
            for s, ready in window.push(i, loss, t0):
                losses[s] = float(ready)
        for s, ready in window.drain():
            losses[s] = float(ready)
        if checkpointer is not None:
            # drain the writer INSIDE the makespan: the async arm
            # only wins by what it genuinely overlapped
            errors = checkpointer.close()
            assert not errors, f"async checkpoint errors: {errors}"
        makespan = time.monotonic() - t_start
        return losses, recorder.records, makespan, params

    # compile + warm both arms END TO END outside every measured
    # window — including the save paths (the fused snapshot copy and
    # the legacy save have first-call compile/alloc costs that must
    # not land in round 1)
    run_loop(False, ckpt_dir=tempfile.mkdtemp(prefix="bench-ckpt-warm-"))
    run_loop(True, ckpt_dir=tempfile.mkdtemp(prefix="bench-ckpt-warm-"))

    import gc

    gc.disable()  # the PR 5 lesson: a GC pause inside one arm of a
    try:          # pair fakes (or hides) a 10%-class effect
        # device floor for the gate: the fast loop, data pre-staged on
        # device, no saves — what the chip says a bare step costs
        staged = (
            jnp.asarray(corpus[0, :, :-1]), jnp.asarray(corpus[0, :, 1:])
        )
        # mean, not p50: the window bills ready-to-ready so TOTAL wall
        # is conserved; inline CPU dispatch clusters ready events,
        # which skews individual records but never their sum.  Two
        # calibrations, keep the LARGER mean: a floor measured in a
        # lucky-fast window would false-trip the gate, a lenient floor
        # still catches the 2x-class stop-the-world regressions the
        # gate exists for
        floor_us = 0.0
        for _cal in range(2):
            _l, floor_records, _m, _p = run_loop(True, staged=staged)
            floor_walls = [r["wall_s"] for r in floor_records]
            floor_us = max(
                floor_us, sum(floor_walls) / len(floor_walls) * 1e6
            )

        # measured side of the cost-model gate: the overlapped loop
        # doing its real per-step host work (slice + device_put), no
        # saves — save-stall detection belongs to the legacy/fast
        # speedup fence below, where both arms save
        _l, fast_records, _m, _p = run_loop(True)
        comparison = stepcompare(
            None, fast_records, floor_us=floor_us,
            slack=gate_pct / 100.0,
        )

        # alternating legacy/fast pairs, median ratio; every round
        # also fences loss equivalence
        legacy_rounds, fast_rounds = [], []
        final_params = None
        async_dir = None
        for _round in range(5):
            legacy_losses, _r, legacy_s, _p = run_loop(
                False,
                ckpt_dir=tempfile.mkdtemp(prefix="bench-ckpt-legacy-"),
            )
            async_dir = tempfile.mkdtemp(prefix="bench-ckpt-fast-")
            fast_losses, _r, fast_s, final_params = run_loop(
                True, ckpt_dir=async_dir
            )
            assert legacy_losses == fast_losses, (
                "fast loop changed the loss sequence"
            )
            legacy_rounds.append(legacy_s)
            fast_rounds.append(fast_s)
    finally:
        gc.enable()
    speedup = statistics.median(
        l / max(f, 1e-9) for l, f in zip(legacy_rounds, fast_rounds)
    )

    # snapshot-vs-donation correctness: the async arm's last save
    # (step 24) must restore to the state the loop actually reached
    params, opt_state = init_state()
    restored, restored_step = restore_checkpoint(
        async_dir, {"params": params, "opt_state": opt_state}
    )
    assert restored_step == steps, (
        f"async checkpoint stamped {restored_step}, wanted {steps}"
    )
    for want, got in zip(
        jax.tree.leaves(final_params),
        jax.tree.leaves(restored["params"]),
    ):
        np.testing.assert_array_equal(
            np.asarray(want), np.asarray(got),
            err_msg="async snapshot diverged from the run's final state",
        )

    out = {
        "train_step_steps": steps,
        "train_step_inflight": inflight,
        "train_step_saves_per_run": steps // save_every,
        "train_step_legacy_s": round(min(legacy_rounds), 4),
        "train_step_fast_s": round(min(fast_rounds), 4),
        "train_step_speedup_x": round(speedup, 3),
        "train_step_equivalent": True,  # asserted every round above
        "train_step_floor_us": round(floor_us, 1),
        "train_step_mean_us": comparison["measured_mean_us"],
        "train_step_p50_us": comparison["measured_p50_us"],
        "train_step_p95_us": comparison["measured_p95_us"],
        "train_step_over_floor_x": comparison["measured_over_floor_x"],
        "train_step_gate_pct": gate_pct,
        "train_step_gate_regression": comparison["regression"],
    }
    print(
        f"[train-step] legacy {min(legacy_rounds):.3f}s -> fast "
        f"{min(fast_rounds):.3f}s (median pairwise {speedup:.2f}x), "
        f"step mean {comparison['measured_mean_us']:.0f}us vs floor "
        f"{floor_us:.0f}us "
        f"({comparison['measured_over_floor_x']}x, gate "
        f"{gate_pct:.0f}%), losses step-equivalent",
        file=sys.stderr, flush=True,
    )
    # the tentpole's bounds, asserted (acceptance criteria):
    assert speedup > 1.0, (
        f"fast loop did not beat the legacy loop: median pairwise "
        f"ratio {speedup:.3f}"
    )
    assert comparison["regression"] is False, (
        f"measured step time regressed past the cost-model floor "
        f"(a save stopped the world, or the step slowed): {comparison}"
    )
    return out


def bench_deploy() -> dict:
    """Control-plane deploy of the single-chip MNIST service."""
    import shutil

    from dcos_commons_tpu.offer.inventory import TpuHost

    host = TpuHost(
        host_id="tpu-host-0",
        slice_id="bench-slice",
        generation="v5e",
        grid=(0, 0),
        chip_block=(1, 1),
        cpus=8.0,
        memory_mb=32768,
    )
    elapsed, completed, scheduler, agent, workdir = _run_deploy(
        os.path.join(REPO, "frameworks/jax/svc_mnist.yml"),
        {
            "JAX_FRAMEWORK_DIR": os.path.join(REPO, "frameworks/jax"),
            "TRAIN_STEPS": os.environ.get("BENCH_MNIST_STEPS", "30"),
        },
        [host],
    )
    status = scheduler.state_store.fetch_status("mnist-0-train")
    agent.shutdown()
    result = {
        "deploy_wall_clock_s": round(elapsed, 3),
        "deploy_completed": completed,
        "task_state": status.state.value if status else None,
    }
    stdout = os.path.join(workdir, "sandboxes", "mnist-0-train", "stdout")
    if os.path.exists(stdout):
        with open(stdout) as f:
            lines = f.read().strip().splitlines()
        if lines:
            result["task_log_tail"] = lines[-1]
    shutil.rmtree(workdir, ignore_errors=True)
    return result


def bench_transformer() -> dict:
    """Flagship train-step throughput on the attached chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.models import init_params, make_train_step
    from dcos_commons_tpu.utils import param_count, synthetic_tokens

    config = flagship_config()
    # batch 12 + no_remat_layers 1 (see flagship_config docstring);
    # batch 16 needs full remat
    batch = int(os.environ.get("BENCH_BATCH", "12"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    params = init_params(config, jax.random.key(0))
    optimizer = optax.adamw(3e-4)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(config, optimizer, donate=True)
    tokens, targets = synthetic_tokens(
        jax.random.key(1), batch, config.max_seq, config.vocab
    )
    t0 = time.monotonic()
    params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
    jax.block_until_ready((params, opt_state, loss))
    compile_s = time.monotonic() - t0
    # one warm step outside the window: the first post-compile
    # execution still pays one-time allocation
    params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)
    t0 = time.monotonic()
    for _ in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
    # block on the WHOLE output tree: waiting only on the scalar loss
    # under-counts the step time (the param update may still run)
    jax.block_until_ready((params, opt_state, loss))
    dt = time.monotonic() - t0
    tokens_per_s = batch * config.max_seq * steps / dt
    n_params = param_count(params)
    flops_per_token = 6 * n_params  # fwd+bwd dense estimate
    achieved_tflops = tokens_per_s * flops_per_token / 1e12
    device = jax.devices()[0]
    peak_tflops = _peak_bf16_tflops(device)
    return {
        "platform": device.platform,
        "device_kind": getattr(device, "device_kind", "?"),
        "transformer_params_m": round(n_params / 1e6, 1),
        "compile_s": round(compile_s, 2),
        "tokens_per_s": round(tokens_per_s, 1),
        "achieved_tflops": round(achieved_tflops, 2),
        "mfu": round(achieved_tflops / peak_tflops, 4),
        "final_loss": round(float(loss), 4),
    }


def bench_profile() -> dict:
    """Per-section decomposition of the flagship train step (VERDICT
    r2 item 2): where the non-MFU time goes, with the evidence that
    each remaining point is structural on this chip.

    Sections, each timed around ``block_until_ready``:
      * attention kernel fwd / fwd+bwd at flagship shapes
      * trunk forward vs the dense-matmul roofline
      * full step, from which the backward+recompute share follows
        (bench_mfu_frontier sweeps the remat frontier).
    None of the shares is measured on the current toolchain yet.
    """
    import gc

    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.models import init_params, make_train_step
    from dcos_commons_tpu.models import transformer as tmod
    from dcos_commons_tpu.ops.attention import flash_attention
    from dcos_commons_tpu.utils import param_count, synthetic_tokens

    def timeit(fn, *args, iters=8):
        jax.block_until_ready(fn(*args))
        t0 = time.monotonic()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / iters

    config = flagship_config()
    # profile at the SAME frontier-optimal point the headline trains
    # (batch 12, no_remat_layers 1): batch 16 with a stored layer is
    # past the HBM boundary
    batch = 12
    out = {}

    # attention kernel at flagship shapes, CHAINED inside one jit
    # (like the matmul rooflines) so per-call dispatch stays out of a
    # kernel time of a few milliseconds
    from jax import lax as _lax

    chain = 8
    bhsd = (batch, config.n_heads, config.max_seq, config.head_dim)
    q = jax.random.normal(jax.random.key(0), bhsd, jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), bhsd, jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), bhsd, jnp.bfloat16)
    attn_flops = 2 * 2 * batch * config.n_heads * config.max_seq ** 2 \
        * config.head_dim / 2

    def one(qq, kk, vv):
        return flash_attention(
            qq, kk, vv,
            block_q=config.attn_block_q, block_k=config.attn_block_k,
        )

    # k/v must be ARGUMENTS: closing over the concrete arrays embeds
    # 268MB of constants into the program
    fwd = jax.jit(lambda q, k, v: _lax.scan(
        lambda qq, _: (one(qq, k, v), None), q, None, length=chain
    )[0])
    t_attn = timeit(fwd, q, k, v, iters=3) / chain
    # all three grads: dq chains through the scan carry, dk/dv
    # accumulate across iterations — dropping them would prune half
    # the backward kernels and understate the training cost
    grad = jax.jit(jax.grad(lambda q, k, v: _lax.scan(
        lambda qq, _: (one(qq, k, v), None), q, None, length=chain
    )[0].astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    t_attn_fb = timeit(grad, q, k, v, iters=3) / chain
    out["profile_attn_fwd_ms"] = round(t_attn * 1e3, 2)
    out["profile_attn_fwd_tflops"] = round(attn_flops / t_attn / 1e12, 1)
    out["profile_attn_fwd_bwd_ms"] = round(t_attn_fb * 1e3, 2)
    del q, k, v
    gc.collect()

    # trunk forward + fwd-with-loss
    params = init_params(config, jax.random.key(0))
    tokens, targets = synthetic_tokens(
        jax.random.key(1), batch, config.max_seq, config.vocab
    )
    trunk = jax.jit(lambda p, t: tmod._trunk(config, p, t)[0])
    t_trunk = timeit(trunk, params, tokens)
    loss_fn = jax.jit(lambda p, t, tg: tmod.loss_fn(config, p, t, tg))
    t_fwd = timeit(loss_fn, params, tokens, targets)
    out["profile_trunk_fwd_ms"] = round(t_trunk * 1e3, 1)
    out["profile_loss_section_ms"] = round((t_fwd - t_trunk) * 1e3, 1)

    # full step (donated) + derived shares
    optimizer = optax.adamw(3e-4)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(config, optimizer, donate=True)
    p, o = params, opt_state
    p, o, loss = step_fn(p, o, tokens, targets)
    sync(loss)
    t0 = time.monotonic()
    iters = 10
    for _ in range(iters):
        p, o, loss = step_fn(p, o, tokens, targets)
    sync(p)
    t_step = (time.monotonic() - t0) / iters
    n_params = param_count(p)
    peak = _peak_bf16_tflops(jax.devices()[0])
    attn_per_step = config.n_layers * (2 * t_attn + (t_attn_fb - t_attn))
    out["profile_step_ms"] = round(t_step * 1e3, 1)
    out["profile_bwd_and_recompute_ms"] = round((t_step - t_fwd) * 1e3, 1)
    out["profile_attn_per_step_ms"] = round(attn_per_step * 1e3, 1)
    out["profile_attn_share"] = round(attn_per_step / t_step, 3)
    out["profile_recompute_share_est"] = round(t_trunk / t_step, 3)
    dense_fwd_ideal_s = 2 * n_params * batch * config.max_seq / (
        peak * 1e12
    )
    out["profile_dense_fwd_efficiency"] = round(
        dense_fwd_ideal_s
        / max(t_trunk - config.n_layers * t_attn, 1e-9),
        3,
    )
    del p, o, params, opt_state
    gc.collect()
    return out


def _timed_median_steps(gen, params, prompt, new_tokens,
                        warmups: int = 2, iters: int = 3):
    """(compile_s, median steps/s).  Warm executions run before the
    timed ones, and every run ends in a device->host read of a token
    that depends on the whole generation."""
    import statistics

    import jax

    t0 = time.monotonic()
    out = gen(params, prompt)
    float(jax.device_get(out[0, 0]))
    compile_s = time.monotonic() - t0
    for _ in range(warmups - 1):
        out = gen(params, prompt)
        float(jax.device_get(out[0, -1]))
    rates = []
    for _ in range(iters):
        t0 = time.monotonic()
        out = gen(params, prompt)
        float(jax.device_get(out[0, -1]))
        rates.append(new_tokens / (time.monotonic() - t0))
    return compile_s, statistics.median(rates)


def _bench_decode_impl(
    prefix: str, kv_dtype: str = "native",
    quantize_weights: bool = False, bf16_roofline_key: str = "",
) -> dict:
    """Shared scaffolding for the three decode benches (bf16 /
    int8-KV / int8-weights+KV): one flagship generate jitted over the
    requested quantization, timed by _timed_median_steps, with the
    HBM stream roofline for the AS-STORED bytes.  All three run in a
    SUBPROCESS with a hard timeout from main(): the remote compile
    helper has been observed to wedge on this program shape, and a
    hung section must never stall the whole bench."""
    import jax

    from dcos_commons_tpu.models import generate, init_params
    from dcos_commons_tpu.utils import synthetic_tokens

    config = flagship_config()
    batch = int(os.environ.get("BENCH_DECODE_BATCH", "16"))
    new_tokens = int(os.environ.get("BENCH_DECODE_TOKENS", "64"))
    prompt_len, max_len = 128, 512
    params = init_params(config, jax.random.key(0))
    hbm = 819.0e9  # v5e
    out = {}
    if bf16_roofline_key:
        # the comparison column, computed on the UNQUANTIZED tree
        out[bf16_roofline_key] = round(
            hbm / _decode_stream_bytes(config, params, batch, max_len,
                                       int8=False), 1
        )
    if quantize_weights:
        from dcos_commons_tpu.models import quantize_params_int8

        qparams = jax.jit(quantize_params_int8)(params)
        jax.block_until_ready(qparams)
        del params  # both trees live would double the HBM footprint
        params = qparams
    prompt, _ = synthetic_tokens(
        jax.random.key(1), batch, prompt_len, config.vocab
    )
    gen = jax.jit(lambda p, t: generate(
        config, p, t, max_new_tokens=new_tokens, max_len=max_len,
        kv_dtype=kv_dtype,
    ))
    compile_s, steps_per_s = _timed_median_steps(
        gen, params, prompt, new_tokens
    )
    out.update({
        f"{prefix}_batch": batch,
        f"{prefix}_compile_s": round(compile_s, 1),
        f"{prefix}_steps_per_s": round(steps_per_s, 1),
        f"{prefix}_tokens_per_s": round(batch * steps_per_s, 1),
        f"{prefix}_stream_roofline_steps_per_s": round(
            hbm / _decode_stream_bytes(config, params, batch, max_len,
                                       int8=(kv_dtype == "int8")), 1
        ),
    })
    return out


def bench_decode() -> dict:
    """Serving throughput: KV-cache autoregressive generate on the
    flagship (models/decode.py), one device dispatch for the whole
    continuation (lax.scan over steps).  Decode is HBM-bound — each
    step streams the full 1.7 GB bf16 parameter set — so the extras
    report the HBM roofline next to the measured rate."""
    return _bench_decode_impl("decode")


def _decode_stream_bytes(config, params, batch, max_len, int8):
    """Bytes decode streams per step: the full parameter set plus the
    whole KV cache (the dense einsum reads every slot of the static
    cache).  The honest roofline divides HBM bandwidth by THIS, not
    params alone."""
    from dcos_commons_tpu.utils import param_bytes

    cache_elems = (
        config.n_layers * batch * max_len * config.n_kv_heads
        * config.head_dim * 2  # k and v
    )
    if int8:
        scale_bytes = (
            config.n_layers * batch * max_len * config.n_kv_heads * 2 * 4
        )
        cache_bytes = cache_elems * 1 + scale_bytes
    else:
        cache_bytes = cache_elems * 2  # bf16
    return param_bytes(params) + cache_bytes


def bench_decode_int8() -> dict:
    """int8 KV cache decode: halving the cache bytes raises the
    HBM-bound ceiling, and the freed HBM admits DOUBLE the batch the
    bf16 cache could hold."""
    return _bench_decode_impl(
        "decode_int8", kv_dtype="int8",
        bf16_roofline_key="decode_bf16_stream_roofline_steps_per_s",
    )


def bench_decode_w8() -> dict:
    """int8 WEIGHTS + int8 KV cache — the full serving quantization
    stack (models/quantize.py): decode streams ~half the weight bytes
    AND half the cache bytes per step, roughly doubling the HBM-bound
    ceiling (the roofline column).  The weight-bytes share is largest
    at SMALL batch (weights dominate per-step bytes there); at b64 the
    cache and attention compute dominate.  Tokens/s: not measured on
    the current toolchain."""
    return _bench_decode_impl(
        "decode_w8", kv_dtype="int8", quantize_weights=True,
    )


def bench_serve() -> dict:
    """The FULL serving path on chip: deploy svc_serve.yml through the
    control plane, then measure POST /generate tok/s and p50/p99
    latency through the HTTP hop — the number an operator of the
    serving pod actually gets."""
    import shutil
    import statistics
    import urllib.request

    from dcos_commons_tpu.offer.inventory import TpuHost

    host = TpuHost(
        host_id="tpu-serve-0",
        hostname="127.0.0.1",  # endpoint listing must be dialable
        slice_id="bench-slice",
        generation="v5e",
        grid=(0, 0),
        chip_block=(1, 1),
        cpus=8.0,
        memory_mb=32768,
        # port 10000 on this box is held by a resident service; the
        # serve task REALLY binds its allocated port
        ports=((23400, 23500),),
    )
    n_layers = os.environ.get("BENCH_SERVE_LAYERS", "12")
    d_model = os.environ.get("BENCH_SERVE_DMODEL", "2048")
    new_tokens = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", "32"))
    serve_batch = int(os.environ.get("BENCH_SERVE_BATCH", "8"))
    elapsed, completed, scheduler, agent, workdir = _run_deploy(
        os.path.join(REPO, "frameworks/jax/svc_serve.yml"),
        {
            "JAX_FRAMEWORK_DIR": os.path.join(REPO, "frameworks/jax"),
            "VOCAB": "32768", "D_MODEL": d_model, "N_LAYERS": n_layers,
            "SEQ_LEN": "256", "MAX_LEN": "256",
            "MAX_NEW_TOKENS": str(new_tokens),
            # batched serving: rows per request share each decode step
            "TASKCFG_ALL_SERVE_BATCH": str(serve_batch),
            "TASKCFG_ALL_KV_DTYPE": os.environ.get(
                "BENCH_SERVE_KV_DTYPE", "int8"
            ),
            # default stays native; flip via BENCH_SERVE_WEIGHT_DTYPE
            "TASKCFG_ALL_WEIGHT_DTYPE": os.environ.get(
                "BENCH_SERVE_WEIGHT_DTYPE", "native"
            ),
        },
        [host],
        budget_s=480.0,
    )
    result = {
        "serve_deploy_wall_clock_s": round(elapsed, 1),
        "serve_deploy_completed": completed,
    }
    try:
        if not completed:
            return result
        # endpoint discovery exactly as a client would
        from dcos_commons_tpu.http.api import SchedulerApi

        code, body = SchedulerApi(scheduler).get_endpoint("http")
        address = body["address"][0]
        url = f"http://{address}/generate"
        prompt = list(range(2, 34))  # 32 tokens

        def one_request(rows):
            payload = json.dumps({
                "tokens": [prompt] * rows, "max_new_tokens": new_tokens,
            }).encode()
            req = urllib.request.Request(
                url, data=payload,
                headers={"Content-Type": "application/json"},
            )
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=120) as resp:
                out = json.loads(resp.read())
            latency = time.monotonic() - t0
            n = sum(len(row) for row in out["tokens"])
            return latency, n

        one_request(1)  # warm the HTTP + dispatch path
        # interactive latency: single-prompt requests (the compiled
        # batch is padded, so this IS the per-request floor)
        latencies = []
        requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "20"))
        for _ in range(requests):
            latency, _n = one_request(1)
            latencies.append(latency)
        # throughput: full-batch requests
        tokens_total = 0
        t_start = time.monotonic()
        for _ in range(requests):
            _latency, n = one_request(serve_batch)
            tokens_total += n
        wall = time.monotonic() - t_start
        # concurrent single-prompt CLIENTS: the worker's engine
        # admits them into shared pool decode steps — the multi-client
        # number, vs the single-client full-batch number above
        import concurrent.futures as _fut

        conc_total = (requests // serve_batch + 1) * serve_batch
        conc_tokens = 0
        t_conc = time.monotonic()
        # ONE map, no per-round barrier: max_workers bounds the
        # in-flight clients and the worker's batcher does the merging
        with _fut.ThreadPoolExecutor(max_workers=serve_batch) as pool:
            for _latency, n in pool.map(one_request, [1] * conc_total):
                conc_tokens += n
        conc_wall = time.monotonic() - t_conc
        # MIXED-length concurrent clients: realistic traffic has no
        # shared prompt length — per-slot true_len admission must hold
        # the homogeneous concurrent number (>= 80% is the bar)
        def one_mixed_request(i):
            rows = [list(range(2, 2 + 8 + (i * 7) % 48))]
            payload = json.dumps({
                "tokens": rows, "max_new_tokens": new_tokens,
            }).encode()
            req = urllib.request.Request(
                url, data=payload,
                headers={"Content-Type": "application/json"},
            )
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=120) as resp:
                out = json.loads(resp.read())
            return time.monotonic() - t0, sum(
                len(row) for row in out["tokens"]
            )

        mixed_tokens = 0
        t_mixed = time.monotonic()
        with _fut.ThreadPoolExecutor(max_workers=serve_batch) as pool:
            for _latency, n in pool.map(
                one_mixed_request, range(conc_total)
            ):
                mixed_tokens += n
        mixed_wall = time.monotonic() - t_mixed
        latencies.sort()
        result.update({
            "serve_requests": requests,
            "serve_batch": serve_batch,
            "serve_tokens_per_s": round(tokens_total / wall, 1),
            "serve_concurrent_clients_tokens_per_s": round(
                conc_tokens / conc_wall, 1
            ),
            "serve_mixed_len_clients_tokens_per_s": round(
                mixed_tokens / mixed_wall, 1
            ),
            "serve_p50_ms": round(
                statistics.median(latencies) * 1e3, 1
            ),
            "serve_p99_ms": round(
                latencies[
                    min(len(latencies) - 1,
                        max(0, math.ceil(0.99 * len(latencies)) - 1))
                ] * 1e3,
                1,
            ),
        })
        return result
    finally:
        for task_id in list(agent.active_task_ids()):
            agent.kill(task_id, grace_period_s=0.0)
        deadline = time.monotonic() + 15
        while agent.active_task_ids() and time.monotonic() < deadline:
            agent.poll()
            time.sleep(0.2)
        agent.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)


def moe_flagship_config():
    """The MoE flagship variant sized for the 16 GB chip: Adam keeps
    12 bytes/param (bf16 p+g, f32 m+v), so ~1B params is the ceiling —
    4 experts at d_ff 2048 lands the SAME total parameter count as the
    dense flagship while activating half the FFN weight per token
    (top-2 of 4)."""
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab=32768,
        d_model=2048,
        n_layers=12,
        n_heads=16,
        n_kv_heads=16,
        d_ff=int(os.environ.get("BENCH_MOE_DFF", "2048")),
        max_seq=2048,
        dtype=jnp.bfloat16,
        remat=True,
        attn_block_q=512,
        attn_block_k=512,
        n_experts=int(os.environ.get("BENCH_MOE_EXPERTS", "4")),
        moe_top_k=int(os.environ.get("BENCH_MOE_TOPK", "2")),
        moe_capacity_factor=float(
            os.environ.get("BENCH_MOE_CAPACITY", "1.25")
        ),
        moe_impl=os.environ.get("BENCH_MOE_IMPL", "onehot"),
    )


def bench_moe() -> dict:
    """MoE flagship on-chip numbers (VERDICT r3 #5): train-step MFU
    (counting ACTIVATED FLOPs — top-k of the expert weights — the
    honest MoE utilisation number) and KV-cache decode tok/s.  Run in
    a subprocess: same wedge-prone shapes as the dense flagship."""
    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.models import (
        generate,
        init_params,
        make_train_step,
    )
    from dcos_commons_tpu.utils import param_count, synthetic_tokens

    config = moe_flagship_config()
    batch = int(os.environ.get("BENCH_MOE_BATCH", "8"))
    steps = int(os.environ.get("BENCH_MOE_STEPS", "20"))
    params = init_params(config, jax.random.key(0))
    optimizer = optax.adamw(3e-4)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(config, optimizer, donate=True)
    tokens, targets = synthetic_tokens(
        jax.random.key(1), batch, config.max_seq, config.vocab
    )
    t0 = time.monotonic()
    params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
    jax.block_until_ready((params, opt_state, loss))
    float(jax.device_get(jnp.sum(loss)))
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
    jax.block_until_ready((params, opt_state, loss))
    dt = time.monotonic() - t0
    tokens_per_s = batch * config.max_seq * steps / dt

    # MoE MFU counts ACTIVATED parameters (top_k of n_experts expert
    # FFNs per token) with the same 6*N fwd+bwd convention the dense
    # bench uses — inactive expert weights do no useful FLOPs
    d, f = config.d_model, config.d_ff
    inactive_ffn = (
        config.n_layers * (config.n_experts - config.moe_top_k)
        * 3 * d * f
    )
    n_active = param_count(params) - inactive_ffn
    flops_per_token = 6 * n_active
    peak = _peak_bf16_tflops(jax.devices()[0]) * 1e12
    mfu = tokens_per_s * flops_per_token / peak

    result = {
        "moe_batch": batch,
        "moe_experts": config.n_experts,
        "moe_top_k": config.moe_top_k,
        "moe_capacity_factor": config.moe_capacity_factor,
        "moe_params_m": round(param_count(params) / 1e6),
        "moe_compile_s": round(compile_s, 1),
        "moe_train_tokens_per_s": round(tokens_per_s),
        "moe_mfu": round(mfu, 3),
        # suspects for the activated-MFU gap to the dense flagship
        # (ROADMAP S8; not measured on the current toolchain): x1.25
        # capacity waste on expert FLOPs, small per-expert matmul
        # tiles ([~640,2048]x[2048,2048] vs dense
        # [16k,2048]x[2048,8192]), and routing's VPU work that
        # activated FLOPs never count.
    }

    # serving: drop-free KV-cache decode
    del opt_state
    dec_batch = int(os.environ.get("BENCH_MOE_DECODE_BATCH", "16"))
    new_tokens = 64
    prompt, _ = synthetic_tokens(
        jax.random.key(2), dec_batch, 128, config.vocab
    )
    gen = jax.jit(lambda p, t: generate(
        config, p, t, max_new_tokens=new_tokens, max_len=512
    ))
    _compile_s, steps_per_s = _timed_median_steps(
        gen, params, prompt, new_tokens
    )
    result["moe_decode_tokens_per_s"] = round(
        dec_batch * steps_per_s, 1
    )
    # the quantized serving stack on MoE: int8 EXPERT weights (ALL
    # experts stream from HBM each step regardless of routing, so the
    # byte saving is over the full expert stack) + int8 KV
    from dcos_commons_tpu.models import quantize_params_int8

    qparams = jax.jit(quantize_params_int8)(params)
    jax.block_until_ready(qparams)
    del params
    gen_q = jax.jit(lambda p, t: generate(
        config, p, t, max_new_tokens=new_tokens, max_len=512,
        kv_dtype="int8",
    ))
    _compile_s, q_steps_per_s = _timed_median_steps(
        gen_q, qparams, prompt, new_tokens
    )
    result["moe_decode_w8_tokens_per_s"] = round(
        dec_batch * q_steps_per_s, 1
    )
    return result


def _peak_bf16_tflops(device) -> float:
    """Per-chip bf16 peak (TFLOP/s, Google Cloud TPU documentation) by
    device kind.  A device that is not in the table is an error, not a
    default: an MFU over a guessed peak is not a measurement."""
    kind = getattr(device, "device_kind", "").lower()
    for token, peak in (
        ("v6e", 918.0), ("v6 lite", 918.0), ("v5p", 459.0),
        ("v5e", 197.0), ("v5 lite", 197.0), ("v4", 275.0),
    ):
        if token in kind:
            return peak
    raise ValueError(
        f"no bf16 peak known for device kind {kind!r} (platform "
        f"{device.platform!r}): add it to _peak_bf16_tflops with its "
        "source before reporting a utilization"
    )


def bench_rooflines() -> dict:
    """Chip rooflines + (multi-chip only) ICI collective bandwidth —
    the BASELINE north-star measurement path.  On the single bench
    chip the collective section reports the rooflines the multi-chip
    GB/s numbers will sit under."""
    import jax

    from dcos_commons_tpu.parallel.collectives import (
        collective_bandwidth,
        single_chip_rooflines,
    )

    devices = jax.devices()
    out = {
        "roofline_platform": devices[0].platform,
        "roofline_device_kind": devices[0].device_kind,
        "roofline_devices": len(devices),
    }
    out.update(single_chip_rooflines(payload_mb=128.0, iters=10))
    if len(devices) >= 2:
        from jax.sharding import Mesh

        mesh = Mesh(devices, ("ici",))
        for key, value in collective_bandwidth(
            mesh, "ici", payload_mb=32.0, iters=10
        ).items():
            out[f"ici_{key}"] = value
    return out


def _run_subprocess_section(
    fn_name: str, timeout_s: float,
    env: dict = None, rename: dict = None,
) -> dict:
    """Run one bench section in a child process with a hard timeout so
    a wedged XLA compile cannot stall the whole bench run.

    Output goes to a FILE (not a pipe) and the child runs in its own
    session: on timeout the whole process GROUP is killed — a wedged
    grandchild holding an inherited pipe FD would otherwise block the
    read forever.

    ``env`` overlays the child's environment (parameterized reruns);
    ``rename`` remaps result keys (None value = drop the key) so one
    section can report under several names."""
    import signal
    import subprocess
    import tempfile

    code = (
        "import json, sys; sys.path.insert(0, %r); import bench; "
        "print('BENCHJSON ' + json.dumps(getattr(bench, %r)()))"
        % (REPO, fn_name)
    )
    child_env = dict(os.environ)
    child_env.update(env or {})
    with tempfile.TemporaryFile(mode="w+") as out:
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=out,
            stderr=subprocess.STDOUT,
            start_new_session=True,
            text=True,
            env=child_env,
        )
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait(timeout=10)
            raise RuntimeError(
                f"{fn_name} exceeded {timeout_s}s; process group killed"
            )
        out.seek(0)
        text = out.read()
    for line in text.splitlines():
        if line.startswith("BENCHJSON "):
            result = json.loads(line[len("BENCHJSON "):])
            if rename:
                remapped = {}
                for key, value in result.items():
                    target = rename.get(key, key)
                    if target is not None:
                        remapped[target] = value
                result = remapped
            return result
    raise RuntimeError(
        f"{fn_name} subprocess rc={rc}: {text[-180:]}"
    )



def _mark(tag, _state={"t": None}):  # noqa — the default IS the state
    """Per-section wall-clock to stderr (stdout carries ONLY the JSON
    line); the driver's bench timeout budget is finite, so the hog
    must be findable from a single run's log."""
    now = time.monotonic()
    if _state["t"] is not None:
        print(f"[bench-timing] {tag}: {now - _state['t']:.1f}s",
              file=sys.stderr, flush=True)
    _state["t"] = now


def main() -> None:
    """The parent process never initialises a JAX backend: a chip
    belongs to one process at a time, so every section that computes
    on it is a child (_run_subprocess_section, or a task the agent
    launches) that has exited before the next one starts.  A chip
    section that fails is recorded in the JSON line AND fails the run
    — a green exit with a zeroed number is how a broken chip path
    hides."""
    extras = {}
    chip_failures = []

    def chip_section(tag, fn_name, **kwargs):
        try:
            extras.update(_run_subprocess_section(fn_name, **kwargs))
        except Exception as e:
            extras[f"{tag}_error"] = repr(e)[:200]
            chip_failures.append(tag)
        _mark(tag)

    _mark(None)
    try:
        extras.update(bench_helloworld())
    except Exception as e:
        extras["helloworld_error"] = repr(e)[:200]
    _mark("helloworld")
    try:
        extras.update(bench_scheduler_scale())
    except Exception as e:
        extras["sched_scale_error"] = repr(e)[:200]
    _mark("sched_scale")
    try:
        extras.update(bench_offer_cycle())
    except Exception as e:
        extras["offer_cycle_error"] = repr(e)[:200]
    _mark("offer_cycle")
    # fleet-scale offer cycle (ISSUE 9): incremental dirty-host
    # evaluation + indexed placement at 1k/10k hosts vs full rebuild
    try:
        extras.update(bench_fleet_scale())
    except Exception as e:
        extras["fleet_scale_error"] = repr(e)[:200]
    _mark("fleet_scale")
    try:
        extras.update(bench_trace_overhead())
    except Exception as e:
        extras["trace_overhead_error"] = repr(e)[:200]
    _mark("trace_overhead")
    # fleet health plane (ISSUE 10): detectors + journal overhead on
    # the trace-bench scenario, fenced at <5% of cycle cost
    try:
        extras.update(bench_health_overhead())
    except Exception as e:
        extras["health_overhead_error"] = repr(e)[:200]
    _mark("health_overhead")
    # HA failover latency (ISSUE 8): standby takeover during a 64-host
    # deploy — lease wait / rebuild / first-working-cycle breakdown
    try:
        extras.update(bench_failover())
    except Exception as e:
        extras["failover_error"] = repr(e)[:200]
    _mark("failover")
    # preemption -> gang recovery latency (ISSUE 13): single gang-host
    # kill to training-resumed, and a 4-kill storm (incl. mid-recovery
    # and span-boundary kills) to convergence, invariants asserted
    try:
        extras.update(bench_preemption_recovery())
    except Exception as e:
        extras["preemption_error"] = repr(e)[:200]
    _mark("preemption_recovery")
    # multi-slice gang lifecycle (ISSUE 20): 2-slice deploy on a 10k-
    # host world, whole-slice preemption -> time-to-resumed-shrunken,
    # capacity return -> time-to-regrown, journal verbs asserted
    try:
        extras.update(bench_multislice())
    except Exception as e:
        extras["multislice_error"] = repr(e)[:200]
    _mark("multislice")
    # closed health->action loop (ISSUE 15): seeded SLO breach ->
    # time-to-scale-plan / time-to-recovered-SLO, quiet -> scale-in
    # with the pre-kill drain, zero flap asserted over the run
    try:
        extras.update(bench_slo_recovery())
    except Exception as e:
        extras["slo_recovery_error"] = repr(e)[:200]
    _mark("slo_recovery")
    # CPU-runnable routing-tier trend (ISSUE 12): the multi-pod front
    # door's 1/2/4-pod open-loop sweep, affinity-vs-spray prefix hit
    # rate, and the mid-sweep drain round — jax-free, subprocess for
    # the hard timeout
    try:
        extras.update(_run_subprocess_section(
            "bench_router_scale", timeout_s=600,
            env={"JAX_PLATFORMS": "cpu"},
        ))
    except Exception as e:
        extras["router_scale_error"] = repr(e)[:200]
    _mark("router_scale")
    # CPU-runnable disaggregated-serving trend (ISSUE 16): unified vs
    # prefill/decode split under a long-prefill-heavy mix, drain with
    # vs without live KV migration, and one mid-generation move over
    # the modeled DCN — jax-free, subprocess for the hard timeout
    try:
        extras.update(_run_subprocess_section(
            "bench_disagg", timeout_s=600,
            env={"JAX_PLATFORMS": "cpu"},
        ))
    except Exception as e:
        extras["disagg_error"] = repr(e)[:200]
    _mark("disagg")
    # CPU-runnable training step-loop trend (ISSUE 7): the worker fast
    # path (donation + in-flight window + async fenced checkpointing)
    # vs the loop it replaced, plus the cost-model step-time gate
    try:
        extras.update(_run_subprocess_section(
            "bench_train_step", timeout_s=600,
            env={"JAX_PLATFORMS": "cpu"},
        ))
    except Exception as e:
        extras["train_step_error"] = repr(e)[:200]
    _mark("train_step")
    # persistent XLA compilation cache for the deploy's train task.
    # The task finds it where JAX_COMPILATION_CACHE_DIR says, else in
    # the checkout's fixed directory (utils/compile_cache.py); the
    # bench sets no path — a cache that moves never hits.  Three
    # measurements:
    #   true cold — the task's cache switched OFF (jax's own
    #     JAX_ENABLE_COMPILATION_CACHE, inherited through the agent)
    #   provisioned — after the provisioning step (agent
    #     --provision-cmd running warm_cache.py) seeded the cache;
    #     this is what a first deploy on a properly provisioned host
    #     costs, and the HEADLINE metric
    #   warm — repeat deploy on the same host
    import subprocess as _sp

    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    try:
        true_cold = bench_deploy()
        extras["deploy_true_cold_wall_clock_s"] = \
            true_cold["deploy_wall_clock_s"]
        extras["deploy_true_cold_completed"] = \
            true_cold["deploy_completed"]
        if not true_cold["deploy_completed"]:
            chip_failures.append("deploy_true_cold")
    except Exception as e:
        extras["deploy_true_cold_error"] = repr(e)[:200]
        chip_failures.append("deploy_true_cold")
    finally:
        del os.environ["JAX_ENABLE_COMPILATION_CACHE"]
    _mark("deploy_true_cold")
    provisioned = False
    try:
        t0 = time.monotonic()
        # route the child's prints to STDERR: bench stdout must carry
        # ONLY the one JSON line (the child inherits stdout otherwise)
        rc = _sp.run(
            [sys.executable,
             os.path.join(REPO, "frameworks/jax/warm_cache.py")],
            timeout=300, stdout=sys.stderr, stderr=sys.stderr,
        ).returncode
        extras["provision_warm_cache_s"] = round(
            time.monotonic() - t0, 1
        )
        extras["provision_rc"] = rc
        provisioned = rc == 0
    except Exception as e:
        extras["provision_error"] = repr(e)[:200]
    _mark("provision")
    # measurement honesty: the headline deploy is only "provisioned"
    # when the seeding actually succeeded
    extras["deploy_provisioned"] = provisioned
    deploy = bench_deploy()
    extras.update(deploy)
    if not deploy["deploy_completed"]:
        chip_failures.append("deploy")
    try:
        warm = bench_deploy()
        extras["deploy_warm_wall_clock_s"] = warm["deploy_wall_clock_s"]
        extras["deploy_warm_completed"] = warm["deploy_completed"]
        if not warm["deploy_completed"]:
            chip_failures.append("deploy_warm")
    except Exception as e:
        extras["deploy_warm_error"] = repr(e)[:200]
        chip_failures.append("deploy_warm")
    _mark("deploys_provisioned_and_warm")
    chip_section("roofline", "bench_rooflines", timeout_s=600)
    chip_section("transformer", "bench_transformer", timeout_s=900)
    chip_section("profile", "bench_profile", timeout_s=900)
    # the (batch, no_remat_layers) frontier — each point is a fresh
    # compile with an OOM boundary
    chip_section("frontier", "bench_mfu_frontier", timeout_s=1200)
    chip_section("decode", "bench_decode", timeout_s=420)
    # tokens/s scales with batch until HBM bites; bf16 tops out where
    # the cache bytes do, int8 halves the cache and keeps scaling
    chip_section(
        "decode_b64", "bench_decode", timeout_s=420,
        env={"BENCH_DECODE_BATCH": "64"},
        rename={
            "decode_batch": "decode_b64_batch",
            "decode_compile_s": None,
            "decode_steps_per_s": "decode_b64_steps_per_s",
            "decode_tokens_per_s": "decode_b64_tokens_per_s",
            "decode_stream_roofline_steps_per_s": None,
        },
    )
    chip_section("decode_int8", "bench_decode_int8", timeout_s=420)
    chip_section(
        "decode_int8_b64", "bench_decode_int8", timeout_s=480,
        env={"BENCH_DECODE_BATCH": "64"},
        rename={
            "decode_int8_batch": "decode_int8_b64_batch",
            "decode_int8_compile_s": None,
            "decode_int8_steps_per_s": "decode_int8_b64_steps_per_s",
            "decode_int8_tokens_per_s": "decode_int8_b64_tokens_per_s",
            "decode_int8_stream_roofline_steps_per_s":
                "decode_int8_b64_stream_roofline_steps_per_s",
            "decode_bf16_stream_roofline_steps_per_s": None,
        },
    )
    # int8 weights + int8 cache: the full serving quantization stack,
    # at the small batch where weight bytes dominate and at b64
    chip_section("decode_w8", "bench_decode_w8", timeout_s=480)
    chip_section(
        "decode_w8_b64", "bench_decode_w8", timeout_s=540,
        env={"BENCH_DECODE_BATCH": "64"},
        rename={
            "decode_w8_batch": "decode_w8_b64_batch",
            "decode_w8_compile_s": None,
            "decode_w8_steps_per_s": "decode_w8_b64_steps_per_s",
            "decode_w8_tokens_per_s": "decode_w8_b64_tokens_per_s",
            "decode_w8_stream_roofline_steps_per_s":
                "decode_w8_b64_stream_roofline_steps_per_s",
        },
    )
    # the serve pod is a grandchild (agent-launched task) of a child
    # that itself stays off JAX: still one process on the chip
    chip_section("serve", "bench_serve", timeout_s=540)
    chip_section("moe", "bench_moe", timeout_s=540)
    # 8-expert point: same total params at finer expert granularity
    # (8 x d_ff 1024 top-2); the 4-expert config stays the headline
    chip_section(
        "moe8", "bench_moe", timeout_s=540,
        env={
            "BENCH_MOE_EXPERTS": "8", "BENCH_MOE_DFF": "1024",
            "BENCH_MOE_DECODE_BATCH": "16",
        },
        rename={
            "moe_batch": None,
            "moe_experts": "moe8_experts",
            "moe_top_k": None,
            "moe_capacity_factor": None,
            "moe_params_m": "moe8_params_m",
            "moe_compile_s": "moe8_compile_s",
            "moe_train_tokens_per_s": "moe8_train_tokens_per_s",
            "moe_mfu": "moe8_mfu",
            "moe_decode_tokens_per_s": "moe8_decode_tokens_per_s",
            "moe_decode_w8_tokens_per_s": "moe8_decode_w8_tokens_per_s",
        },
    )
    try:
        # analyzer-coverage trend keys: how much of the env/config
        # contract surface configcheck's flow graph tracks (the
        # findings gate itself lives in tests/test_lint_gate.py)
        from dcos_commons_tpu.analysis import configcheck

        config_result = configcheck.analyze_all(
            os.path.dirname(os.path.abspath(__file__))
        )
        extras["config_env_vars"] = len(config_result.env_vars)
        extras["config_flows"] = len(config_result.flows)
        extras["config_findings"] = len(config_result.findings)
        extras["config_suppressed"] = len(config_result.suppressed)
    except Exception as e:
        extras["config_error"] = repr(e)[:200]
    _mark("configcheck")
    try:
        # durability-surface trend keys: how many persistence
        # boundaries durcheck tracks for the auto-derived chaos
        # matrix (the findings gate lives in tests/test_lint_gate.py)
        from dcos_commons_tpu.analysis import durcheck

        dur_result = durcheck.analyze_tree(
            os.path.dirname(os.path.abspath(__file__))
        )
        extras["dur_persistence_points"] = len(
            dur_result.persistence_points
        )
        extras["dur_findings"] = len(dur_result.findings)
        extras["dur_suppressed"] = len(dur_result.suppressed)
        per_kind: dict = {}
        for point in dur_result.persistence_points:
            per_kind[point.kind] = per_kind.get(point.kind, 0) + 1
        extras["dur_per_kind"] = per_kind
    except Exception as e:
        extras["dur_error"] = repr(e)[:200]
    _mark("durcheck")
    value = deploy["deploy_wall_clock_s"]
    print(
        json.dumps(
            {
                "metric": "jax_mnist_deploy_plan_wall_clock",
                "value": value,
                "unit": "s",
                "vs_baseline": round(DEPLOY_BUDGET_S / max(value, 1e-9), 3)
                if deploy["deploy_completed"]
                else 0.0,
                "extras": extras,
            },
            sort_keys=True,
        )
    )
    if chip_failures:
        print(
            f"bench: chip sections failed: {', '.join(chip_failures)}",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
