"""The closed health->action loop (ISSUE 15): decision-rule
properties, the quiet-pod low-watermark detector, and the acceptance
scenario end to end — a seeded serving SLO breach under load
synthesizes a scale-out plan that deploys through the normal offer
cycle, the SLO recovers, a later sustained quiet period synthesizes a
scale-in that flips the victim's /v1/endpoints rows to draining and
waits out the router grace BEFORE any kill fires, everything is
journaled and operator-interruptible, and a failover neither re-fires
a completed action nor forgets an in-flight one (latches seeded from
the replayed journal).  Chaos kills the scheduler at every scale-plan
boundary and asserts convergence with zero duplicate actions.
"""

import random

import pytest

from dcos_commons_tpu.common import TaskState, TaskStatus
from dcos_commons_tpu.health.actions import (
    ActionPolicy,
    Decision,
    decide,
    remediation_allowed,
    scale_out_target,
    seed_latches,
)
from dcos_commons_tpu.health.detectors import (
    QuietPodWatcher,
    ServingSloWatcher,
)
from dcos_commons_tpu.http.api import SchedulerApi
from dcos_commons_tpu.scheduler.config import SchedulerConfig
from dcos_commons_tpu.testing import (
    AdvanceCycles,
    ExpectDeploymentComplete,
    SendTaskRunning,
    ServiceTestRunner,
)

SERVE_YAML = """
name: svc
pods:
  serve:
    count: 1
    tasks:
      server:
        goal: RUNNING
        cmd: "python serve.py"
        cpus: 0.5
        memory: 256
        ports:
          web:
            env-key: PORT_WEB
"""

# pod-level decommission needs the YAML opt-in (validation rejects a
# count shrink otherwise); the autoscale loop manages counts through
# the live-spec verb, which the opt-in does not gate
DECOMMISSION_YAML = SERVE_YAML.replace(
    "count: 1", "count: 2\n    allow-decommission: true"
)


def autoscale_config(**overrides) -> SchedulerConfig:
    base = dict(
        backoff_enabled=False,
        revive_capacity=10**9,
        health_autoscale=True,
        health_queue_depth_slo=10.0,
        autoscale_max_instances=3,
        autoscale_breach_hold_s=0.0,
        autoscale_quiet_hold_s=0.0,
        # large: within one test, each direction fires at most once
        # (run_cycle's own observe passes use the wall clock, so a
        # zero cooldown would let wall-time passes re-fire actions
        # between the test's explicit synthetic-now passes)
        autoscale_cooldown_out_s=1e6,
        autoscale_cooldown_in_s=1e6,
        autoscale_drain_grace_s=30.0,
    )
    base.update(overrides)
    return SchedulerConfig(**base)


def inject_stats(monitor, stats):
    """Feed the detectors directly (the telemetry fan-in itself is
    test_health's subject; these tests own the ACTION seam): park
    collection far in the future so _observe scores the injected
    snapshot instead of re-collecting over the FakeAgent."""
    monitor.telemetry_interval_s = 1e9
    monitor._last_telemetry = 1e18
    monitor._serving_stats = dict(stats)
    monitor._serving_env = {t: {} for t in stats}
    monitor._telemetry_seq += 1


def deploy_serve(config=None, count_running=1):
    runner = ServiceTestRunner(
        SERVE_YAML, scheduler_config=config or autoscale_config()
    )
    runner.run([
        AdvanceCycles(1),
        *[SendTaskRunning(f"serve-{i}-server")
          for i in range(count_running)],
        ExpectDeploymentComplete(),
    ])
    return runner


def ack_new_running(world):
    """RUNNING+ready for every launch not yet acked."""
    acked = world.extras.setdefault("acked", set())
    for info in list(world.agent.launched):
        if info.task_id in acked:
            continue
        acked.add(info.task_id)
        world.agent.send(TaskStatus(
            task_id=info.task_id, state=TaskState.RUNNING,
            ready=True, agent_id=info.agent_id,
        ))


def drive(world, cycles=8):
    for _ in range(cycles):
        world.scheduler.run_cycle()
        ack_new_running(world)


POLICY = ActionPolicy(
    autoscale=True, max_instances=4, breach_hold_s=10.0,
    quiet_hold_s=60.0, cooldown_out_s=30.0, cooldown_in_s=120.0,
)


# -- the pure decision rule -------------------------------------------


def test_scale_out_target_monotone_and_clamped():
    for count in range(1, 5):
        prev = count
        for severity in [0.5, 1.0, 1.5, 2.0, 3.9, 4.0, 9.0, 100.0]:
            target = scale_out_target(count, 6, severity, step_max=3)
            assert target >= prev  # monotone in severity
            assert count <= target <= 6
            prev = target
    # the step cap and the instance cap both bind
    assert scale_out_target(1, 8, 1e9, step_max=2) == 3
    assert scale_out_target(7, 8, 1e9, step_max=4) == 8
    # a pod set already past a lowered cap: a breach never scales it IN
    assert scale_out_target(8, 1, 2.0, step_max=2) == 8


def test_decide_breach_path():
    assert decide(
        100.0, policy=POLICY, count=2, baseline=1,
        breach_since=80.0, severity=2.0,
    ) == Decision("out", 4)
    # hysteresis hold not yet satisfied
    assert decide(
        85.0, policy=POLICY, count=2, baseline=1,
        breach_since=80.0, severity=2.0,
    ) is None
    # cooldown suppresses
    assert decide(
        100.0, policy=POLICY, count=2, baseline=1,
        breach_since=80.0, severity=2.0, cooldown_out_until=150.0,
    ) is None
    # at the ceiling: no-op decision is NO decision
    assert decide(
        100.0, policy=POLICY, count=4, baseline=1,
        breach_since=0.0, severity=9.0,
    ) is None


def test_decide_quiet_path_and_floor():
    assert decide(
        1000.0, policy=POLICY, count=3, baseline=1, quiet_since=900.0,
    ) == Decision("in", 2)
    # never below the YAML floor
    assert decide(
        1000.0, policy=POLICY, count=1, baseline=1, quiet_since=0.0,
    ) is None
    # cooldown and hold
    assert decide(
        1000.0, policy=POLICY, count=3, baseline=1, quiet_since=990.0,
    ) is None
    assert decide(
        1000.0, policy=POLICY, count=3, baseline=1, quiet_since=0.0,
        cooldown_in_until=2000.0,
    ) is None


def test_decide_single_flight_hold_and_precedence():
    # an in-flight action of EITHER direction suppresses everything
    for active in ("out", "in"):
        assert decide(
            1e6, policy=POLICY, count=2, baseline=1,
            breach_since=0.0, severity=9.0, quiet_since=0.0,
            active=active,
        ) is None
    # flap hold (open lease-churn episode) suppresses everything
    assert decide(
        1e6, policy=POLICY, count=2, baseline=1, breach_since=0.0,
        severity=9.0, hold=True,
    ) is None
    # breach dominates quiet: one state can never emit "in"
    decision = decide(
        1e6, policy=POLICY, count=3, baseline=1,
        breach_since=0.0, severity=2.0, quiet_since=0.0,
    )
    assert decision is not None and decision.direction == "out"
    # disabled policy decides nothing
    assert decide(
        1e6, policy=ActionPolicy(autoscale=False), count=2, baseline=1,
        breach_since=0.0, severity=9.0,
    ) is None


def test_constant_signal_never_oscillates():
    """The hysteresis band: replay a CONSTANT signal against the
    breach threshold and the quiet watermark and fold the emitted
    directions — at most ONE direction ever fires, whatever the
    value (in the dead band, neither)."""
    threshold, factor = 10.0, 0.25
    for value in [0.0, 1.0, 2.5, 2.6, 5.0, 9.9, 10.0, 10.1, 40.0]:
        breaching = value > threshold
        quiet = value <= threshold * factor
        assert not (breaching and quiet)
        directions = set()
        count, cooldowns = 2, {"out": 0.0, "in": 0.0}
        for now in range(0, 2000, 50):
            decision = decide(
                float(now), policy=POLICY, count=count, baseline=1,
                breach_since=0.0 if breaching else None,
                severity=value / threshold if breaching else 1.0,
                quiet_since=0.0 if quiet else None,
                cooldown_out_until=cooldowns["out"],
                cooldown_in_until=cooldowns["in"],
            )
            if decision is None:
                continue
            directions.add(decision.direction)
            count = decision.target
            cooldowns[decision.direction] = now + (
                POLICY.cooldown_out_s if decision.direction == "out"
                else POLICY.cooldown_in_s
            )
        assert len(directions) <= 1, (value, directions)


def _scale_events():
    return [
        {"seq": 1, "verb": "scale-out", "stage": "start", "pod": "a",
         "from": 1, "to": 3, "t": 10.0},
        {"seq": 2, "verb": "scale-out", "stage": "complete", "pod": "a",
         "from": 1, "to": 3, "t": 20.0},
        {"seq": 3, "verb": "auto-replace", "host": "h1", "t": 25.0},
        {"seq": 4, "verb": "scale-in", "stage": "start", "pod": "a",
         "from": 3, "to": 2, "t": 400.0},
        {"seq": 5, "verb": "scale-in", "stage": "complete", "pod": "a",
         "from": 3, "to": 2, "t": 410.0},
        {"seq": 6, "verb": "scale-out", "stage": "start", "pod": "b",
         "from": 2, "to": 4, "t": 500.0},
    ]


def test_seed_latches_fold_and_permutation_invariance():
    events = _scale_events()
    in_flight, done_t, last_replace = seed_latches(events)
    assert in_flight == {
        "b": {"direction": "out", "from": 2, "to": 4, "t": 500.0}
    }
    assert done_t == {("a", "out"): 20.0, ("a", "in"): 410.0}
    assert last_replace == 25.0
    # cooldown invariance under episode-event permutation: the fold
    # orders by journal seq, so shuffles cannot change the outcome
    for seed in range(12):
        shuffled = list(events)
        random.Random(seed).shuffle(shuffled)
        assert seed_latches(shuffled) == (in_flight, done_t,
                                          last_replace)


def test_remediation_allowed_gates():
    assert remediation_allowed(
        100.0, enabled=True, scale_active=False, hold=False,
        last_replace_t=None, cooldown_s=300.0,
    )
    assert not remediation_allowed(
        100.0, enabled=False, scale_active=False, hold=False,
        last_replace_t=None, cooldown_s=300.0,
    )
    # never while a scale plan for the service is active
    assert not remediation_allowed(
        100.0, enabled=True, scale_active=True, hold=False,
        last_replace_t=None, cooldown_s=300.0,
    )
    assert not remediation_allowed(
        100.0, enabled=True, scale_active=False, hold=True,
        last_replace_t=None, cooldown_s=300.0,
    )
    assert not remediation_allowed(
        100.0, enabled=True, scale_active=False, hold=False,
        last_replace_t=50.0, cooldown_s=300.0,
    )
    assert remediation_allowed(
        1000.0, enabled=True, scale_active=False, hold=False,
        last_replace_t=50.0, cooldown_s=300.0,
    )


# -- hypothesis properties (skipped without the package) --------------


try:  # pragma: no cover - availability varies by container
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=200, deadline=None)
    @given(
        count=st.integers(1, 8),
        cap=st.integers(1, 10),
        severities=st.lists(
            st.floats(0.1, 1e6, allow_nan=False), min_size=2,
            max_size=6,
        ),
    )
    def test_hyp_scale_out_target_monotone(count, cap, severities):
        targets = [
            scale_out_target(count, cap, s, step_max=3)
            for s in sorted(severities)
        ]
        assert targets == sorted(targets)
        assert all(count <= t <= max(cap, count) for t in targets)

    @settings(max_examples=150, deadline=None)
    @given(
        value=st.floats(0.0, 100.0, allow_nan=False),
        baseline=st.integers(1, 3),
        start_count=st.integers(1, 6),
    )
    def test_hyp_constant_signal_single_direction(
        value, baseline, start_count
    ):
        threshold, factor = 10.0, 0.25
        breaching = value > threshold
        quiet = value <= threshold * factor
        directions = set()
        count = max(start_count, baseline)
        cooldowns = {"out": 0.0, "in": 0.0}
        for now in range(0, 3000, 37):
            decision = decide(
                float(now), policy=POLICY, count=count,
                baseline=baseline,
                breach_since=0.0 if breaching else None,
                severity=max(1.0, value / threshold),
                quiet_since=0.0 if quiet else None,
                cooldown_out_until=cooldowns["out"],
                cooldown_in_until=cooldowns["in"],
            )
            if decision is None:
                continue
            directions.add(decision.direction)
            count = decision.target
            cooldowns[decision.direction] = now + 30.0
        assert len(directions) <= 1

    @settings(max_examples=100, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_hyp_seed_latches_permutation_invariant(seed):
        events = _scale_events()
        shuffled = list(events)
        random.Random(seed).shuffle(shuffled)
        assert seed_latches(shuffled) == seed_latches(events)


# -- the quiet-pod watcher --------------------------------------------


def test_quiet_watcher_episodes_and_dead_band():
    slo = ServingSloWatcher(queue_depth_slo=10.0, ttft_p95_slo_s=1.0)
    quiet = QuietPodWatcher(slo, quiet_factor=0.25)
    busy = {"t": {"queue_depth": 40.0, "ttft_p95_s": 0.1}}
    idle = {"t": {"queue_depth": 0.0, "ttft_p95_s": 0.01}}
    band = {"t": {"queue_depth": 5.0, "ttft_p95_s": 0.1}}

    assert quiet.observe(busy, now=1.0) == []
    events = quiet.observe(idle, now=2.0)
    assert [e["detector"] for e in events] == ["quiet"]
    assert quiet.quiet_since == {"t": 2.0}
    # still quiet: edge-triggered, no repeat; since is preserved
    assert quiet.observe(idle, now=3.0) == []
    assert quiet.quiet_since == {"t": 2.0}
    # the dead band (above the watermark, below the SLO): clears
    # quiet but test_constant_signal... shows it breaches nothing
    cleared = quiet.observe(band, now=4.0)
    assert cleared and cleared[0].get("cleared")
    assert quiet.quiet_since == {}
    assert slo.observe(band, now=4.0) == []  # not a breach either


def test_quiet_watcher_missed_samples_and_min_direction():
    slo = ServingSloWatcher(queue_depth_slo=10.0,
                            kv_pages_free_slo=16.0)
    quiet = QuietPodWatcher(slo, quiet_factor=0.25)
    idle = {"t": {"queue_depth": 0.0, "kv_pages_free": 100.0}}
    quiet.observe(idle, now=1.0)
    assert "t" in quiet.quiet_since
    # a missing sample is not a recovery; three in a row retires
    assert quiet.observe({}, now=2.0) == []
    assert quiet.observe({}, now=3.0) == []
    assert "t" in quiet.quiet_since
    assert quiet.observe({}, now=4.0) == []
    assert "t" not in quiet.quiet_since
    # a breaching MIN-direction signal (kv pages exhausted) is the
    # opposite of quiet even with an empty queue
    starved = {"t": {"queue_depth": 0.0, "kv_pages_free": 2.0}}
    assert quiet.observe(starved, now=5.0) == []
    assert "t" not in quiet.quiet_since


def test_slo_watcher_records_breach_since_and_severity():
    slo = ServingSloWatcher(queue_depth_slo=10.0)
    slo.observe({"t": {"queue_depth": 40.0}}, now=100.0)
    assert slo.breach_since[("t", "queue_depth")] == 100.0
    assert slo.breach_severity[("t", "queue_depth")] == 4.0
    # still breaching: since keeps the episode start, severity tracks
    slo.observe({"t": {"queue_depth": 80.0}}, now=110.0)
    assert slo.breach_since[("t", "queue_depth")] == 100.0
    assert slo.breach_severity[("t", "queue_depth")] == 8.0
    slo.observe({"t": {"queue_depth": 1.0}}, now=120.0)
    assert slo.breach_since == {} and slo.breach_severity == {}


# -- the closed loop, end to end --------------------------------------


def test_closed_loop_breach_scale_out_recover_quiet_scale_in():
    """The acceptance scenario: breach -> scale-out deploys through
    the normal offer cycle -> SLO recovers -> sustained quiet ->
    scale-in with the endpoints draining flip and router grace
    BEFORE the kill -> journal carries the audited episode pairs."""
    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    monitor = scheduler.health
    api = SchedulerApi(scheduler)
    clock = [0.0]
    scheduler.actions._clock = lambda: clock[0]

    # seeded SLO breach under load (queue depth 4x its SLO)
    inject_stats(monitor, {"serve-0-server": {"queue_depth": 40.0}})
    events = monitor._observe(scheduler, 1000.0)
    starts = [e for e in events if e.get("stage") == "start"]
    assert [e["verb"] for e in starts] == ["scale-out"]
    assert starts[0]["to"] == 3 and starts[0]["from"] == 1
    # trace correlation back to the triggering episode
    assert starts[0]["task"] == "serve-0-server"
    assert starts[0]["signal"] == "queue_depth"
    phase = scheduler.actions.manager.phase_for("serve")
    assert phase.name == "scale-out-serve-3"

    drive(world, cycles=8)
    assert scheduler.spec.pod("serve").count == 3
    names = {i.name for i in world.agent.launched}
    assert {"serve-1-server", "serve-2-server"} <= names
    assert phase.is_complete
    # settled (run_cycle's own observe passes): completion journaled,
    # cooldown clock started, phase pruned
    assert scheduler.actions.manager.phase_for("serve") is None
    assert ("serve", "out") in scheduler.actions._done_t
    assert any(
        e.get("stage") == "complete"
        for e in scheduler.journal.events(kinds=("health",))
    )

    # recovered SLO, then a sustained quiet period on ALL instances
    idle = {
        f"serve-{i}-server": {"queue_depth": 0.5} for i in range(3)
    }
    inject_stats(monitor, idle)
    events = monitor._observe(scheduler, 2000.0)
    assert any(
        e.get("detector") == "slo" and e.get("cleared") for e in events
    )
    starts = [e for e in events if e.get("stage") == "start"]
    assert [e["verb"] for e in starts] == ["scale-in"]
    phase = scheduler.actions.manager.phase_for("serve")
    assert phase.name == "scale-in-serve-2"
    assert scheduler.draining_instances() == {"serve-2"}

    # drive the shrink + drain start; the kill must NOT fire inside
    # the router drain grace, while the endpoints surface shows the
    # victim draining with its task still RUNNING on a healthy host
    clock[0] = 3000.0
    world.scheduler.run_cycle()
    world.scheduler.run_cycle()
    assert scheduler.spec.pod("serve").count == 2
    victim_id = world.agent.task_id_of("serve-2-server")
    assert victim_id not in world.agent.kills
    _code, endpoint = api.get_endpoint("web")
    rows = {r["task"]: r for r in endpoint["backends"]}
    assert rows["serve-2-server"]["draining"] is True
    assert rows["serve-2-server"]["state"] == "TASK_RUNNING"
    assert rows["serve-0-server"]["draining"] is False

    # grace elapses -> kill -> unreserve -> erase
    clock[0] = 3031.0
    drive(world, cycles=6)
    assert victim_id in world.agent.kills
    assert scheduler.state_store.fetch_task("serve-2-server") is None
    assert scheduler.ledger.for_task("serve-2-server") == []
    assert scheduler.actions.manager.phase_for("serve") is None

    # the audited, flap-free episode record: start/complete pairs in
    # strict alternation, no opposite-direction overlap
    stages = [
        (e["verb"], e["stage"])
        for e in scheduler.journal.events(kinds=("health",))
        if e.get("stage")
    ]
    assert stages == [
        ("scale-out", "start"), ("scale-out", "complete"),
        ("scale-in", "start"), ("scale-in", "complete"),
    ]


def test_scale_plan_is_operator_interruptible():
    """An automated action is a plan like any other: interrupt parks
    it (single flight holds, nothing else fires), proceed resumes."""
    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    monitor = scheduler.health
    api = SchedulerApi(scheduler)

    inject_stats(monitor, {"serve-0-server": {"queue_depth": 40.0}})
    monitor._observe(scheduler, 1000.0)
    code, _body = api.plan_interrupt("autoscale")
    assert code == 200
    drive(world, cycles=4)
    phase = scheduler.actions.manager.phase_for("serve")
    assert phase is not None and not phase.is_complete
    # interrupted-but-active: still single-flight, no second action
    inject_stats(monitor, {"serve-0-server": {"queue_depth": 90.0}})
    events = monitor._observe(scheduler, 1500.0)
    assert not [e for e in events if e.get("stage") == "start"]
    code, _body = api.plan_continue("autoscale")
    assert code == 200
    drive(world, cycles=8)
    assert phase.is_complete
    assert scheduler.spec.pod("serve").count == 3


def test_failover_resumes_in_flight_action_without_refire():
    """Action latches and cooldown clocks are seeded from the
    replayed journal: a successor RESUMES the in-flight scale-out
    (idempotent steps, deployment steps re-seeded from state) and a
    later successor sees the completed action's cooldown instead of
    re-firing it."""
    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    monitor = scheduler.health

    inject_stats(monitor, {"serve-0-server": {"queue_depth": 40.0}})
    monitor._observe(scheduler, 1000.0)
    # grow + first launches land; the action is mid-flight
    world.scheduler.run_cycle()
    assert scheduler.spec.pod("serve").count == 3
    launched_before = {i.name for i in world.agent.launched}

    # the scheduler dies; a successor rebuilds over the same store
    runner2 = runner.restart()
    world2 = runner2.build()
    scheduler2 = world2.scheduler
    world2.scheduler.run_cycle()  # rehydrate: seed + restore plans
    phase = scheduler2.actions.manager.phase_for("serve")
    assert phase is not None and phase.name == "scale-out-serve-3"
    assert scheduler2.spec.pod("serve").count == 3
    drive(world2, cycles=8)
    assert phase.is_complete
    # no duplicate action, no duplicate deploys: one start event,
    # one complete event, and the successor re-launched nothing that
    # already ran
    completes = [
        e for e in scheduler2.journal.events(kinds=("health",))
        if e.get("stage") == "complete"
    ]
    assert len(completes) == 1
    starts = [
        e for e in scheduler2.journal.events(kinds=("health",))
        if e.get("stage") == "start"
    ]
    assert len(starts) == 1
    relaunched = [
        i.name for i in world2.agent.launched
        if i.name in launched_before
    ]
    assert len(relaunched) == len(launched_before)

    # a THIRD incarnation seeds the completed action as a cooldown
    # latch, not an in-flight plan
    runner3 = runner2.restart()
    world3 = runner3.build()
    world3.scheduler.run_cycle()
    engine3 = world3.scheduler.actions
    assert engine3.manager.phase_for("serve") is None
    assert ("serve", "out") in engine3._done_t


CHAOS_BOUNDARIES = (
    "post-evaluate",
    "post-wal",
    "mid-status-fan-in",
    "mid-plan-transition",
)


@pytest.mark.parametrize("kind", CHAOS_BOUNDARIES)
def test_chaos_kill_at_scale_plan_boundary(kind):
    """Kill the scheduler at every span boundary of a scale-out
    plan's deploy work: the successor converges, the journal carries
    exactly ONE scale action, and no reservation is double-held."""
    from dcos_commons_tpu.testing.chaos import (
        CrashInjector,
        KillPoint,
        SchedulerKilled,
    )

    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    inject_stats(scheduler.health,
                 {"serve-0-server": {"queue_depth": 40.0}})
    scheduler.health._observe(scheduler, 1000.0)
    scheduler.chaos = CrashInjector(KillPoint(kind, 1))

    killed = False
    for _ in range(24):
        try:
            world.scheduler.run_cycle()
        except SchedulerKilled:
            killed = True
            runner = runner.restart()
            world = runner.build()
            scheduler = world.scheduler
            inject_stats(scheduler.health,
                         {"serve-0-server": {"queue_depth": 40.0}})
            continue
        ack_new_running(world)
        phase = scheduler.actions.manager.phase_for("serve")
        if phase is None or phase.is_complete:
            if scheduler.spec.pod("serve").count == 3 and all(
                scheduler.state_store.fetch_task(f"serve-{i}-server")
                is not None
                for i in range(3)
            ):
                break
    assert killed, f"kill point {kind} never fired"
    assert scheduler.spec.pod("serve").count == 3
    # exactly one audited action across both incarnations
    starts = [
        e for e in scheduler.journal.events(kinds=("health",))
        if e.get("stage") == "start"
    ]
    assert len(starts) == 1, starts
    # zero double-reservations: every claim belongs to a stored task,
    # at most one claim set per task name
    stored = {i.name for i in scheduler.state_store.fetch_tasks()}
    seen = {}
    for reservation in scheduler.ledger.all():
        assert reservation.task_name in stored
        key = (reservation.task_name, reservation.host_id)
        assert seen.setdefault(key, reservation.reservation_id) == \
            reservation.reservation_id


# -- single flight across plan families + the multi discipline --------


def test_remediation_suppressed_while_scale_plan_active():
    runner = deploy_serve(config=autoscale_config(
        health_remediation=True,
    ))
    world = runner.world
    scheduler = world.scheduler
    monitor = scheduler.health

    inject_stats(monitor, {"serve-0-server": {"queue_depth": 40.0}})
    monitor._observe(scheduler, 1000.0)
    assert scheduler.actions.manager.phase_for("serve") is not None
    # a straggler episode lands while the scale plan is in flight:
    # remediation must NOT fire (no storm)
    straggler = [{
        "kind": "alert", "detector": "straggler",
        "host": world.agent.launched[0].agent_id, "score": 5.0,
    }]
    out = scheduler.actions.remediate(
        scheduler, straggler, True, now=1001.0
    )
    assert out == []
    # once the scale action settles, the same episode may remediate
    drive(world, cycles=8)
    assert scheduler.actions.manager.phase_for("serve") is None
    out = scheduler.actions.remediate(
        scheduler, straggler, True, now=1011.0
    )
    assert len(out) == 1 and out[0]["verb"] == "auto-replace"


def test_recovery_defers_to_in_flight_scale_action():
    """A failed scale-out launch is the SCALE phase's to retry:
    recovery treats an instance owned by an incomplete autoscale step
    as externally managed, exactly as it defers to an incomplete
    deploy step — otherwise the two plans would trade launches for
    the same task names."""
    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    inject_stats(scheduler.health,
                 {"serve-0-server": {"queue_depth": 40.0}})
    scheduler.health._observe(scheduler, 1000.0)
    world.scheduler.run_cycle()  # grow
    world.scheduler.run_cycle()  # launch serve-1
    failed = world.agent.task_id_of("serve-1-server")
    assert failed is not None
    world.agent.send(TaskStatus(
        task_id=failed, state=TaskState.FAILED,
        message="boom", agent_id="host-0",
    ))
    world.scheduler.run_cycle()  # route the failure
    recovery = scheduler.plan("recovery")
    assert not any(
        "serve-1" in s.get_asset_names()
        for p in recovery.phases for s in p.steps
    ), [p.name for p in recovery.phases]
    # the scale phase itself retries the launch and completes
    drive(world, cycles=8)
    assert scheduler.actions.manager.phase_for("serve") is None or \
        scheduler.actions.manager.phase_for("serve").is_complete
    assert scheduler.spec.pod("serve").count == 3
    status = scheduler.state_store.fetch_status("serve-1-server")
    assert status is not None and status.state is TaskState.RUNNING


def test_scale_out_counts_as_growth_for_offer_discipline():
    """Bounded concurrent growth across services: a service with an
    active scale-out plan reads as 'growing', so the multi
    scheduler's ParallelFootprintDiscipline bounds how many services
    scale out at once (the OfferDiscipline enforcement point)."""
    from dcos_commons_tpu.multi.scheduler import MultiServiceScheduler

    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    assert not MultiServiceScheduler._is_growing(scheduler)
    inject_stats(scheduler.health,
                 {"serve-0-server": {"queue_depth": 40.0}})
    scheduler.health._observe(scheduler, 1000.0)
    assert MultiServiceScheduler._is_growing(scheduler)
    drive(world, cycles=8)
    scheduler.health._observe(scheduler, 1010.0)
    assert not MultiServiceScheduler._is_growing(scheduler)


# -- operator surfaces ------------------------------------------------


def test_pod_scale_verb_and_single_flight_conflict():
    runner = deploy_serve(config=autoscale_config(
        health_autoscale=False,  # manual scale works with the loop off
    ))
    world = runner.world
    scheduler = world.scheduler
    api = SchedulerApi(scheduler)

    code, body = api.pod_scale("serve", {"count": 2})
    assert code == 200 and body["phase"] == "scale-out-serve-2"
    # single flight: a second scale while one is in flight is a 409
    code, body = api.pod_scale("serve", {"count": 3})
    assert code == 409
    code, _body = api.pod_scale("nope", {"count": 2})
    assert code == 404
    code, _body = api.pod_scale("serve", {"count": "two"})
    assert code == 400
    drive(world, cycles=8)
    assert scheduler.spec.pod("serve").count == 2
    assert scheduler.actions.manager.phase_for("serve") is None
    # scale-in goes one instance at a time
    code, body = api.pod_scale("serve", {"count": 1})
    assert code == 200 and body["phase"] == "scale-in-serve-1"
    drive(world, cycles=8)
    assert scheduler.spec.pod("serve").count == 1
    # never below the YAML floor: the restart overlay would silently
    # undo it — the verb refuses and points at the YAML path
    code, body = api.pod_scale("serve", {"count": 0})
    assert code == 400


def test_surplus_decommission_flips_endpoint_draining():
    """The satellite proper: a POD-LEVEL decommission (count shrunk
    in the target spec — no autoscale involved) flips the surplus
    backend's endpoint rows to draining while its task is still
    RUNNING and its host healthy, BEFORE the kill completes."""
    import dataclasses

    runner = ServiceTestRunner(
        DECOMMISSION_YAML,
        scheduler_config=SchedulerConfig(
            backoff_enabled=False, revive_capacity=10**9,
        ),
    )
    runner.run([
        AdvanceCycles(1),
        SendTaskRunning("serve-0-server"),
        SendTaskRunning("serve-1-server"),
        ExpectDeploymentComplete(),
    ])
    # the operator shrinks the spec: a restart builds the surplus
    # decommission plan for serve-1
    shrunk = dataclasses.replace(
        runner.spec,
        pods=tuple(
            dataclasses.replace(p, count=1) for p in runner.spec.pods
        ),
    )
    runner2 = ServiceTestRunner(
        spec=shrunk, persister=runner.persister,
        scheduler_config=runner.config,
    )
    runner2.agent = runner.agent
    runner2.inventory = runner.inventory
    runner2.agent.auto_ack_kills = False  # hold the kill un-acked
    world2 = runner2.build()
    scheduler2 = world2.scheduler
    api = SchedulerApi(scheduler2)
    assert scheduler2.plan("decommission") is not None
    assert scheduler2.draining_instances() == {"serve-1"}
    world2.scheduler.run_cycle()  # kill issued, not yet acked
    # the count shrink is also a config update: serve-0 rolls to the
    # new target — ack its relaunch so the survivor row is healthy
    ack_new_running(world2)
    world2.scheduler.run_cycle()
    _code, endpoint = api.get_endpoint("web")
    rows = {r["task"]: r for r in endpoint["backends"]}
    assert rows["serve-1-server"]["draining"] is True
    assert rows["serve-1-server"]["state"] == "TASK_RUNNING"
    assert rows["serve-0-server"]["draining"] is False


def test_remediation_hold_covers_whole_churn_episode():
    """The lease-churn alert event fires only on the episode's
    OPENING edge; the hold must ride the stateful episode flag, or a
    straggler alert one pass later would replace a pod under
    flapping leadership."""
    runner = deploy_serve(config=autoscale_config(
        health_remediation=True, health_autoscale=False,
    ))
    scheduler = runner.world.scheduler
    straggler = [{
        "kind": "alert", "detector": "straggler",
        "host": runner.world.agent.launched[0].agent_id, "score": 5.0,
    }]
    # episode open (no edge event in THIS pass): still held
    out = scheduler.actions.remediate(
        scheduler, straggler, True, now=100.0, hold=True,
    )
    assert out == []
    out = scheduler.actions.remediate(
        scheduler, straggler, True, now=101.0, hold=False,
    )
    assert len(out) == 1


def test_quiet_needs_a_load_signal_not_just_headroom():
    """Min-direction headroom signals veto quiet but never attest:
    with only kv_pages_free_slo enabled, a loaded-but-not-starved
    pod must read UNKNOWN, not quiet (the scale-in it would trigger
    breaches and flaps)."""
    slo = ServingSloWatcher(kv_pages_free_slo=16.0)
    quiet = QuietPodWatcher(slo, quiet_factor=0.25)
    plenty = {"t": {"kv_pages_free": 100.0}}
    assert quiet.observe(plenty, now=1.0) == []
    assert quiet.quiet_since == {}


def test_task_owner_longest_type_match():
    """Pod 'web-2''s tasks must never attribute to pod 'web'."""
    import dataclasses

    from dcos_commons_tpu.health.actions import HealthActionEngine
    from dcos_commons_tpu.specification.yaml_spec import from_yaml

    spec = from_yaml(SERVE_YAML)
    twin = dataclasses.replace(spec.pods[0], type="serve-2")
    spec = dataclasses.replace(spec, pods=spec.pods + (twin,))
    owner = HealthActionEngine._task_owner
    assert owner(spec, "serve-0-server") == ("serve", 0)
    assert owner(spec, "serve-2-0-server") == ("serve-2", 0)
    assert owner(spec, "serve-2-3-server") == ("serve-2", 3)
    assert owner(spec, "unrelated-0-x") is None


def test_abandon_settles_count_to_deployed_reality():
    """Abandoning a half-deployed scale-out reverts the persisted
    count to the contiguous deployed prefix — otherwise the next
    restart's count overlay would silently resume the abandoned
    widening."""
    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    inject_stats(scheduler.health,
                 {"serve-0-server": {"queue_depth": 40.0}})
    scheduler.health._observe(scheduler, 1000.0)  # start 1 -> 3
    world.scheduler.run_cycle()  # grow: count = 3
    world.scheduler.run_cycle()  # serve-1 launched (not yet acked)
    assert scheduler.spec.pod("serve").count == 3
    assert scheduler.actions.abandon(scheduler, "serve")
    # serve-1 has a stored task, serve-2 does not: settle at 2
    assert scheduler.spec.pod("serve").count == 2
    raw = scheduler.state_store.fetch_property("autoscale-count-serve")
    assert raw == b"2@1"  # count @ the YAML floor it was written against
    abandoned = [
        e for e in scheduler.journal.events(kinds=("health",))
        if e.get("stage") == "abandoned"
    ]
    assert abandoned and abandoned[0]["settled"] == 2
    # the abandonment is terminal: the out-direction cooldown latched
    assert ("serve", "out") in scheduler.actions._done_t


def test_failover_mid_scale_in_honors_drain_grace():
    """The successor of a scheduler killed mid-scale-in must NOT
    build a drain-less surplus-decommission phase for the victim:
    the journal-latched scale-in owns the teardown, and its drain
    step re-waits the FULL router grace before any kill."""
    runner = deploy_serve()
    world = runner.world
    scheduler = world.scheduler
    monitor = scheduler.health
    clock = [0.0]
    scheduler.actions._clock = lambda: clock[0]

    inject_stats(monitor, {"serve-0-server": {"queue_depth": 40.0}})
    monitor._observe(scheduler, 1000.0)
    drive(world, cycles=8)  # scale-out to 3 completes + settles
    idle = {
        f"serve-{i}-server": {"queue_depth": 0.5} for i in range(3)
    }
    inject_stats(monitor, idle)
    monitor._observe(scheduler, 2000.0)  # scale-in starts
    clock[0] = 3000.0
    world.scheduler.run_cycle()  # shrink (count persists at 2) + drain starts
    victim_id = world.agent.task_id_of("serve-2-server")

    # kill -9; the successor rebuilds over the persisted count
    runner2 = runner.restart()
    world2 = runner2.build()
    scheduler2 = world2.scheduler
    clock2 = [5000.0]
    scheduler2.actions._clock = lambda: clock2[0]
    # NO decommission phase for the victim: the scale-in owns it
    decommission = scheduler2.plan("decommission")
    assert decommission is None or not any(
        "serve-2" in getattr(p, "decommission_targets", set())
        for p in decommission.phases
    )
    inject_stats(scheduler2.health, idle)
    for _ in range(6):
        world2.scheduler.run_cycle()
    # inside the re-started grace: victim alive, rows draining
    assert victim_id not in world2.agent.kills
    assert scheduler2.draining_instances() == {"serve-2"}
    clock2[0] = 5031.0  # the FULL grace elapses on the successor
    drive(world2, cycles=8)
    assert victim_id in world2.agent.kills
    assert scheduler2.state_store.fetch_task("serve-2-server") is None


def test_pod_scale_abandon_verb():
    runner = deploy_serve(config=autoscale_config(
        health_autoscale=False,
    ))
    world = runner.world
    scheduler = world.scheduler
    api = SchedulerApi(scheduler)
    code, _body = api.pod_scale_abandon("serve")
    assert code == 409  # nothing in flight
    code, _body = api.pod_scale("serve", {"count": 3})
    assert code == 200
    world.scheduler.run_cycle()  # grow only; no deploys acked
    code, body = api.pod_scale_abandon("serve")
    assert code == 200 and body["abandoned"] is True
    # settled back to the deployed single instance
    assert scheduler.spec.pod("serve").count == 1
    assert scheduler.actions.manager.phase_for("serve") is None
    code, _body = api.pod_scale_abandon("nope")
    assert code == 404


def test_manual_scale_settles_without_health_plane():
    """HEALTH_ENABLED=false wires the NullHealthMonitor, which never
    calls the engine's settle pass — the scale verbs settle terminal
    phases themselves, so single flight can never wedge a
    health-disabled scheduler."""
    runner = deploy_serve(config=autoscale_config(
        health_enabled=False, health_autoscale=False,
    ))
    world = runner.world
    scheduler = world.scheduler
    api = SchedulerApi(scheduler)
    code, _body = api.pod_scale("serve", {"count": 2})
    assert code == 200
    drive(world, cycles=8)
    assert scheduler.actions.manager.phase_for("serve") is not None
    assert scheduler.actions.manager.phase_for("serve").is_complete
    # a second scale settles the completed phase instead of 409ing
    code, body = api.pod_scale("serve", {"count": 3})
    assert code == 200, body
    # and abandon of a COMPLETED phase settles it as complete too —
    # never a false 'abandoned' journal stage
    drive(world, cycles=8)
    assert scheduler.abandon_scale("serve") is False


def test_yaml_count_change_invalidates_stale_override():
    """The persisted count is stamped with the YAML floor it was
    written against: an operator's config update that CHANGES the
    declared count drops the stale autoscale decision — the overlay
    must never neutralize a YAML count decrease."""
    import dataclasses

    from dcos_commons_tpu.scheduler.builder import (
        _apply_autoscale_counts,
    )
    from dcos_commons_tpu.specification.yaml_spec import from_yaml
    from dcos_commons_tpu.state.state_store import StateStore
    from dcos_commons_tpu.storage import MemPersister

    spec = from_yaml(SERVE_YAML)  # serve: count 1
    store = StateStore(MemPersister())
    store.store_property("autoscale-count-serve", b"4@1")
    # unchanged YAML floor: the override applies
    overlaid, baselines = _apply_autoscale_counts(spec, store)
    assert overlaid.pod("serve").count == 4
    assert baselines == {"serve": 1}
    # the operator moves the YAML count: the stale override is dropped
    wider = dataclasses.replace(
        spec,
        pods=tuple(
            dataclasses.replace(p, count=2) for p in spec.pods
        ),
    )
    overlaid, baselines = _apply_autoscale_counts(wider, store)
    assert overlaid.pod("serve").count == 2
    assert baselines == {"serve": 2}
    # corrupt property: ignored
    store.store_property("autoscale-count-serve", b"junk")
    overlaid, _ = _apply_autoscale_counts(spec, store)
    assert overlaid.pod("serve").count == 1


def test_scale_out_steps_inherit_launch_backoff():
    """A crash-looping scaled-out instance backs off like a
    deploy-plan instance, not hot-retrying every cycle."""
    from dcos_commons_tpu.plan.backoff import ExponentialBackoff

    runner = deploy_serve(config=autoscale_config(
        backoff_enabled=True,
    ))
    scheduler = runner.world.scheduler
    assert isinstance(scheduler.actions.backoff, ExponentialBackoff)
    inject_stats(scheduler.health,
                 {"serve-0-server": {"queue_depth": 40.0}})
    scheduler.health._observe(scheduler, 1000.0)
    phase = scheduler.actions.manager.phase_for("serve")
    deploy_steps = [
        s for s in phase.steps if hasattr(s, "requirement")
    ]
    assert deploy_steps and all(
        isinstance(s._backoff, ExponentialBackoff) for s in deploy_steps
    )


def test_debug_health_exposes_action_state():
    runner = deploy_serve()
    scheduler = runner.world.scheduler
    inject_stats(scheduler.health,
                 {"serve-0-server": {"queue_depth": 40.0}})
    scheduler.health._observe(scheduler, 1000.0)
    api = SchedulerApi(scheduler)
    _code, body = api.debug_health()
    actions = body["actions"]
    assert actions["enabled"] is True
    assert actions["active"]["serve"]["direction"] == "out"
    assert actions["active"]["serve"]["to"] == 3
    assert any(
        e.get("verb") == "scale-out" for e in actions["recent"]
    )
    # quiet watcher state rides the detector block
    assert "quiet" in body["slo"] or "quiet" in body
