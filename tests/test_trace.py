"""traceview: the cross-layer correlation chain, exporters, steplog.

The acceptance scenario: a simulated 4-host gang deploy (testing/
harness) must produce ONE trace in which the offer-cycle span, its
per-pod evaluation spans, the launch span, the launch WAL, the status
arrivals, and the plan-step COMPLETE transition all share a
correlation chain — the join the operator used to do by timestamp
across /v1/debug/offers, plan state, and sandbox logs.
"""

import json
import os

from dcos_commons_tpu.metrics.registry import Metrics
from dcos_commons_tpu.offer.inventory import TpuHost, make_test_fleet
from dcos_commons_tpu.testing import (
    AdvanceCycles,
    ExpectDeploymentComplete,
    SendTaskRunning,
    ServiceTestRunner,
)
from dcos_commons_tpu.trace import (
    StepLog,
    TraceRecorder,
    read_steplog,
    to_chrome,
    to_text,
)

GANG_YAML = """
name: jax
pods:
  trainer:
    count: 4
    gang: true
    tpu:
      generation: v5e
      chips-per-host: 4
      topology: 4x4
    tasks:
      worker:
        goal: RUNNING
        cmd: "python train.py"
        cpus: 2.0
        memory: 4096
"""


def deploy_gang():
    """4-host gang deploy through the sim harness; returns the world."""
    runner = ServiceTestRunner(
        GANG_YAML,
        hosts=make_test_fleet(host_grid=(2, 2), chip_block=(2, 2)),
    )
    world = runner.run([
        AdvanceCycles(1),
        SendTaskRunning("trainer-0-worker"),
        SendTaskRunning("trainer-1-worker"),
        SendTaskRunning("trainer-2-worker"),
        SendTaskRunning("trainer-3-worker"),
        ExpectDeploymentComplete(),
    ])
    return runner, world


def by_name(spans, prefix):
    return [s for s in spans if s.name.startswith(prefix)]


# -- the correlation chain (acceptance criterion) ---------------------


def test_gang_deploy_single_correlation_chain():
    _runner, world = deploy_gang()
    spans = world.scheduler.tracer.snapshot()

    launches = by_name(spans, "launch:trainer")
    assert len(launches) == 1, [s.name for s in spans]
    launch = launches[0]
    trace = launch.trace_id

    # the offer-cycle span IS the root of the chain
    cycles = [s for s in by_name(spans, "cycle") if s.trace_id == trace]
    assert len(cycles) == 1
    cycle = cycles[0]
    assert not cycle.parent_id  # the chain root has no parent
    assert launch.parent_id == cycle.span_id

    # per-requirement evaluation span, child of the cycle
    evals = [
        s for s in by_name(spans, "evaluate:trainer-[")
        if s.trace_id == trace
    ]
    assert len(evals) == 1 and evals[0].parent_id == cycle.span_id
    assert evals[0].attrs["passed"] == "true"

    # per-pod evaluation outcome spans, one lane per pod instance
    for i in range(4):
        pods = [
            s for s in by_name(spans, f"evaluate:trainer-{i}")
            if s.trace_id == trace and s.track == f"trainer-{i}"
        ]
        assert pods and pods[0].attrs["outcome"] == "pass"

    # the WAL write is a child of the launch span
    wals = [s for s in by_name(spans, "launch.wal") if s.trace_id == trace]
    assert len(wals) == 1 and wals[0].parent_id == launch.span_id
    assert "trainer-0-worker" in wals[0].attrs["tasks"]

    # every task id the launch carried is in the launch span attrs
    task_ids = wals[0].attrs["task_ids"].split(",")
    assert len(task_ids) == 4
    assert set(launch.attrs["task_ids"].split(",")) == set(task_ids)

    # status arrivals (later cycles!) link back to the launch span via
    # the task id, joining the SAME trace
    statuses = [
        s for s in by_name(spans, "status:TASK_RUNNING")
        if s.trace_id == trace
    ]
    assert len(statuses) == 4
    assert all(s.parent_id == launch.span_id for s in statuses)
    assert {s.track for s in statuses} == {
        f"trainer-{i}" for i in range(4)
    }

    # the plan-step transitions reference the chain too: the launch
    # anchors PENDING->STARTING, the final status anchors ->COMPLETE
    steps = [s for s in by_name(spans, "step:") if s.trace_id == trace]
    transitions = {(s.attrs["from"], s.attrs["to"]) for s in steps}
    assert ("PENDING", "STARTING") in transitions
    assert any(to == "COMPLETE" for _from, to in transitions)
    complete = [s for s in steps if s.attrs["to"] == "COMPLETE"][0]
    # ...and the COMPLETE transition's parent is the triggering
    # status's span (the 4th RUNNING)
    assert complete.parent_id in {s.span_id for s in statuses}


def test_chrome_export_round_trips_with_pod_lanes():
    _runner, world = deploy_gang()
    tracer = world.scheduler.tracer
    blob = json.loads(json.dumps(to_chrome(tracer, service="jax")))
    events = blob["traceEvents"]
    assert events
    assert all(e["ph"] == "X" for e in events)
    assert all(e["pid"] == "jax" for e in events)
    tids = {e["tid"] for e in events}
    for i in range(4):
        assert f"trainer-{i}" in tids, tids
    assert "scheduler" in tids and "plan" in tids
    # timestamps are wall µs and durations are positive
    assert all(e["dur"] >= 1 for e in events)
    assert blob["otherData"]["dropped"] == 0


def test_text_timeline_renders():
    _runner, world = deploy_gang()
    text = to_text(world.scheduler.tracer, service="jax")
    assert text.startswith("# trace:")
    assert "cycle" in text and "launch:trainer" in text
    assert "status:TASK_RUNNING" in text


def test_failing_evaluation_records_the_failing_requirement():
    # the gang wants 4 hosts; give it one CPU host: the evaluation
    # span must carry the refusal as an attribute
    runner = ServiceTestRunner(
        GANG_YAML, hosts=[TpuHost(host_id="only-host")]
    )
    runner.run([AdvanceCycles(1)])
    spans = runner.world.scheduler.tracer.snapshot()
    evals = by_name(spans, "evaluate:trainer-[")
    assert evals and evals[0].attrs["passed"] == "false"
    assert evals[0].attrs["failing_requirement"]
    pod_events = by_name(spans, "evaluate:trainer-0")
    assert pod_events and pod_events[0].attrs["outcome"] == "fail"
    assert pod_events[0].attrs["failing_requirement"]


# -- recorder mechanics ----------------------------------------------


def test_ring_buffer_drops_oldest_and_counts():
    metrics = Metrics()
    tracer = TraceRecorder(capacity=4, metrics=metrics)
    for i in range(10):
        tracer.event(f"e{i}")
    spans = tracer.snapshot()
    assert [s.name for s in spans] == ["e6", "e7", "e8", "e9"]
    assert tracer.dropped == 6
    assert metrics.counters()["trace.dropped"] == 6
    # the drop count is surfaced by both exporters
    assert to_chrome(tracer)["otherData"]["dropped"] == 6
    assert "(6 dropped" in to_text(tracer)


def test_disabled_recorder_is_inert():
    tracer = TraceRecorder(capacity=0)
    with tracer.span("cycle", pod="x") as span:
        span.set_attr("k", "v")
        child = tracer.event("child", parent=span)
    assert tracer.snapshot() == []
    assert tracer.dropped == 0
    assert child.attrs == {}
    tracer.register_launch("task-1", span)
    assert tracer.launch_ref("task-1") is None


def test_span_end_is_idempotent_and_drop_skips_recording():
    tracer = TraceRecorder(capacity=8)
    span = tracer.span("once")
    span.end()
    span.end()
    assert len(tracer.snapshot()) == 1
    idle = tracer.span("idle")
    idle.drop()
    idle.end()
    assert len(tracer.snapshot()) == 1  # dropped spans never record
    assert tracer.dropped == 0  # ...and don't count as ring overflow


def test_interval_records_a_closed_span_with_the_stamps_given():
    tracer = TraceRecorder(capacity=8)
    with tracer.span("request", track="req") as parent:
        done = tracer.interval(
            "waited", 10.0, 12.5, parent=parent, rid=7,
        )
    assert done.end_s == 12.5 and done.duration_s == 2.5
    assert (done.trace_id, done.parent_id, done.track) == (
        parent.trace_id, parent.span_id, "req",
    )
    done.end()  # already closed: not recorded twice
    assert [s.name for s in tracer.snapshot()] == ["waited", "request"]
    assert "rid=7" in to_text(tracer)
    # a disabled recorder mints nothing
    off = TraceRecorder(capacity=0)
    assert off.interval("waited", 1.0, 2.0, rid=1).attrs == {}
    assert off.snapshot() == []


def test_idle_cycles_do_not_flood_the_ring():
    _runner, world = deploy_gang()
    tracer = world.scheduler.tracer
    before = len(tracer.snapshot())
    for _ in range(50):
        world.scheduler.run_cycle()  # nothing to do: all idle
    assert len(tracer.snapshot()) == before


# -- steplog -----------------------------------------------------------


def test_steplog_write_read_and_merge(tmp_path):
    path = str(tmp_path / "steplog.jsonl")
    log = StepLog(path)
    for i in range(3):
        log.record(i, wall_s=0.5, tokens=4096, blocked_s=0.01 * i,
                   worker=2)
    log.close()
    # a torn half-line (worker killed mid-write) must not break parsing
    with open(path, "a") as f:
        f.write('{"step": 3, "wall')
    records = read_steplog(path)
    assert [r["step"] for r in records] == [0, 1, 2]
    assert records[2]["blocked_s"] == 0.02

    tracer = TraceRecorder(capacity=8)
    tracer.event("cycle")
    steplogs = {"trainer-2-worker": records}
    blob = json.loads(json.dumps(
        to_chrome(tracer, service="jax", steplogs=steplogs)
    ))
    lanes = {e["tid"] for e in blob["traceEvents"]}
    assert "trainer-2-worker/steps" in lanes
    step_events = [
        e for e in blob["traceEvents"]
        if e["tid"] == "trainer-2-worker/steps"
    ]
    assert len(step_events) == 3
    assert step_events[0]["args"]["tokens"] == 4096
    text = to_text(tracer, steplogs=steplogs)
    assert "trainer-2-worker/steps" in text and "blocked_s=0.02" in text


def test_steplog_missing_file_and_write_errors(tmp_path):
    assert read_steplog(str(tmp_path / "absent.jsonl")) == []
    log = StepLog(str(tmp_path / "no-such-dir" / "steplog.jsonl"))
    log.record(0, wall_s=1.0)  # must not raise
    assert log.errors == 1


def test_agent_surfaces_steplog(tmp_path):
    """LocalProcessAgent.steplog_of reads the sandbox steplog the
    worker wrote (the scheduler merges it into /v1/debug/trace)."""
    from dcos_commons_tpu.agent.local import LocalProcessAgent

    agent = LocalProcessAgent(str(tmp_path), use_native=False)
    sandbox = agent.sandbox_of("trainer-0-worker")
    os.makedirs(sandbox, exist_ok=True)
    StepLog(os.path.join(sandbox, "steplog.jsonl")).record(
        7, wall_s=0.25, tokens=1024, blocked_s=0.003
    )
    records = agent.steplog_of("trainer-0-worker")
    assert records and records[0]["step"] == 7
    assert agent.steplog_of("never-launched") == []


def test_api_merges_steplogs_into_the_timeline():
    _runner, world = deploy_gang()
    from dcos_commons_tpu.http.api import SchedulerApi

    # the sim FakeAgent has no sandboxes; give it the surface the real
    # agent exposes so the API-level merge path is exercised
    world.agent.steplog_of = lambda name: (
        [{"step": 0, "t": 1.0, "wall_s": 0.5, "blocked_s": 0.1}]
        if name == "trainer-3-worker" else []
    )
    api = SchedulerApi(world.scheduler)
    code, body = api.debug_trace("chrome")
    assert code == 200
    lanes = {e["tid"] for e in body["traceEvents"]}
    assert "trainer-3-worker/steps" in lanes
    code, text = api.debug_trace(None)
    assert code == 200 and "trainer-3-worker/steps" in text
    assert api.debug_trace("bogus")[0] == 400
