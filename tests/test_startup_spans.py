"""A deploy's own timeline (ISSUE 41): the launch carries its trace
context to the task, the serve worker stamps its start-up as seven
phases that touch, ``/stats`` ``startup`` and the sandbox's steplog
hold them, and the scheduler's exporters render them on
``<task>/startup`` under the launch's trace id.

The worker tests start REAL ``frameworks/jax/serve_worker.py``
processes on the CPU at a toy size, once for the module: one through
the scheduler (so the launch context is the scheduler's own) and one
by hand with none.
"""

import json
import os
import time
import urllib.request

import pytest

from dcos_commons_tpu.agent import LocalProcessAgent
from dcos_commons_tpu.agent.daemon import AgentDaemon
from dcos_commons_tpu.agent.remote import RemoteFleet
from dcos_commons_tpu.analysis import shardcheck
from dcos_commons_tpu.common import TaskInfo
from dcos_commons_tpu.health.detectors import StragglerDetector
from dcos_commons_tpu.offer.inventory import SliceInventory, TpuHost
from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
from dcos_commons_tpu.specification import from_yaml_file
from dcos_commons_tpu.storage import MemPersister
from dcos_commons_tpu.testing import (
    AdvanceCycles,
    ExpectDeploymentComplete,
    ExpectNoLaunches,
    SendTaskRunning,
    ServiceTestRunner,
)
from dcos_commons_tpu.trace import (
    LAUNCH_TRACE_ENV,
    StartupClock,
    StepLog,
    TraceRecorder,
    launch_context,
    read_steplog,
    step_records,
    to_chrome,
    to_text,
)
from dcos_commons_tpu.trace import startup as startup_module
from dcos_commons_tpu.trace.span import render_id
from dcos_commons_tpu.trace.startup import PHASES, warm_by_program
from dcos_commons_tpu.utils.compile_cache import CACHE_ENV

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ONE_POD_YAML = """
name: svc
pods:
  server:
    count: 1
    tasks:
      api:
        goal: RUNNING
        cmd: "python serve.py"
        cpus: 0.5
        memory: 64
"""

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


# -- warm, by program --------------------------------------------------


def sums(by_program):
    """The seconds that are not zero, without each program's source."""
    out = {}
    for program, kinds in by_program.items():
        seconds = {k: v for k, v in kinds.items() if v and k != "source"}
        if seconds:
            out[program] = seconds
    return out


@pytest.mark.parametrize("events,expected", [
    ([], {}),
    # a program's three parts, one after another
    ([(0.0, 2.0, "trace_s", "_prefill"), (2.0, 3.0, "lower_s", "_prefill"),
      (3.0, 3.5, "compile_s", "_prefill")],
     {"_prefill": {"trace_s": 2.0, "lower_s": 1.0, "compile_s": 0.5}}),
    # every jitted function a program calls raises its own trace event
    # while the program's is open: the outer one's time already
    ([(0.2, 0.4, "trace_s", "other"), (0.5, 0.9, "trace_s", "_decode"),
      (0.0, 2.0, "trace_s", "_prefill")],
     {"_prefill": {"trace_s": 2.0}}),
    # the same interval twice is one interval
    ([(0.0, 1.0, "trace_s", "other"), (0.0, 1.0, "trace_s", "other")],
     {"other": {"trace_s": 1.0}}),
    # a cache read counts where the compile it fired in counts ...
    ([(1.0, 1.5, "compile_s", "_decode"), (1.1, 1.4, "cache_read_s", "other")],
     {"_decode": {"compile_s": 0.5, "cache_read_s": 0.3}}),
    # ... and under `other` where no compile holds it
    ([(1.0, 1.5, "compile_s", "_decode"), (2.0, 2.25, "cache_read_s", "other")],
     {"_decode": {"compile_s": 0.5}, "other": {"cache_read_s": 0.25}}),
    # what compiles beside the two programs is `other`, once each
    ([(0.0, 0.1, "trace_s", "other"), (0.1, 0.2, "lower_s", "other"),
      (0.2, 0.4, "compile_s", "other"), (1.0, 1.1, "trace_s", "other")],
     {"other": {"trace_s": 0.2, "lower_s": 0.1, "compile_s": 0.2}}),
    # a stored program's own two events: a load, and the write that
    # follows a compile
    ([(0.0, 0.25, "load_s", "_prefill"), (1.0, 1.5, "compile_s", "_decode"),
      (1.5, 1.75, "store_s", "_decode")],
     {"_prefill": {"load_s": 0.25},
      "_decode": {"compile_s": 0.5, "store_s": 0.25}}),
], ids=["nothing", "three-parts", "nested-traces", "twice", "cache-read-held",
        "cache-read-unheld", "other", "stored-program"])
def test_warm_by_program(events, expected):
    got = sums(warm_by_program(events))
    assert set(got) == set(expected)
    for program, kinds in expected.items():
        assert got[program] == pytest.approx(kinds)


@pytest.mark.parametrize("kinds,source", [
    ((), None), (("trace_s", "lower_s"), None), (("load_s",), "stored"),
    (("trace_s", "lower_s", "compile_s", "store_s"), "compiled"),
    # an entry that loaded and was discarded: what is held was compiled
    (("load_s", "compile_s"), "compiled"),
])
def test_a_programs_source_is_how_it_came_to_be_held(kinds, source):
    events = [(float(i), i + 0.5, kind, "_decode")
              for i, kind in enumerate(kinds)]
    by_program = warm_by_program(events)
    assert by_program["_decode"]["source"] == source
    assert by_program["_prefill"]["source"] is None


def test_warm_by_program_always_names_both_programs_and_every_kind():
    empty = warm_by_program(())
    assert set(empty) == {"_prefill", "_decode", "other"}
    for kinds in empty.values():
        assert kinds == {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                         "cache_read_s": 0.0, "load_s": 0.0, "store_s": 0.0,
                         "source": None}


@pytest.mark.parametrize("fun_name,program", [
    ("_prefill", "_prefill"), ("jit(_prefill)", "_prefill"),
    ("pjit(_decode)", "_decode"), ("jit(convert_element_type)", "other"),
    ("", "other"), (None, "other"), ("_prefill_helper", "other"),
])
def test_a_programs_trace_and_its_lowering_are_one_program(fun_name, program):
    assert startup_module._program(fun_name) == program


# -- the clock ---------------------------------------------------------


def context(launched_ago=0.5, started_ago=2.0):
    now = time.time()
    return {LAUNCH_TRACE_ENV: json.dumps({
        "trace_id": "abcd123400000003", "span_id": "abcd123400000008",
        "scheduler_started": now - started_ago, "launched": now - launched_ago,
    })}


def run_clock(tmp_path, env):
    log = StepLog(str(tmp_path / "steplog.jsonl"))
    clock = StartupClock(env, steplog=log)
    for phase in ("backend_up", "weights", "build"):
        time.sleep(0.01)
        clock.mark(phase)
    with clock.warm():
        time.sleep(0.01)
        clock.on_duration(TRACE, 0.004, fun_name="_prefill")
        time.sleep(0.01)  # an event ends when the listener hears it
        clock.on_duration(COMPILE, 0.002, fun_name="jit(_prefill)")
    clock.ready()
    return clock, read_steplog(log.path)


def assert_phases_touch(stats, first):
    """Each phase's end stamp is the next one's start, their seconds
    sum to ``start_to_ready_s``."""
    ends, seconds = stats["phase_end"], stats["phase_s"]
    order = PHASES[PHASES.index(first):]
    for before, phase in zip(order, order[1:]):
        assert ends[before] + seconds[phase] == pytest.approx(
            ends[phase], abs=2e-6
        ), phase
    assert sum(seconds[p] for p in order) == pytest.approx(
        stats["start_to_ready_s"], abs=1e-6
    )
    assert ends["ready"] <= time.time()


def test_the_phases_touch_and_sum_under_a_launch_context(tmp_path):
    env = context(launched_ago=0.5)
    clock, records = run_clock(tmp_path, env)
    stats = clock.stats
    assert list(stats["phase_s"]) == list(PHASES) == list(stats["phase_end"])
    assert all(stats["phase_s"][p] is not None for p in PHASES)
    assert_phases_touch(stats, "launch")
    sent = json.loads(env[LAUNCH_TRACE_ENV])
    # launch: the hand-off until this process's OS start
    assert stats["phase_end"]["launch"] - stats["phase_s"]["launch"] == \
        pytest.approx(sent["launched"], abs=2e-6)
    assert stats["launched"] == sent["launched"]
    assert stats["scheduler_started"] == sent["scheduler_started"]
    assert stats["trace_id"] == "abcd123400000003"
    assert stats["warm"]["_prefill"]["trace_s"] == pytest.approx(0.004)
    assert stats["warm"]["_prefill"]["compile_s"] == pytest.approx(0.002)
    # one record a phase, under the launch's ids
    assert [r["phase"] for r in records] == ["startup." + p for p in PHASES]
    for record, phase in zip(records, PHASES):
        assert "step" not in record
        assert record["wall_s"] == stats["phase_s"][phase]
        assert record["t"] == stats["phase_end"][phase]
        assert record["trace_id"] == "abcd123400000003"
        assert record["parent_id"] == "abcd123400000008"
    assert records[5]["trace_s"] == pytest.approx(0.004)


def test_under_forty_numbers(tmp_path):
    clock, _records = run_clock(tmp_path, context())

    def numbers(value):
        if isinstance(value, dict):
            return sum(numbers(v) for v in value.values())
        return int(isinstance(value, (int, float)))

    assert 25 <= numbers(clock.stats) < 40
    json.dumps(clock.stats)


@pytest.mark.parametrize("env", [
    {}, {LAUNCH_TRACE_ENV: ""}, {LAUNCH_TRACE_ENV: "not json"},
    {LAUNCH_TRACE_ENV: "[1, 2]"},
    {LAUNCH_TRACE_ENV: json.dumps({"launched": "soon"})},
], ids=["absent", "empty", "garbage", "no-object", "no-number"])
def test_without_a_launch_context_the_launch_reads_null(tmp_path, env):
    clock, records = run_clock(tmp_path, env)
    stats = clock.stats
    assert stats["phase_s"]["launch"] is None
    assert stats["launched"] is None and stats["scheduler_started"] is None
    assert stats["trace_id"] == ""
    # the sum runs from the process's start, which still has its stamp
    assert stats["phase_end"]["launch"] is not None
    assert_phases_touch(stats, "imports")
    assert [r["phase"] for r in records] == [
        "startup." + p for p in PHASES[1:]
    ]


def test_imports_is_the_process_age(tmp_path):
    before = startup_module.process_age_s()
    clock, _records = run_clock(tmp_path, {})
    after = startup_module.process_age_s()
    assert before <= clock.stats["phase_s"]["imports"] <= after
    assert startup_module.process_started_wall() == pytest.approx(
        time.time() - after, abs=0.05
    )


def test_where_proc_is_absent_the_modules_first_line_stands(monkeypatch):
    import builtins

    real_open = builtins.open

    def no_proc(path, *args, **kwargs):
        if str(path).startswith("/proc/"):
            raise FileNotFoundError(path)
        return real_open(path, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", no_proc)
    assert startup_module.process_age_s() == pytest.approx(
        time.time() - startup_module.IMPORTED_WALL, abs=0.05
    )


def test_a_steplog_that_cannot_be_written_takes_nothing_down(tmp_path):
    log = StepLog(str(tmp_path / "no" / "such" / "dir" / "steplog.jsonl"))
    clock = StartupClock({}, steplog=log)
    clock.mark("backend_up")
    assert log.errors >= 2
    assert clock.stats["phase_s"]["backend_up"] is not None


# -- after ready -------------------------------------------------------


def test_only_a_compile_after_ready_counts_and_is_a_span(tmp_path):
    tracer = TraceRecorder(capacity=16, service="serve")
    log = StepLog(str(tmp_path / "steplog.jsonl"))
    clock = StartupClock({}, steplog=log)
    # before the warm-up: the weights' and the arena's own compiles
    clock.on_duration(COMPILE, 0.5, fun_name="jit(init)")
    with clock.warm():
        clock.on_duration(COMPILE, 0.25, fun_name="jit(_decode)")
    assert clock.stats["compiles_after_ready"] == 0
    clock.ready(tracer)
    clock.on_duration(TRACE, 0.125, fun_name="_decode")
    clock.on_duration(LOWER, 0.125, fun_name="jit(_decode)")
    clock.on_duration(CACHE_READ, 0.01)
    clock.on_duration("/jax/some/other/event", 9.0)
    assert clock.stats["compiles_after_ready"] == 0
    clock.on_duration(COMPILE, 0.75, fun_name="jit(_decode)")
    clock.on_duration(COMPILE, 0.5, fun_name="jit(_prefill)")
    assert clock.stats["compiles_after_ready"] == 2
    assert clock.stats["compile_after_ready_s_sum"] == pytest.approx(1.25)
    # the two programs by how they came to be held, `init` is neither
    assert clock.stats["programs"] == {"stored": 0, "compiled": 3}
    # the warm-up's sums are closed
    assert clock.stats["warm"]["_decode"]["compile_s"] == pytest.approx(0.25)
    spans = [s for s in tracer.snapshot() if s.name == "engine.compile"]
    assert [s.attrs["fun_name"] for s in spans] == [
        "jit(_decode)", "jit(_prefill)"
    ]
    assert spans[0].duration_s == pytest.approx(0.75)
    assert "engine.compile" in to_text(tracer, service="serve")


def test_the_listener_hears_jax(tmp_path):
    """On ``jax.monitoring`` itself: a program traced inside the
    warm-up is named, the functions it calls are not counted twice,
    and a fresh function after ``ready`` is a compile the warm-up
    missed."""
    import jax
    import jax.numpy as jnp

    clock = StartupClock({}, steplog=StepLog(str(tmp_path / "s.jsonl")))
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    try:
        def _prefill(x):
            return jnp.tanh(x) * jnp.float32(41.5)

        def _decode(x):
            return jnp.cos(x) + jnp.float32(41.25)

        with clock.warm():
            began = time.monotonic()
            jax.block_until_ready(jax.jit(_prefill)(jnp.ones(7)))
            jax.block_until_ready(jax.jit(_decode)(jnp.ones(7)))
            took = time.monotonic() - began
        warm = clock.stats["warm"]
        for program in ("_prefill", "_decode"):
            for kind in ("trace_s", "lower_s", "compile_s"):
                assert warm[program][kind] > 0, (program, kind)
        assert sum(
            kinds[k] for kinds in warm.values()
            for k in ("trace_s", "lower_s", "compile_s")
        ) <= took
        clock.ready()
        jax.block_until_ready(jax.jit(_prefill)(jnp.ones(7)))  # cached
        assert clock.stats["compiles_after_ready"] == 0

        def forced_retrace(x):
            return jnp.sin(x) - jnp.float32(41.125)

        jax.block_until_ready(jax.jit(forced_retrace)(jnp.ones(7)))
        assert clock.stats["compiles_after_ready"] >= 1
        assert clock.stats["compile_after_ready_s_sum"] > 0
    finally:
        jax.monitoring.unregister_event_duration_listener(clock.on_duration)


# -- the launch carries its context ------------------------------------


def deploy_one_pod():
    runner = ServiceTestRunner(ONE_POD_YAML)
    world = runner.run([
        AdvanceCycles(1),
        SendTaskRunning("server-0-api"),
        ExpectDeploymentComplete(),
    ])
    return runner, world


def test_the_launch_request_carries_the_launch_spans_ids():
    before = time.time()
    _runner, world = deploy_one_pod()
    info, = world.agent.launched
    sent = world.agent.payloads[info.task_id]["launch_env"]
    assert set(sent) == {LAUNCH_TRACE_ENV}
    context = json.loads(sent[LAUNCH_TRACE_ENV])
    assert set(context) == {
        "trace_id", "span_id", "scheduler_started", "launched",
    }
    launch, = [s for s in world.scheduler.tracer.snapshot()
               if s.name.startswith("launch:")]
    assert context["trace_id"] == render_id(launch.trace_id)
    assert context["span_id"] == render_id(launch.span_id)
    assert before <= context["launched"] <= time.time()
    # this process's OS start: before any of this ran, and not long ago
    assert context["scheduler_started"] < before
    assert context["scheduler_started"] == pytest.approx(
        startup_module.process_started_wall()
    )
    # the hand-off lies inside the launch span
    start = world.scheduler.tracer.wall_of(launch.start_s)
    assert start - 0.01 <= context["launched"] <= \
        start + launch.duration_s + 0.01


def test_a_recorder_that_is_off_sends_the_stamps_without_ids():
    context = json.loads(launch_context(TraceRecorder(capacity=0).span("x")))
    assert context["trace_id"] == "" and context["span_id"] == ""
    assert context["launched"] >= context["scheduler_started"]


def test_the_context_is_persisted_nowhere_and_relaunches_nothing():
    runner, world = deploy_one_pod()
    info = world.state_store.fetch_task("server-0-api")
    assert LAUNCH_TRACE_ENV not in info.env
    assert LAUNCH_TRACE_ENV not in json.dumps(info.to_dict())
    # no state-store entry, plan checkpoint or WAL record holds it
    dump = world.persister.dump()
    assert dump
    for path, value in dump.items():
        assert LAUNCH_TRACE_ENV.encode() not in (value or b""), path
        assert LAUNCH_TRACE_ENV not in path
    # a second offer cycle, and a third: nothing differs, nothing starts
    runner.run([AdvanceCycles(3)])
    assert len(world.agent.launched) == 1
    # a scheduler restart over the running pod: the same
    restarted = runner.restart()
    again = restarted.run([
        AdvanceCycles(3), ExpectNoLaunches(), ExpectDeploymentComplete(),
    ])
    assert len(again.agent.launched) == 1
    assert not again.agent.kills


def echo_task(name):
    return TaskInfo(
        name=name, task_id=f"{name}__1", agent_id="h0",
        command='printf "%s|%s" "$LAUNCH_TRACE" "$TASK_OWN" > out.txt',
        env={"TASK_OWN": "kept"},
    )


def wait_for(path, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(path) and os.path.getsize(path):
            with open(path) as f:
                return f.read()
        time.sleep(0.05)
    raise AssertionError(f"{path} never appeared")


@pytest.mark.parametrize("sent", [{LAUNCH_TRACE_ENV: '{"launched": 7}'}, None],
                         ids=["with-context", "without"])
def test_the_local_agent_merges_it_at_exec_time_only(tmp_path, sent):
    agent = LocalProcessAgent(str(tmp_path / "sbx"))
    info = echo_task("app-0-server")
    try:
        agent.launch_one(info, launch_env=sent)
        out = wait_for(str(tmp_path / "sbx" / info.name / "out.txt"))
    finally:
        agent.shutdown()
    assert out == ('{"launched": 7}|kept' if sent else "|kept")
    # the durable launch record (what a restarted agent rebuilds its
    # tasks from) holds the TaskInfo, and the TaskInfo never had it
    record = str(tmp_path / "sbx" / info.name / ".super" / info.task_id)
    for name in os.listdir(record):
        with open(os.path.join(record, name), errors="replace") as f:
            assert "launched" not in f.read(), name


def test_the_remote_daemons_wire_carries_it(tmp_path):
    daemon = AgentDaemon("h0", str(tmp_path / "sandbox-h0")).start()
    try:
        fleet = RemoteFleet()
        fleet.add_host("h0", daemon.url)
        info = echo_task("app-0-server")
        fleet.launch_one(
            info, launch_env={LAUNCH_TRACE_ENV: '{"launched": 9}'}
        )
        out = wait_for(str(tmp_path / "sandbox-h0" / info.name / "out.txt"))
        assert out == '{"launched": 9}|kept'
        fleet.kill(info.task_id)
    finally:
        daemon.stop()


# -- the exporters -----------------------------------------------------


def phase_records(trace_id="abcd123400000003", parent="abcd123400000008",
                  start=1000.0):
    records, t = [], start
    for phase, seconds in zip(PHASES, (0.2, 3.0, 12.0, 1.9, 0.4, 4.1, 0.01)):
        t += seconds
        records.append({
            "phase": "startup." + phase, "t": t, "wall_s": seconds,
            "trace_id": trace_id, "parent_id": parent,
        })
    return records


def test_to_chrome_puts_phases_on_the_startup_lane_under_the_launch():
    steplogs = {"server-0-api": phase_records() + [
        {"step": 3, "t": 1030.0, "wall_s": 0.5, "tokens": 64},
    ]}
    doc = to_chrome(TraceRecorder(capacity=4), steplogs=steplogs)
    json.dumps(doc)
    lane = [e for e in doc["traceEvents"] if e["tid"] == "server-0-api/startup"]
    assert [e["name"] for e in lane] == ["startup." + p for p in PHASES]
    for event in lane:
        assert event["ph"] == "X"
        assert event["args"]["trace_id"] == "abcd123400000003"
        assert event["args"]["parent_id"] == "abcd123400000008"
    # they touch on the exported clock too
    for before, after in zip(lane, lane[1:]):
        assert abs(before["ts"] + before["dur"] - after["ts"]) <= 2
    assert lane[0]["ts"] == int(1000.0 * 1e6)
    # a step stays a step
    step, = [e for e in doc["traceEvents"] if e["tid"] == "server-0-api/steps"]
    assert step["name"] == "step 3"


def test_to_text_reads_as_one_chain():
    recorder = TraceRecorder(capacity=8)
    with recorder.span("launch:server-[0]:[api]", track="scheduler") as launch:
        pass
    records = phase_records(
        trace_id=render_id(launch.trace_id),
        parent=render_id(launch.span_id),
        start=recorder.wall_of(launch.end_s),
    )
    recorder.event(
        "status:TASK_RUNNING", trace_id=launch.trace_id,
        parent_id=launch.span_id, track="server-0",
    )
    text = to_text(recorder, steplogs={"server-0-api": records})
    rows = [line.split() for line in text.splitlines()
            if not line.startswith("#")]
    names = [row[4] for row in rows]
    assert names[0] == "launch:server-[0]:[api]"
    assert names[1:3] == ["status:TASK_RUNNING", "startup.launch"] or \
        names[1:3] == ["startup.launch", "status:TASK_RUNNING"]
    assert [n for n in names if n.startswith("startup.")] == [
        "startup." + p for p in PHASES
    ]
    # one trace id from the launch to the last phase
    tail = render_id(launch.trace_id)[-8:]
    assert {row[2] for row in rows} == {tail}
    assert {row[3] for row in rows if row[4].startswith("startup.")} == {
        "server-0-api/startup"
    }
    # a steplog's own steps keep their column
    text = to_text(recorder, steplogs={"t": [{"step": 1, "t": 5.0, "wall_s": 1}]})
    assert " steplog " in text and "t/steps" in text


# -- what else reads steplogs ------------------------------------------


def test_step_records_leaves_the_phases_out():
    steps = [{"step": i, "wall_s": 0.01, "blocked_s": 0.0} for i in range(4)]
    bare = [{"wall_s": 0.01}]  # hand-made: no step index, still a step
    assert step_records(phase_records() + steps + bare) == steps + bare
    assert step_records([]) == []


def test_stepcompare_is_as_it_was_with_phase_records_in_the_log():
    steps = [{"step": i, "wall_s": 0.010, "blocked_s": 0.001}
             for i in range(20)]
    alone = shardcheck.stepcompare(None, steps, floor_us=9000.0)
    mixed = shardcheck.stepcompare(
        None, phase_records() + steps, floor_us=9000.0
    )
    assert mixed == alone
    assert mixed["steps"] == 19 if "steps" in mixed else True


def test_a_twelve_second_backend_start_is_no_straggler():
    fast = [{"step": i, "wall_s": 1.0, "blocked_s": 0.9} for i in range(8)]
    fleet = {f"h{i}": list(fast) for i in range(3)}
    alone = StragglerDetector(threshold=2.0)
    assert alone.observe(fleet) == []
    # h2's worker wrote its start-up into the same steplog
    mixed = StragglerDetector(threshold=2.0)
    events = mixed.observe(dict(fleet, h2=phase_records() + fast))
    assert events == []
    assert mixed.scores == alone.scores
    # and a host that only started (no step yet) has no score at all
    only = StragglerDetector(threshold=2.0)
    only.observe(dict(fleet, h3=phase_records()))
    assert "h3" not in only.scores


# -- a real worker, on the CPU -----------------------------------------

TINY_ENV = {
    "FRAMEWORK_NAME": "tiny-serve",
    "VOCAB": "64", "D_MODEL": "32", "N_LAYERS": "2", "SEQ_LEN": "64",
    "MAX_LEN": "48", "MAX_NEW_TOKENS": "8", "SERVE_BATCH": "1",
    "KV_DTYPE": "native", "SERVE_TRACE_CAPACITY": "64",
}

# The task's entry in these tests: the program's serve_worker.main()
# as it is, beside a thread that compiles one fresh function when the
# test asks (a file `retrace` in the sandbox) and says when it has.
ENTRY = '''
import importlib.util, os, sys, threading, time

sys.path.insert(0, {repo!r})


def retrace_when_asked():
    while not os.path.exists("retrace"):
        time.sleep(0.05)
    import jax
    import jax.numpy as jnp

    def forced_retrace(x):
        return jnp.sin(x) * jnp.float32(41.0625)

    jax.block_until_ready(jax.jit(forced_retrace)(jnp.ones(5)))
    with open("retraced", "w") as f:
        f.write("done")


threading.Thread(target=retrace_when_asked, daemon=True).start()
spec = importlib.util.spec_from_file_location(
    "program_serve_worker",
    os.path.join({repo!r}, "frameworks", "jax", "serve_worker.py"),
)
program = importlib.util.module_from_spec(spec)
spec.loader.exec_module(program)
raise SystemExit(program.main())
'''


def get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as response:
        return response.read().decode()


@pytest.fixture(scope="module")
def workers(tmp_path_factory):
    """One worker launched by the scheduler (port 23310..) and one by
    hand without a launch context, started together."""
    root = tmp_path_factory.mktemp("startup")
    # compile caches (and program stores) of these workers' own: what
    # an earlier run of the tests left in the checkout's would be
    # LOADED, and a start that loads traces nothing
    patch = pytest.MonkeyPatch()
    patch.setenv(CACHE_ENV, str(root / "cache"))
    entry_dir = root / "entry"
    entry_dir.mkdir()
    (entry_dir / "serve_worker.py").write_text(ENTRY.format(repo=REPO))
    env = dict(TINY_ENV, JAX_FRAMEWORK_DIR=str(entry_dir))
    spec = from_yaml_file(
        os.path.join(REPO, "frameworks", "jax", "svc_serve.yml"), env
    )
    persister = MemPersister()
    builder = SchedulerBuilder(
        spec,
        SchedulerConfig(sandbox_root=str(root / "sbx"), backoff_enabled=False),
        persister,
    )
    builder.set_inventory(SliceInventory([TpuHost(
        host_id="h0", hostname="127.0.0.1", generation="v5e",
        grid=(0, 0), chip_block=(1, 1), cpus=8.0, memory_mb=16384,
        ports=((23310, 23350),),
    )]))
    agent = LocalProcessAgent(str(root / "sbx"))
    builder.set_agent(agent)
    scheduler = builder.build()
    bare_agent = LocalProcessAgent(str(root / "bare"))
    try:
        scheduler.run_cycle()
        launched = scheduler.state_store.fetch_task("server-0-api")
        def launch_bare(name, cache):
            # a hand-built launch: no context rides it
            bare_agent.launch([TaskInfo(
                name=name, task_id=name + "__1", agent_id="h0",
                command=launched.command,
                env=dict(launched.env, PORT_HTTP="0",
                         **{CACHE_ENV: str(root / cache)}),
            )])
            return str(root / "bare" / name)

        launch_bare("bare-0-api", "bare-cache")
        deadline = time.monotonic() + 240
        bare_ready = str(root / "bare" / "bare-0-api" / "ready")
        while time.monotonic() < deadline:
            scheduler.run_cycle()
            if scheduler.deploy_manager.get_plan().is_complete and \
                    os.path.exists(bare_ready):
                break
            time.sleep(0.1)
        stderr = root / "sbx" / "server-0-api" / "stderr"
        assert scheduler.deploy_manager.get_plan().is_complete, (
            stderr.read_text()[-800:] if stderr.exists() else "no stderr"
        )
        assert os.path.exists(bare_ready), "the bare worker never warmed"
        port = int(launched.env["PORT_HTTP"])
        with open(root / "bare" / "bare-0-api" / "servestats.json") as f:
            bare_port = json.load(f)["http_port"]
        yield {
            "scheduler": scheduler, "agent": agent, "persister": persister,
            "url": f"http://127.0.0.1:{port}",
            "bare_url": f"http://127.0.0.1:{bare_port}",
            "sandbox": str(root / "sbx" / "server-0-api"),
            "bare_sandbox": str(root / "bare" / "bare-0-api"),
            "launch_bare": launch_bare,
        }
    finally:
        agent.shutdown()
        bare_agent.shutdown()
        patch.undo()


def test_a_deployed_worker_has_every_phase_and_they_touch(workers):
    stats = json.loads(get(workers["url"] + "/stats"))
    startup = stats["startup"]
    assert list(startup["phase_s"]) == list(PHASES)
    for phase in PHASES:
        assert startup["phase_s"][phase] is not None, phase
        assert startup["phase_s"][phase] >= (0 if phase != "launch" else -1)
    assert_phases_touch(startup, "launch")
    # from the hand-off: the first phase starts at `launched`
    assert startup["phase_end"]["launch"] - startup["phase_s"]["launch"] == \
        pytest.approx(startup["launched"], abs=2e-6)
    assert startup["scheduler_started"] <= startup["launched"]
    # imports and the warm-up are the seconds of a CPU start
    assert startup["phase_s"]["imports"] > 0.2
    assert startup["phase_s"]["warm"] > 0.05
    # what warm_s times, stamped where warm_s is
    assert startup["phase_s"]["warm"] == pytest.approx(stats["warm_s"], abs=0.1)


def test_warm_by_program_names_both_programs(workers):
    startup = json.loads(get(workers["url"] + "/stats"))["startup"]
    for program in ("_prefill", "_decode"):
        kinds = startup["warm"][program]
        assert kinds["trace_s"] > 0 and kinds["lower_s"] > 0, program
        assert kinds["compile_s"] > 0, program
        # a cache of its own, found empty: compiled, and stored
        assert kinds["source"] == "compiled" and kinds["store_s"] > 0
        assert kinds["load_s"] == 0
    assert startup["programs"] == {"stored": 0, "compiled": 2}
    total = sum(
        kinds[k] for kinds in startup["warm"].values()
        for k in ("trace_s", "lower_s", "compile_s")
    )
    # the warm-up is its three parts and the programs' first runs
    assert 0.5 * startup["phase_s"]["warm"] < total <= \
        startup["phase_s"]["warm"]


def test_the_worker_runs_under_its_launchs_trace_id(workers):
    scheduler = workers["scheduler"]
    startup = json.loads(get(workers["url"] + "/stats"))["startup"]
    launch, = [s for s in scheduler.tracer.snapshot()
               if s.name.startswith("launch:")]
    assert startup["trace_id"] == render_id(launch.trace_id)
    assert startup["span_id"] == render_id(launch.span_id)
    records = workers["agent"].steplog_of("server-0-api")
    assert [r["phase"] for r in records] == ["startup." + p for p in PHASES]
    assert {r["trace_id"] for r in records} == {render_id(launch.trace_id)}
    assert {r["parent_id"] for r in records} == {render_id(launch.span_id)}


@pytest.mark.parametrize("fmt", ["text", "chrome"])
def test_the_schedulers_debug_trace_shows_the_chain(workers, fmt):
    from dcos_commons_tpu.http.api import SchedulerApi

    scheduler = workers["scheduler"]
    code, body = SchedulerApi(scheduler).debug_trace(fmt)
    assert code == 200
    launch, = [s for s in scheduler.tracer.snapshot()
               if s.name.startswith("launch:")]
    trace = render_id(launch.trace_id)
    chain = ["launch:"] + ["startup." + p for p in PHASES] + [
        "status:TASK_RUNNING", "step:",
    ]
    if fmt == "chrome":
        events = [e for e in body["traceEvents"]
                  if e["args"].get("trace_id") == trace]
        names = [e["name"] for e in events]
        lanes = {e["tid"] for e in events if e["name"].startswith("startup.")}
    else:
        rows = [line.split() for line in body.splitlines()
                if not line.startswith("#")]
        rows = [r for r in rows if r[2] == trace[-8:]]
        names = [r[4] for r in rows]
        lanes = {r[3] for r in rows if r[4].startswith("startup.")}
        complete = [r for r in rows if r[4].startswith("step:")
                    and "to=COMPLETE" in r]
        assert complete
    assert lanes == {"server-0-api/startup"}
    # each link of the chain is there; from the imports on, in order
    # after the launch (the process's OS start has the resolution of a
    # clock tick, 10 ms, so a process forked within one of the
    # hand-off may sort before it)
    at = names.index(next(n for n in names if n.startswith("launch:")))
    for link in chain:
        found = [i for i, n in enumerate(names) if n.startswith(link)]
        assert found, (link, names)
        if link.startswith("startup.") and link not in (
                "startup.launch", "startup.imports"):
            assert found[0] > at, (link, names)
            at = found[0]
    # ready comes before the status that says so and the step it completes
    ready = names.index("startup.ready")
    assert any(n.startswith("status:TASK_RUNNING") for n in names[ready:])
    assert any(n.startswith("step:") for n in names[ready:])


def test_nothing_of_the_launch_context_is_kept_and_nothing_relaunches(workers):
    scheduler, agent = workers["scheduler"], workers["agent"]
    info = scheduler.state_store.fetch_task("server-0-api")
    assert LAUNCH_TRACE_ENV not in info.env
    for path, value in workers["persister"].dump().items():
        assert LAUNCH_TRACE_ENV.encode() not in (value or b""), path
    active = agent.active_task_ids()
    for _ in range(3):
        scheduler.run_cycle()
    assert agent.active_task_ids() == active == {info.task_id}
    assert scheduler.state_store.fetch_task("server-0-api").task_id == \
        info.task_id


def test_a_compile_after_ready_is_counted_and_named(workers):
    before = json.loads(get(workers["url"] + "/stats"))["startup"]
    assert before["compiles_after_ready"] == 0
    # serving what the warm-up compiled compiles nothing
    request = urllib.request.Request(
        workers["url"] + "/generate",
        data=json.dumps({"tokens": [[1, 2, 3]], "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(request, timeout=60) as response:
        assert len(json.loads(response.read())["tokens"][0]) == 4
    served = json.loads(get(workers["url"] + "/stats"))["startup"]
    assert served["compiles_after_ready"] == 0
    # the sandbox's snapshot (written when the loop has worked) holds
    # the same account, for the scheduler's /v1/debug/serving
    with open(os.path.join(workers["sandbox"], "servestats.json")) as f:
        assert json.load(f)["startup"]["phase_s"] == served["phase_s"]
    with open(os.path.join(workers["sandbox"], "retrace"), "w") as f:
        f.write("now")
    wait_for(os.path.join(workers["sandbox"], "retraced"), timeout_s=60)
    after = json.loads(get(workers["url"] + "/stats"))["startup"]
    assert after["compiles_after_ready"] >= 1
    assert after["compile_after_ready_s_sum"] > 0
    # the phases are as they were
    assert after["phase_s"] == before["phase_s"]
    trace = get(workers["url"] + "/trace")
    assert "engine.compile" in trace
    assert "fun_name=jit(forced_retrace)" in trace


def test_a_worker_without_a_launch_context_starts_and_reads_null(workers):
    startup = json.loads(get(workers["bare_url"] + "/stats"))["startup"]
    assert startup["phase_s"]["launch"] is None
    assert startup["launched"] is None
    assert startup["scheduler_started"] is None
    assert startup["trace_id"] == "" and startup["span_id"] == ""
    assert_phases_touch(startup, "imports")
    records = read_steplog(
        os.path.join(workers["bare_sandbox"], "steplog.jsonl")
    )
    assert [r["phase"] for r in records] == [
        "startup." + p for p in PHASES[1:]
    ]


def test_the_next_worker_of_that_cache_loads_both_programs(workers):
    """A relaunch, a rolling update, a scale-out replica: the store
    beside the first worker's compile cache holds both programs, and a
    worker started on it traces, lowers and compiles neither."""
    sandbox = workers["launch_bare"]("bare-1-api", "cache")
    wait_for(os.path.join(sandbox, "ready"), timeout_s=240)
    with open(os.path.join(sandbox, "servestats.json")) as f:
        url = f"http://127.0.0.1:{json.load(f)['http_port']}"
    startup = json.loads(get(url + "/stats"))["startup"]
    assert startup["programs"] == {"stored": 2, "compiled": 0}
    for program in ("_prefill", "_decode"):
        kinds = startup["warm"][program]
        assert kinds["source"] == "stored" and kinds["load_s"] > 0
        assert kinds["trace_s"] == kinds["lower_s"] == 0
        assert kinds["compile_s"] == kinds["store_s"] == 0
    # and serves what the worker that compiled them serves
    said = []
    for worker in (workers["url"], url):
        request = urllib.request.Request(
            worker + "/generate",
            data=json.dumps(
                {"tokens": [[5, 6, 7, 8]], "max_new_tokens": 6}
            ).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(request, timeout=60) as response:
            said.append(json.loads(response.read())["tokens"])
    assert said[0] == said[1] and len(said[0][0]) == 6
    after = json.loads(get(url + "/stats"))["startup"]
    assert after["compiles_after_ready"] == 0
    assert after["programs"] == {"stored": 2, "compiled": 0}
