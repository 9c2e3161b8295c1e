"""traceview: cross-layer span tracing + flight recorder + steplog.

One correlation chain from offer intake to the worker's pjit step
loop: the scheduler mints a trace id per offer cycle
(``scheduler/scheduler.py run_cycle``), threads it through offer
evaluation, the launch WAL, status fan-in, and plan-step transitions;
workers append per-step telemetry (``steplog.py``) that the exporters
merge into the same timeline.  The serve worker writes a steplog too:
one record a START-UP PHASE (``startup.py``: the launch carries its
trace id to the task, the worker stamps imports, backend, weights,
build, warm by program, ready), so the chain reads launch -> start-up
-> ``status:TASK_RUNNING`` -> ``step:... COMPLETE`` without a gap.
Surfaced at ``GET /v1/debug/trace`` (plain text) and
``GET /v1/debug/trace?fmt=chrome`` (Perfetto).
"""

from dcos_commons_tpu.trace.export import chrome_json, to_chrome, to_text
from dcos_commons_tpu.trace.recorder import (
    NULL_TRACER,
    LaunchRef,
    TraceRecorder,
)
from dcos_commons_tpu.trace.span import NullSpan, Span
from dcos_commons_tpu.trace.startup import (
    LAUNCH_TRACE_ENV,
    StartupClock,
    launch_context,
)
from dcos_commons_tpu.trace.steplog import (
    STEPLOG_NAME,
    StepLog,
    read_steplog,
    step_records,
)

__all__ = [
    "LAUNCH_TRACE_ENV",
    "NULL_TRACER",
    "STEPLOG_NAME",
    "LaunchRef",
    "NullSpan",
    "Span",
    "StartupClock",
    "StepLog",
    "TraceRecorder",
    "chrome_json",
    "launch_context",
    "read_steplog",
    "step_records",
    "to_chrome",
    "to_text",
]
