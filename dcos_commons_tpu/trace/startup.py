"""A worker's start-up, told by the worker, under its launch's trace id.

The scheduler's flight recorder ends at ``launch:<pod>`` and picks up
again at ``status:TASK_RUNNING``; what lies between (the sandbox, the
interpreter, the backend, the weights, the compiles) is the worker's,
and most of a deploy's seconds.  Two halves meet here:

* ``launch_context(span)`` is what the scheduler sends WITH a launch
  (``LAUNCH_TRACE`` in the task's environment at exec time, the way
  secret env rides: never part of the persisted TaskInfo): the launch
  span's ids as the exporters render them, the wall time at which the
  scheduler's process began and the wall time of the hand-off.
* ``StartupClock`` is what the worker stamps its start-up with: seven
  phases that TOUCH (each ends where the next begins, so their sum is
  launch -> ready and no instant is nobody's), durations on
  ``time.monotonic()``, end stamps on the wall clock of the steplog's
  ``t`` and the recorder's exported ``ts``.  Each phase is one record
  of the sandbox's ``steplog.jsonl`` (``phase`` in place of ``step``),
  which ``export.py`` renders on a ``<task>/startup`` lane under the
  launch's trace id, and the whole is ``/stats`` -> ``startup``.

Inside ``warm`` the clock listens to ``jax.monitoring``'s duration
events and sums, by program, the host's tracing, its lowering and the
backend compile (a cache read where it was one), and the two events a
stored program raises itself (utils/stored_program.py): the load of an
executable the store held, and the write of one it did not.  A program
that was loaded reads ``source`` ``"stored"`` and no tracing, lowering
or compile at all; ``programs`` counts both kinds.  After ``ready`` the
same listener counts what still compiles or loads.  It is never called
in steady state: a program that is held raises no event.

No jax import here: the worker registers ``on_duration`` itself.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

from dcos_commons_tpu.trace.span import render_id
from dcos_commons_tpu.trace.steplog import StepLog

# this process's start where /proc says none: the package's first
# import of the tracing code, early in every entry point
IMPORTED_WALL = time.time()
LAUNCH_TRACE_ENV = "LAUNCH_TRACE"
PHASES = (
    "launch", "imports", "backend_up", "weights", "build", "warm", "ready",
)
# the programs ``warm`` is split by; whatever else compiles inside it
# (the pool's small constants) is ``other``
WARM_PROGRAMS = ("_prefill", "_decode")
WARM_KINDS = (
    "trace_s", "lower_s", "compile_s", "cache_read_s", "load_s", "store_s",
)
# raised by a stored program (utils/stored_program.py), with
# ``fun_name`` as JAX's own compile events carry it: an executable
# deserialized from the store, and one serialized and written to it
LOAD_EVENT = "/dcos_commons_tpu/stored_program/load_duration"
STORE_EVENT = "/dcos_commons_tpu/stored_program/store_duration"
_EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    LOAD_EVENT: "load_s",
    STORE_EVENT: "store_s",
}
# how a program came to be held: a compile (a cache read or not) wins
# over a load, which was then of an entry that was discarded
_SOURCES = {"compile_s": "compiled", "load_s": "stored"}
# An event's start is its end (stamped here, when the listener is
# called) less the duration JAX measured on its own clock: the two
# ends of a nested event may each be off by the call between them.
_NESTING_SLACK_S = 1e-3

_Event = Tuple[float, float, str, str]  # start, end, kind, program


def process_age_s() -> float:
    """Seconds since the OS started this process: ``/proc/self/stat``
    field 22 (start time, in clock ticks since boot) against
    ``CLOCK_BOOTTIME``; since this module's import where ``/proc`` is
    absent."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # the command's name may hold spaces and brackets: count
            # the fields from its closing one (field 2)
            fields = f.read().rsplit(b")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return time.time() - IMPORTED_WALL


@functools.lru_cache(maxsize=None)
def process_started_wall() -> float:
    """Wall time at which this process began (read once: every launch
    of one scheduler carries the same stamp)."""
    return time.time() - process_age_s()


def launch_context(span) -> str:
    """``LAUNCH_TRACE``'s value for a task handed to the agent NOW:
    the launch span's ids as the exporters render them ("" from a
    recorder that is off), ``scheduler_started`` and ``launched``."""
    # a recorder that is off hands out a span of trace 0
    trace_id = getattr(span, "trace_id", 0)
    return json.dumps({
        "trace_id": render_id(trace_id),
        "span_id": render_id(trace_id and span.span_id),
        "scheduler_started": round(process_started_wall(), 6),
        "launched": round(time.time(), 6),
    })


def _launch_of(env: Mapping[str, str]) -> dict:
    """The launch context a task was started with; {} for a bare
    launch or a value that does not parse."""
    try:
        # sent by the scheduler with a launch request and never part
        # of the persisted TaskInfo (JSON: the launch span's trace_id
        # and span_id, scheduler_started and launched in wall seconds).
        # Absent from a bare launch, which reads null in `launch`
        context = json.loads(env.get("LAUNCH_TRACE") or "{}")
    except ValueError:
        return {}
    return context if isinstance(context, dict) else {}


def _wall(value) -> Optional[float]:
    return float(value) if isinstance(value, (int, float)) else None


def _program(fun_name: str) -> str:
    """``jit(_prefill)`` (lowering, compile) and ``_prefill`` (trace)
    are one program."""
    name = str(fun_name or "")
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name if name in WARM_PROGRAMS else "other"


def warm_by_program(events: Iterable[_Event]) -> Dict[str, Dict[str, object]]:
    """Sum ``warm``'s events by program and kind, and say each
    program's ``source``: ``"compiled"``, ``"stored"`` (loaded, and
    nothing compiled) or None (no event of either).  A trace inside a
    trace (every jitted function a program calls raises its own event
    while the program's is open) is the outer one's time already and
    is left out; a cache read counts where the compile it fired in
    counts, or under ``other`` where none holds it."""
    out = {
        program: dict.fromkeys(WARM_KINDS, 0.0)
        for program in WARM_PROGRAMS + ("other",)
    }
    seen = {(kind, program) for _start, _end, kind, program in events}
    spans = sorted(
        (e for e in events if e[2] != "cache_read_s"),
        key=lambda e: (e[0], -e[1]),
    )
    compiles: List[_Event] = []
    reach = float("-inf")  # the latest end among the events before
    for event in spans:
        start, end, kind, program = event
        if end <= reach + _NESTING_SLACK_S:
            continue
        reach = max(reach, end)
        out[program][kind] += end - start
        if kind == "compile_s":
            compiles.append(event)
    for start, end, kind, _other in events:
        if kind != "cache_read_s":
            continue
        holder = next(
            (c[3] for c in compiles
             if c[0] - _NESTING_SLACK_S <= start
             and end <= c[1] + _NESTING_SLACK_S),
            "other",
        )
        out[holder]["cache_read_s"] += end - start
    return {
        program: dict(
            {kind: round(s, 6) for kind, s in kinds.items()},
            source=next(
                (source for kind, source in _SOURCES.items()
                 if (kind, program) in seen), None,
            ),
        )
        for program, kinds in out.items()
    }


class StartupClock:
    """Stamps one worker's start-up.  Built where ``main()`` is past
    its imports, which closes ``launch`` (hand-off -> this process's
    OS start: two processes, perhaps two hosts' wall clocks) and
    ``imports``; ``mark(phase)`` closes each later phase where the
    next begins, ``warm()`` wraps the warm-up and ``ready(tracer)``
    closes the last and arms the after-ready counts.

    ``stats`` is the ``startup`` key of ``/stats``: every key is there
    from the first moment (``None`` until its phase has ended, or
    where no launch context came) and values are assigned in place, so
    a snapshot taken from another thread never meets a dict that
    changes size.  Telemetry never takes a worker down: a steplog that
    cannot be written is counted by ``StepLog``.
    """

    def __init__(
        self,
        env: Optional[Mapping[str, str]] = None,
        steplog: Optional[StepLog] = None,
    ):
        now, wall, age = time.monotonic(), time.time(), process_age_s()
        launch = _launch_of(os.environ if env is None else env)
        self._steplog = steplog if steplog is not None else StepLog()
        self._wall_less_mono = wall - now
        self._last = now
        self._lock = threading.Lock()
        self._events: Optional[List[_Event]] = None  # a list inside warm
        self._is_ready = False
        self._tracer = None
        self.trace_id = str(launch.get("trace_id") or "")
        self.span_id = str(launch.get("span_id") or "")
        launched = _wall(launch.get("launched"))
        self.stats: Dict[str, object] = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "scheduler_started": _wall(launch.get("scheduler_started")),
            "launched": launched,
            "phase_s": dict.fromkeys(PHASES),
            "phase_end": dict.fromkeys(PHASES),
            "start_to_ready_s": None,
            "warm": warm_by_program(()),
            # the two programs (and any further set of argument types
            # they met) by how they came to be held, to this moment
            "programs": {"stored": 0, "compiled": 0},
            "compiles_after_ready": 0,
            "compile_after_ready_s_sum": 0.0,
        }
        started = wall - age
        self._close(
            "launch", None if launched is None else started - launched,
            started,
        )
        self._close("imports", age, wall)

    def _close(self, phase: str, seconds: Optional[float],
               end_wall: float, **fields) -> None:
        self.stats["phase_end"][phase] = round(end_wall, 6)
        if seconds is None:
            return
        self.stats["phase_s"][phase] = round(seconds, 6)
        self._steplog.phase(
            "startup." + phase, wall_s=round(seconds, 6),
            t=round(end_wall, 6), trace_id=self.trace_id,
            parent_id=self.span_id, **fields,
        )

    def mark(self, phase: str, **fields) -> None:
        """``phase`` ends now, where the one after it begins."""
        now = time.monotonic()
        self._close(
            phase, now - self._last, self._wall_less_mono + now, **fields
        )
        self._last = now

    @contextlib.contextmanager
    def warm(self):
        """Around the warm-up: the phase, and its events by program."""
        with self._lock:
            self._events = []
        try:
            yield
        finally:
            with self._lock:
                events, self._events = self._events, None
            by_program = warm_by_program(events)
            for program, kinds in by_program.items():
                self.stats["warm"][program].update(kinds)
            totals = {
                kind: round(sum(k[kind] for k in by_program.values()), 6)
                # a cache read is inside the compile it was served in
                for kind in WARM_KINDS if kind != "cache_read_s"
            }
            self.mark("warm", **totals, **self.stats["programs"])

    def ready(self, tracer=None) -> None:
        """The ``ready`` file is written: the last phase ends, the sum
        is drawn, and from here a compile is one the warm-up missed
        (an ``engine.compile`` span in ``tracer``'s ring, where on)."""
        self.mark("ready")
        with self._lock:
            self.stats["start_to_ready_s"] = round(sum(
                s for s in self.stats["phase_s"].values() if s is not None
            ), 6)
            self._tracer = tracer
            self._is_ready = True
        self._steplog.close()

    def on_duration(self, event: str, duration: float, fun_name: str = "",
                    **_kwargs) -> None:
        """``jax.monitoring``'s duration listener (JAX passes
        ``fun_name`` with the three compile events, and a stored
        program with its two; the cache's own names no function and
        fires inside the backend compile it belongs to)."""
        kind = _EVENT_KINDS.get(event)
        if kind is None:
            return
        end = time.monotonic()
        program = _program(fun_name)
        with self._lock:
            source = _SOURCES.get(kind)
            if source and program in WARM_PROGRAMS:
                self.stats["programs"][source] += 1
            if self._events is not None:
                self._events.append((end - duration, end, kind, program))
                return
            if not self._is_ready or not source:
                return
            self.stats["compiles_after_ready"] += 1
            self.stats["compile_after_ready_s_sum"] = round(
                self.stats["compile_after_ready_s_sum"] + duration, 6
            )
        if self._tracer is not None:
            self._tracer.interval(
                "engine.compile", end - duration, end, track="loop",
                fun_name=fun_name,
            )
