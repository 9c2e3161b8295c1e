"""From a profiler trace to numbers: the device's busy and idle time,
each program's durations, the operations that took most device time,
and the idle gaps by what the host was doing in them.

Two steps, so that the arithmetic can be checked on a small recorded
trace: ``load_xplane`` turns the profiler's ``.xplane.pb`` into plain
lists, ``reduce`` works on those lists alone.

    python -m perfbench.harness.trace_reduce <trace dir> <out.json>

prints the reduction as its last line and writes the plain lists.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from statistics import median

# the worker entry's spans (perfbench/worker/serve_worker.py): the
# engine's two calls into the device half and the blocking fetches
# inside them.  A call's "dispatch" is its time before its first fetch.
OUTER = ("decode", "prefill_chunk")
INNER = tuple(f"{o}:fetch" for o in OUTER)
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load_xplane(trace_dir: str) -> dict:
    """{"devices": {plane: {"ops": [[name, start_ns, dur_ns]...],
    "modules": [...]}}, "host": [[name, start_ns, dur_ns]...]}"""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": [], "lines_seen": {}}
    wanted = set(OUTER) | set(INNER)
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        out["lines_seen"][plane.name] = sorted(lines)
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            out["devices"][plane.name] = {
                key: [
                    [e.name[:160], float(e.start_ns), float(e.duration_ns)]
                    for e in lines[name].events
                ] if name in lines else []
                for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE))
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        out["host"].append(
                            [e.name, float(e.start_ns), float(e.duration_ns)]
                        )
    return out


def union(intervals):
    """Sorted, merged [start, end] pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


class Cover:
    """How much of [a, b] a set of merged intervals covers."""

    def __init__(self, intervals):
        self.merged = union(intervals)
        self.starts = [s for s, _ in self.merged]
        self.total = [0.0]
        for s, e in self.merged:
            self.total.append(self.total[-1] + (e - s))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.merged[i - 1]
        return self.total[i - 1] + (min(t, e) - s)

    def within(self, a: float, b: float) -> float:
        return self.upto(b) - self.upto(a)

    def holes(self, a: float, b: float):
        """The parts of [a, b] that the intervals leave uncovered."""
        out, at = [], a
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        for s, e in self.merged[i:]:
            if s >= b:
                break
            if e <= at:
                continue
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if at < b:
            out.append((at, b))
        return out


def dispatches(host: list, outer: str) -> list:
    """(start, end) of each ``outer`` call's time before its first
    blocking fetch: the host preparing and dispatching the program.  A
    call with no fetch span has none, and counts whole as "other"."""
    fetches = sorted(s for n, s, _d in host if n == f"{outer}:fetch")
    out = []
    for name, start, dur in host:
        if name != outer:
            continue
        i = bisect.bisect_left(fetches, start)
        if i < len(fetches) and fetches[i] < start + dur:
            out.append((start, fetches[i]))
    return out


def program_of(name: str) -> str:
    """'jit__decode(1234)' -> 'jit__decode'."""
    return re.sub(r"\(.*$", "", name).strip()


def short(name: str) -> str:
    """An operation's name without its operands: '%fusion.1 = bf16[8,64]
    {..} fusion(...)' -> 'fusion.1 bf16[8,64]'."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*\(?([\w]+\[[\d,]*\])?", name)
    if not m:
        return name[:60]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:60]


def reduce(plain: dict) -> dict:
    devices = plain["devices"]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    every = [
        e for d in devices.values() for e in d["ops"] + d["modules"]
    ] + plain["host"]
    t0 = min(e[1] for e in every)
    t1 = max(e[1] + e[2] for e in every)
    window = t1 - t0

    busy_total = 0.0
    op_time, programs, gaps = {}, {}, {}
    host = {
        name: Cover([(s, s + d) for n, s, d in plain["host"] if n == name])
        for name in OUTER + INNER
    }
    for outer in OUTER:
        host[f"{outer}:dispatch"] = Cover(dispatches(plain["host"], outer))
    for device in devices.values():
        ops = Cover([(s, s + d) for _, s, d in device["ops"]])
        busy_total += ops.total[-1]
        modules = sorted(device["modules"], key=lambda e: e[1])
        module_cover = Cover([(s, s + d) for _, s, d in modules])
        starts = [e[1] for e in modules]
        for name, s, d in modules:
            programs.setdefault(program_of(name), []).append(d)
        for name, s, d in device["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][1] + modules[i][2]
            owner = program_of(modules[i][0]) if inside else "no_program"
            if short(name).startswith(("while", "conditional", "call")):
                continue  # a container: its body's operations are listed
            key = f"{owner}:{short(name)}"
            op_time[key] = op_time.get(key, 0.0) + d
        # the idle gaps of this device, by what the host was doing
        edges = [t0] + [t for pair in ops.merged for t in pair] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            in_program = module_cover.within(a, b)
            parts = {"inside_device_programs:_between_operations": in_program}
            for x, y in module_cover.holes(a, b):
                covered = 0.0
                for outer in OUTER:
                    inner = 0.0
                    for part in ("dispatch", "fetch"):
                        t = host[f"{outer}:{part}"].within(x, y)
                        key = f"inside_{outer}_call:_{part}"
                        parts[key] = parts.get(key, 0.0) + t
                        inner += t
                    whole = max(host[outer].within(x, y), inner)
                    key = f"inside_{outer}_call:_other"
                    parts[key] = parts.get(key, 0.0) + whole - inner
                    covered += whole
                key = "engine_loop_outside_both"
                parts[key] = parts.get(key, 0.0) + max((y - x) - covered, 0.0)
            for key, t in parts.items():
                gaps[key] = gaps.get(key, 0.0) + t
    chips = len(devices)
    ns = 1e-9
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gap_list = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_total / chips * ns,
        "window_s": window * ns,
        "programs": {
            name: {
                "count": len(ds), "median_ms": median(ds) * 1e-6,
                "total_s": sum(ds) * ns / chips,
            } for name, ds in programs.items()
        },
        "breakdown": {
            "device_ops": [[k, v * ns / chips] for k, v in top],
            "idle_gaps": [[k, v * ns / chips] for k, v in gap_list if v > 0],
        },
    }


def main(argv) -> int:
    plain = load_xplane(argv[1])
    with open(argv[2], "w") as f:
        json.dump(plain, f)
    for plane, lines in plain["lines_seen"].items():
        print(f"trace plane {plane}: {lines}", flush=True)
    print(json.dumps(reduce(plain)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
