#!/usr/bin/env python3
"""Put every interval in which the device ran nothing down to what the
program's own host spans say the host was doing in it: the innermost
``engine.*`` / ``pool.*`` span that covers it (serve/engine.py's phases
of the loop thread, serve/pool.py's calls and their blocking fetches).
A builder's tool, beside ``knee_sweep.py``: no run of the benchmark
calls it.  It is how the engine's counters are checked against the
device trace on the chip.

    JAX_PLATFORMS=cpu python3 perfbench/tools/loop_gaps.py <profile dir>

reduces a profile that is there: the directory a worker's ``POST
/profile`` names, or any ``jax.profiler`` trace.  Run it with JAX kept
on the CPU where another process holds the chip.  The time the host
spends under no span (serve/engine.py's ``other``) is ``outside_every_span``.

Two steps, as in ``harness/trace_reduce.py``: ``load`` turns the
``.xplane.pb`` into plain lists, ``attribute`` works on those alone.
"""

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

from perfbench.harness.trace_reduce import OPS_LINE, Cover, union  # noqa: E402

PREFIXES = ("engine.", "pool.")
OUTSIDE = "outside_every_span"


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [[start_ns, dur_ns]...]}, "host": {thread:
    [[name, start_ns, dur_ns]...]}}: the device's operations and the
    program's spans, thread by thread."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        for i, line in enumerate(plane.lines):
            if plane.name.startswith("/device:") and line.name == OPS_LINE:
                out["devices"][plane.name] = [
                    [float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ]
            elif plane.name.startswith("/host:"):
                spans = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(PREFIXES)
                ]
                if spans:
                    out["host"][f"{plane.name}/{i}:{line.name}"] = spans
    return out


def self_intervals(spans: list) -> dict:
    """One thread's spans, properly nested -> {name: [(start, end)...]}
    of each span's OWN time: the span less the spans inside it.  The
    intervals of all names together are disjoint, so each instant
    belongs to the innermost span over it."""
    own, stack = {}, []  # stack of [name, end, covered up to]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, at = stack.pop()
            if end > at:
                own.setdefault(name, []).append((at, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack and start > stack[-1][2]:
            own.setdefault(stack[-1][0], []).append((stack[-1][2], start))
        stack.append([name, start + dur, start])
    close(float("inf"))
    return own


def attribute(plain: dict) -> dict:
    """Idle seconds of the device(s) by the innermost program span
    over them, and each span's own seconds for comparison."""
    every = [s for d in plain["devices"].values() for s in d]
    if not every:
        return {"window_s": 0.0, "idle_s": 0.0, "idle_by_span": [],
                "span_self_s": {}}
    t0 = min(s for s, _d in every)
    t1 = max(s + d for s, d in every)
    own = {}
    for spans in plain["host"].values():
        for name, intervals in self_intervals(spans).items():
            own.setdefault(name, []).extend(intervals)
    covers = {name: Cover(union(iv)) for name, iv in own.items()}
    idle, idle_total = {}, 0.0
    for ops in plain["devices"].values():
        busy = Cover([(s, s + d) for s, d in ops])
        for a, b in busy.holes(t0, t1):
            idle_total += b - a
            inside = 0.0
            for name, cover in covers.items():
                t = cover.within(a, b)
                if t > 0:
                    idle[name] = idle.get(name, 0.0) + t
                    inside += t
            idle[OUTSIDE] = idle.get(OUTSIDE, 0.0) + max(b - a - inside, 0.0)
    chips, ns = len(plain["devices"]), 1e-9
    return {
        "window_s": (t1 - t0) * ns,
        "idle_s": idle_total * ns / chips,
        "idle_by_span": sorted(
            ([k, v * ns / chips] for k, v in idle.items() if v > 0),
            key=lambda kv: -kv[1],
        ),
        # each span's own seconds inside the device's window
        "span_self_s": {
            name: cover.within(t0, t1) * ns
            for name, cover in sorted(covers.items())
        },
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("profile_dir")
    args = parser.parse_args(argv)
    print(json.dumps(attribute(load(args.profile_dir))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
