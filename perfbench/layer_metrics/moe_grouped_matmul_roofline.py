"""The grouped expert matmul's share of its roofline, in place: what the
grouped products that the trace lists need (the family's `needs.py`
`moe_grouped_matmul`: ONE expert layer's three products, so a third of it a
product) for the assignments and the expert groups their program's calls of
the traced span really had, over the chip's peaks, over the device time those
same operations took.

Which operations: the program walks its layer pattern as `model.layer_plan` in
`/stats` says ([lead, period, reps, tail], models/decode.py `layer_plan`): the
layers of one period are unrolled in the body of one scan, so each of a
period's products is one operation of the trace (`jit__decode:gmm.<n>`,
`jit__prefill:gmm.<n>`) that runs `reps` times a program.  The trace's
breakdown lists the ten operations that took most time; a period has twelve
products a program, and which of the two programs' fill the ten moves with
the load (at 4.2/s the ten were all `jit__prefill`'s in two traced runs of
four, PERF.md section 6, PR 33).  So EVERY listed product counts, each at its
own program's counts: a decode step's products at
`loop.moe_assignments_sum`, `loop.moe_groups_touched_sum` over
`loop.decode_calls` x expert layers, a prefill chunk's at
`loop.moe_prefill_assignments_sum`, `loop.moe_prefill_groups_touched_sum`
over `loop.moe_prefill_chunks_counted` x expert layers (the chunks' counts
reach the host with a prompt's last chunk, so they are sums over the chunks
COUNTED, not over `prefill_calls`).  The share is the needed seconds of all
of them over the seconds all of them took.  The three products of a layer
move the same weights and operations, so the ones listed stand for all.

Over which samples the sums are taken: `/stats` is sampled once a second and
the harness stamps the traced span's end when `/trace/stop` has returned,
which here was two minutes after the 4 recorded seconds ended (under one
since PR 34); through it the worker slows and rows pile up.  The trace says
itself how many programs it holds, so the sums run from the span's first
sample to the sample at which the program's own count of calls has grown by
the number nearest that: a span cut at `trace_s` by the clock lost the
traced calls' last third in one run (3.6/s,
call u1: 382 calls of 572, and they were the short steps of few rows, so the
share read 98.5% where the 581 calls up to the next sample give 87.6%), and
never a gauge.

Nothing where an expert layer lies outside the scanned periods (its
operations run once a program and cannot be told from the others), where the
trace lists no such operation of a program whose counters the program has,
or where the program lacks the plan."""
from perfbench.harness import roofline
from perfbench.harness.counters import ratio
from perfbench.harness.readers import family_needs, window_samples
from perfbench.layer_metrics.moe_experts_touched_per_layer import expert_layers

# device program -> its (calls, assignments, groups) counters under `loop`
COUNTERS = {
    "jit__decode": (
        "decode_calls", "moe_assignments_sum", "moe_groups_touched_sum",
    ),
    "jit__prefill": (
        "moe_prefill_chunks_counted", "moe_prefill_assignments_sum",
        "moe_prefill_groups_touched_sum",
    ),
}


def traced_sums(run, names, programs):
    """What the counters `names` (calls first) grew by from the traced
    span's first sample to the sample at which the calls had grown by
    the number nearest `programs`; None where a counter is missing."""
    carrying = [
        s["loop"] for s in window_samples(run, traced_only=True)
        if all(name in s.get("loop", {}) for name in names)
    ]
    if len(carrying) < 2:
        return None
    first, calls = carrying[0], names[0]
    last = min(
        carrying[1:],
        key=lambda loop: abs(loop[calls] - first[calls] - programs),
    )
    return [last[name] - first[name] for name in names]


def scan_trips(run):
    """Times a program runs each grouped product: the plan's `reps`, where
    every expert layer lies in the scanned periods."""
    model = (run.get("final_stats") or {}).get("model") or {}
    plan, layers = model.get("layer_plan"), expert_layers(run)
    if not plan or not layers:
        return None
    lead, period, reps, tail = plan
    if lead > model.get("n_dense_layers", 0) or tail:
        return None
    return reps


def products(run, program, layers, trips, needs_of):
    """(needed seconds, device seconds) of the grouped products of `program`
    that the trace lists, or None where there are none to read."""
    trace = run["trace"]
    sites = [
        seconds for name, seconds in trace["breakdown"].get("device_ops", [])
        if name.startswith(program + ":gmm")
    ]
    programs = trace.get("programs", {}).get(program, {}).get("count")
    if not sites or not programs:
        return None
    calls, assignments, groups = traced_sums(
        run, COUNTERS[program], programs
    ) or (0, None, None)
    per = calls * layers
    assignments, groups = ratio(assignments, per), ratio(groups, per)
    if not assignments or not groups:
        return None
    least, _bound = roofline.least_seconds(
        needs_of(run["model"], assignments, groups), run["peaks"]
    )
    return least / 3.0 * len(sites) * programs * trips, sum(sites)


def read(run):
    trace = run.get("trace") or {}
    needs_of = getattr(family_needs(run), "moe_grouped_matmul", None)
    trips, layers = scan_trips(run), expert_layers(run)
    if not (trace.get("breakdown") and needs_of and trips
            and run.get("peaks")):
        return None
    pairs = [
        pair for pair in (
            products(run, program, layers, trips, needs_of)
            for program in COUNTERS
        ) if pair
    ]
    if not pairs:
        return None
    return 100.0 * sum(n for n, _ in pairs) / sum(t for _, t in pairs)
