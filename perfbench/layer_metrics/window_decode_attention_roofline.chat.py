"""The window layers' decode attention kernel's share of its roofline: what ONE
window layer's call needs for the rows and ring entries the decode calls of
the traced span computed for (the family's `needs.py`
`window_decode_attention`), over the chip's peaks, over the kernel's mean
device time a call: the total of the operations named
`paged_decode_attention_window` that the trace lists, over (decode programs in
the trace x the window layers those operations stand for).  The program walks
its pattern as `/stats` `model.layer_plan` says ([lead, period, reps, tail]): a
window layer of the scanned period is ONE operation that runs `reps` times a
program, a window layer before or behind it one that runs once.  The trace
lists its ten longest operations only, so a layer outside the period may be
missing from it (call c1: the period's operation 0.174 s, the leading layer's a
third of that and not among the ten); the operations listed are taken to be the
longest-running ones, so `n` of them stand for the `n` largest of those counts,
and a share is never read over a time that leaves part of the work out.  Rows
and entries are the engine's own sums over its decode calls
(`loop.decode_rows_sum`, `loop.decode_window_entries_sum`, last sample of the
recorded span minus first, a call), as `eva_decode_attention_roofline.chat`
takes its own.  Nothing where the trace lists no operation of that name (a
program without the kernel, or one whose kernel is not among the ten), where
the program has no such sums or states no plan, or where the family states no
such needs."""
from perfbench.harness import roofline
from perfbench.harness.counters import delta, ratio
from perfbench.harness.readers import family_needs
from perfbench.layer_metrics.eva_decode_attention_roofline import recorded

KERNEL = "jit__decode:paged_decode_attention_window"


def layer_calls(run, listed):
    """Window layers' calls a program that `listed` operations of the
    trace stand for: the largest of each operation's runs a program."""
    model = (run.get("final_stats") or {}).get("model") or {}
    plan, types = model.get("layer_plan"), model.get("layer_types")
    if not plan or not types:
        return None
    lead, period, reps, _tail = plan
    runs = sorted((
        reps if lead <= at < lead + period else 1
        for at, kind in enumerate(
            types[:lead + period] + types[lead + period * reps:]
        ) if kind == "sliding"
    ), reverse=True)
    return sum(runs[:listed])


def read(run):
    trace = run.get("trace") or {}
    sites = [
        seconds for name, seconds
        in trace.get("breakdown", {}).get("device_ops", [])
        if name.startswith(KERNEL)
    ]
    programs = trace.get("programs", {}).get("jit__decode", {}).get("count")
    needs_of = getattr(family_needs(run), "window_decode_attention", None)
    layers = layer_calls(run, len(sites))
    span = recorded(run)
    calls = delta(span, "loop", "decode_calls", traced_only=True)
    rows = ratio(
        delta(span, "loop", "decode_rows_sum", traced_only=True), calls
    )
    entries = ratio(
        delta(span, "loop", "decode_window_entries_sum", traced_only=True),
        calls,
    )
    if not (sites and programs and needs_of and layers and rows and entries
            and run.get("peaks")):
        return None
    least, _bound = roofline.least_seconds(
        needs_of(run["model"], rows, entries), run["peaks"]
    )
    return 100.0 * least / (sum(sites) / (programs * layers))
