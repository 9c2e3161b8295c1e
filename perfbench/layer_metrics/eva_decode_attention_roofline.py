"""The decode kernel's share of its roofline: what one layer's call needs for
the rows and entries the decode calls of the traced span computed for (the
family's `needs.py`), over the chip's peaks, over the kernel's mean device time
a call: its total among the trace's operations over (decode programs in the
trace x layers).  Rows and entries are the engine's own sums over its decode
calls (`loop.decode_rows_sum`, `loop.decode_entries_sum`, last sample of the
span minus first, a call): the gauge `kv_live_tokens` also holds the rows that
are still prefilling, which no decode call reads.  Nothing where the trace
holds no operation of that name (a program without the kernel) or the program
has no such sums.

The span: the harness stamps `trace_window`'s end when `/trace/stop` has
returned, and the stop collects and writes the profile for some seconds (30.8 s
in all for 4 s of trace in PR 28's call j5; half of that since PR 34),
through which the rows live on and change.
The device's times are of the mix's `trace_s` seconds from the span's start,
so the sums are read over those seconds and one more, not over the whole
stamp."""
from perfbench.harness import roofline
from perfbench.harness.counters import delta, ratio
from perfbench.harness.readers import family_needs

KERNEL = "jit__decode:eva_decode_attention"


def recorded(run):
    """The run with `trace_window` cut to the seconds the profiler recorded."""
    span, seconds = run.get("trace_window"), run["mix"].get("trace_s")
    if not span or not seconds:
        return run
    return dict(run, trace_window=[
        span[0], min(span[1], span[0] + seconds + 1.0)
    ])


def read(run):
    trace = run.get("trace") or {}
    took_s = sum(
        seconds for name, seconds
        in trace.get("breakdown", {}).get("device_ops", [])
        if name.startswith(KERNEL)
    )
    programs = trace.get("programs", {}).get("jit__decode", {}).get("count")
    needs_of = getattr(family_needs(run), "eva_decode_attention", None)
    span = recorded(run)
    calls = delta(span, "loop", "decode_calls", traced_only=True)
    rows = ratio(
        delta(span, "loop", "decode_rows_sum", traced_only=True), calls
    )
    entries = ratio(
        delta(span, "loop", "decode_entries_sum", traced_only=True), calls
    )
    if not (took_s and programs and needs_of and rows and run.get("peaks")) \
            or entries is None:
        return None
    least, _bound = roofline.least_seconds(
        needs_of(run["model"], rows, entries), run["peaks"]
    )
    kernel_calls = programs * run["model"]["num_hidden_layers"]
    return 100.0 * least / (took_s / kernel_calls)
