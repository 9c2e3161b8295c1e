"""Window ends the engine's rows crossed, a second of the window: each makes a
row's ring of exact pages dead at once and its chunk summaries visible."""
from perfbench.harness.counters import delta, elapsed_s, ratio


def read(run):
    return ratio(
        delta(run, "loop", "window_rollovers"),
        elapsed_s(run, "loop", "window_rollovers"),
    )
