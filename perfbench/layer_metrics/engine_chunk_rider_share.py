"""Share of the prefill chunk calls that carried the tick's decode step for at least one live row."""
from perfbench.harness.counters import delta, ratio


def read(run):
    return ratio(delta(run, "loop", "prefill_rider_calls"),
                 delta(run, "loop", "prefill_calls"))
