"""Prefill chunks that rode with each decode tick: prefill calls over decode calls."""
from perfbench.harness.counters import delta, ratio


def read(run):
    return ratio(delta(run, "loop", "prefill_calls"),
                 delta(run, "loop", "decode_calls"))
