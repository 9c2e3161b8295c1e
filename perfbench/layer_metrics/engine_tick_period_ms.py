"""The engine's tick period: seconds of the window over the decode calls made in it.
A chat user's gap between output tokens, where `decode_tick_ms` is only the device's part of it."""
from perfbench.harness.counters import delta, elapsed_s, ratio


def read(run):
    return ratio(elapsed_s(run, "loop"), delta(run, "loop", "decode_calls"), 1e3)
