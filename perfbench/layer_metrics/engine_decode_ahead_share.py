"""Share of the decode calls dispatched while the previous call's tokens were still unread."""
from perfbench.harness.counters import delta, ratio


def read(run):
    return ratio(delta(run, "loop", "decode_ahead_calls"),
                 delta(run, "loop", "decode_calls"))
