"""Mean time from a request's admission to its first token: its prefill chunks and the
ticks they rode, over the requests that finished in the window."""
from perfbench.harness.counters import delta, ratio


def read(run):
    return ratio(delta(run, "loop", "prefill_s_sum"),
                 delta(run, "loop", "requests_timed"))
