"""Mean wait from a request's arrival at the engine to its admission (a row and its
page budget granted), over the requests that finished in the window."""
from perfbench.harness.counters import delta, ratio


def read(run):
    return ratio(delta(run, "loop", "queue_wait_s_sum"),
                 delta(run, "loop", "requests_timed"))
