"""Mean of the engine's `active_slots`, polled once a second over the window."""
from perfbench.harness.readers import stat_mean


def read(run):
    return stat_mean(run, "active_slots")
