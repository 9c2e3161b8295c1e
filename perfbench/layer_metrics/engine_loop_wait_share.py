"""Share of the window the loop thread spent parked with no work (its ``wait`` phase)."""
from perfbench.harness.counters import delta, elapsed_s, ratio


def read(run):
    phases = delta(run, "loop", "phase_s")
    if phases is None:
        return None
    return ratio(phases.get("wait"), elapsed_s(run, "loop"))
