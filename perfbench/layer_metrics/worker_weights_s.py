"""`startup.weights`: backend up until the parameters are on the device."""
from perfbench.harness.startup import phase_s


def read(run):
    return phase_s(run, "weights")
