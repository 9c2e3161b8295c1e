"""`startup.build`: the HTTP bind, the arena, pool and engine built."""
from perfbench.harness.startup import phase_s


def read(run):
    return phase_s(run, "build")
