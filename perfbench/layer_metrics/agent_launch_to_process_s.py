"""`startup.launch`: the launch handed to the agent until the worker's process exists (sandbox, templates, the supervisor's exec)."""
from perfbench.harness.startup import phase_s


def read(run):
    return phase_s(run, "launch")
