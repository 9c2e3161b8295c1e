"""`startup.imports`: the worker process's OS start until `main()` is past its imports."""
from perfbench.harness.startup import phase_s


def read(run):
    return phase_s(run, "imports")
