"""Inside `startup.warm`, the backend's part: every program's compile, or its read from the compile cache."""
from perfbench.harness.startup import warm_sum


def read(run):
    return warm_sum(run, ("compile_s",))
