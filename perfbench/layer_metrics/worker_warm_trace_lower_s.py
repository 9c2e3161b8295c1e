"""Inside `startup.warm`, the host's part: every program's tracing and lowering."""
from perfbench.harness.startup import warm_sum


def read(run):
    return warm_sum(run, ("trace_s", "lower_s"))
