"""Median device duration of the `jit__prefill` program in the trace."""
from perfbench.harness.readers import program_ms


def read(run):
    return program_ms(run, "jit__prefill")
