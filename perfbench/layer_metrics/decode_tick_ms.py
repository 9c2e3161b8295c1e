"""Median device duration of the `jit__decode` program in the trace."""
from perfbench.harness.readers import program_ms


def read(run):
    return program_ms(run, "jit__decode")
