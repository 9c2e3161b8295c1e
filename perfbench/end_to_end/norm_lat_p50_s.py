"""Median, over the window's requests, of (completion - due) / output tokens."""
from perfbench.harness.readers import normalised_latency


def read(run):
    return normalised_latency(run, 50)
