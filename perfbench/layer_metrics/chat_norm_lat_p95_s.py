"""95th percentile, over the window's requests, of (completion - due) /
output tokens.  Not an end-to-end metric: with the 61-143 requests a
window holds, three to seven lie beyond it, and two sets of runs of one
program spread by 3.9% and 10.1% (PERF.md, noise study)."""
from perfbench.harness.readers import normalised_latency


def read(run):
    return normalised_latency(run, 95)
