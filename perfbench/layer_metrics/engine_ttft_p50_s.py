"""The engine's own median time to a request's first token, at the window's end."""
from perfbench.harness.readers import window_samples


def read(run):
    samples = [s for s in window_samples(run) if "ttft_p50_s" in s]
    return samples[-1]["ttft_p50_s"] if samples else None
