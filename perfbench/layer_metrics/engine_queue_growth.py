"""Slope of the engine's `queue_depth` over the window, requests a second."""
from perfbench.harness import metrics
from perfbench.harness.readers import window_samples


def read(run):
    return metrics.slope(
        [(s["_t"], s["queue_depth"]) for s in window_samples(run)]
    )
