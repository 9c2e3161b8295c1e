"""Ring entries the live rows' window layers still count, over the positions
the rows stand for: mean over the window of `kv_window_live_tokens` /
`context_live_tokens`.  1.0 would mean the window layers keep everything, as a
full layer does; a row of `n` positions counts `min(n, sliding_window)` there.
Nothing where the program reports no `kv_window_live_tokens` (a commit from
before window layers) or the model has none (the gauge reads 0)."""
from statistics import mean

from perfbench.harness.readers import window_samples


def read(run):
    shares = [
        s["kv_window_live_tokens"] / s["context_live_tokens"]
        for s in window_samples(run)
        if s.get("context_live_tokens") and s.get("kv_window_live_tokens")
    ]
    return mean(shares) if shares else None
