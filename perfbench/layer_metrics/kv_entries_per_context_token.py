"""Cache entries the live rows read, over the positions those entries stand
for: mean over the window of `kv_live_tokens` / `context_live_tokens`.
1.0 where every token is kept; a row of windows and chunk summaries holds far fewer.
Nothing where the program reports no `context_live_tokens`."""
from statistics import mean

from perfbench.harness.readers import window_samples


def read(run):
    shares = [
        s["kv_live_tokens"] / s["context_live_tokens"]
        for s in window_samples(run)
        if s.get("context_live_tokens") and "kv_live_tokens" in s
    ]
    return mean(shares) if shares else None
