"""One decode tick's share of the roofline: what the tick needs for the
rows and tokens live while the trace ran, over the chip's peaks, over
the tick's device time."""
from perfbench.harness.readers import family_needs, roofline_share, stat_mean


def read(run):
    rows = stat_mean(run, "active_slots", traced_only=True)
    tokens = stat_mean(run, "kv_live_tokens", traced_only=True)
    if not rows or tokens is None:
        return None
    needs = family_needs(run).decode_tick(run["model"], rows, tokens)
    return roofline_share(run, needs, "jit__decode")
