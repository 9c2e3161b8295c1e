"""Mean time per output token after the first, over the requests that finished in the
window, by the engine's own clock: first token to retirement over the tokens between."""
from perfbench.harness.counters import delta, ratio


def read(run):
    return ratio(delta(run, "loop", "decode_s_sum"),
                 delta(run, "loop", "decode_tokens_sum"), 1e3)
