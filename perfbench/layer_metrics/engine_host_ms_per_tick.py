"""The loop thread's own work a tick: every phase but its wait for work and its two
calls into the device half (admission, page tables, padding, applying tokens, stats)."""
from perfbench.harness.counters import delta, ratio

NOT_HOST_WORK = ("wait", "prefill_call", "decode_call")


def read(run):
    phases = delta(run, "loop", "phase_s")
    if phases is None:
        return None
    host = sum(s for name, s in phases.items() if name not in NOT_HOST_WORK)
    return ratio(host, delta(run, "loop", "decode_calls"), 1e3)
