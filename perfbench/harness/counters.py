"""Readers of the program's cumulative counters: the engine's ``loop``
key in ``/stats`` (ticks, calls, seconds in each phase of the loop
thread, per-request sums).  A counter only ever grows, so what a
window saw is the last sample inside it minus the first.

Every function returns ``None`` where the program has no such counter
(a commit from before it had them), where fewer than two samples carry
it, or where a denominator is zero: the metric is then left out of the
result line, never raised.
"""

from __future__ import annotations

from perfbench.harness.readers import window_samples


def _dig(sample: dict, path):
    value = sample
    for key in path:
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value


def ends(run: dict, *path, traced_only: bool = False):
    """(first, last) of the window's samples (or the traced span's)
    that carry ``path``, or None."""
    carrying = [
        s for s in window_samples(run, traced_only)
        if _dig(s, path) is not None
    ]
    if len(carrying) < 2:
        return None
    return carrying[0], carrying[-1]


def delta(run: dict, *path, traced_only: bool = False):
    """Last minus first of the number at ``path``; of a dict of
    numbers, the dict of their differences."""
    pair = ends(run, *path, traced_only=traced_only)
    if pair is None:
        return None
    first, last = (_dig(s, path) for s in pair)
    if isinstance(last, dict):
        return {k: last[k] - first.get(k, 0) for k in last}
    return last - first


def elapsed_s(run: dict, *path, traced_only: bool = False):
    """Seconds between the two samples ``delta`` subtracts, by the
    program's own stamp of each snapshot."""
    pair = ends(run, *path, traced_only=traced_only)
    if pair is None:
        return None
    first, last = pair
    stamp = "t" if "t" in first and "t" in last else "_t"
    return last[stamp] - first[stamp]


def ratio(numerator, denominator, scale: float = 1.0):
    if numerator is None or not denominator:
        return None
    return scale * numerator / denominator
