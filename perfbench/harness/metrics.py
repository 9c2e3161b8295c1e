"""The arithmetic of the end-to-end metrics, on plain numbers."""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks, q in [0, 100]."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    rank = (len(ordered) - 1) * q / 100.0
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def normalised_latencies(rows: Sequence[Tuple[float, float, int]]) -> List[float]:
    """(due, done, output tokens) -> seconds a token, from when the
    request was due."""
    return [(done - due) / tokens for due, done, tokens in rows]


def slope(points: Sequence[Tuple[float, float]]) -> Optional[float]:
    """Least-squares slope of y over x."""
    if len(points) < 2:
        return None
    mx = sum(x for x, _ in points) / len(points)
    my = sum(y for _, y in points) / len(points)
    sxx = sum((x - mx) ** 2 for x, _ in points)
    if sxx == 0:
        return None
    return sum((x - mx) * (y - my) for x, y in points) / sxx
