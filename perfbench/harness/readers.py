"""What the metric readers share: views of one run's record.

A reader is ``read(run) -> number | None``.  ``run`` holds the cell,
its configuration (``model``), mix and parameters, every request's
times (``outcomes``, with ``judged`` indexing the window's), the
worker's ``/stats`` once a second (``stats_samples``, stamped ``_t``
on the same clock as ``window``), ``final_stats``, ``setup_s``,
``deploy_plan_s``, the device's ``peaks``, the configuration's file
(``config_file``, by which its family is found) and, in a traced run,
the reduced ``trace`` with the span it covered (``trace_window``).
"""

from __future__ import annotations

from statistics import mean

from perfbench.harness import metrics, roofline
from perfbench.harness.manifest import family_of


def judged(run: dict) -> list:
    return [run["outcomes"][i] for i in run["judged"]]


def normalised_latency(run: dict, q: float):
    rows = [
        (o["due"], o["done"], o["output_tokens"])
        for o in judged(run) if o["ok"]
    ]
    if not rows:
        return None
    return metrics.percentile(metrics.normalised_latencies(rows), q)


def window_samples(run: dict, traced_only: bool = False) -> list:
    """The `/stats` samples taken inside the window (or, for what is
    set beside device times, inside the traced span)."""
    a, b = run["window"]
    if traced_only and run.get("trace_window"):
        a, b = run["trace_window"]
    return [s for s in run["stats_samples"] if a <= s["_t"] <= b]


def stat_mean(run: dict, key: str, traced_only: bool = False):
    values = [s[key] for s in window_samples(run, traced_only) if key in s]
    return mean(values) if values else None


def program_ms(run: dict, program: str):
    trace = run.get("trace") or {}
    entry = trace.get("programs", {}).get(program)
    return entry["median_ms"] if entry else None


def family_needs(run: dict):
    """What a call of this run's configuration needs of the chip: the
    ``needs`` of its family, ``decode_tick`` and ``prefill_chunk``."""
    return family_of(run["config_file"]).needs


def roofline_share(run: dict, needs: dict, program: str):
    """The least time the chip could take for the call, over the time
    the program took: a share of the roofline, in percent."""
    took_ms = program_ms(run, program)
    if not took_ms or not run.get("peaks"):
        return None
    least, _bound = roofline.least_seconds(needs, run["peaks"])
    return 100.0 * least / (took_ms * 1e-3)
