"""The worker's own account of its start-up (``/stats`` -> ``startup``,
the program's `dcos_commons_tpu/trace/startup.py`), as the eight
`setup_s` readers take it from ``run["final_stats"]``.  A program
without the key (the parent of the PR that brought it), or a launch
without its context, reads ``None`` and the line leaves the metric out.
"""


def startup(run: dict) -> dict:
    return run["final_stats"].get("startup") or {}


def phase_s(run: dict, phase: str):
    """Seconds of one of the seven phases ``launch imports backend_up
    weights build warm ready``."""
    return (startup(run).get("phase_s") or {}).get(phase)


def warm_sum(run: dict, kinds):
    """Inside ``startup.warm``, the sum over the programs (``_prefill``,
    ``_decode``, ``other``) of the seconds of ``kinds`` (``trace_s``,
    ``lower_s``, ``compile_s``, ``cache_read_s``)."""
    warm = startup(run).get("warm")
    if not warm:
        return None
    return sum(
        program.get(kind, 0.0) for program in warm.values() for kind in kinds
    )
