"""The comparison that decides ``correct``, as a child process that
runs once the service is down and the chip is free.

For a sample of the window's answered requests it runs the plain
reference of the configuration's family (``families/<family>/
reference.py``) once over prompt + served tokens and reads, at every served
position, how far the served token's logit lies below the reference's
best.  Greedy tokens only.  Numbers that may be compared, each with
its own limit from the cell's file (a cell compares those it limits):

    max_gap         the widest such gap
    mean_gap        the mean over all served positions read
    mismatch_share  the share of positions whose served token is not
                    the reference's first
    wide_gap_share  the share of positions whose gap exceeds the cell's
                    ``wide_gap``
    steady_max_gap, steady_mean_gap, steady_mismatch_share,
    steady_wide_gap_share
                    the same over the positions whose steadiness
                    margin, as the family's reference reads it, is over
                    the cell's ``routing_margin``: in a mixture, by how
                    much the last chosen expert leads the first one left
                    out, narrowest over the layers; infinite where
                    nothing is routed.  Elsewhere any rounding upstream
                    changes the experts chosen and with them the logits
                    wholesale, whatever the precision: those positions
                    say nothing about precision and are held only to the
                    looser limits over all positions.

Last line of stdout: {"correct": bool, "compared": {name: [value, limit]}}
"""

from __future__ import annotations

import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def bucket(n: int, floor: int) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


def served_logits(reference, model, weights, prompt, served, lower=None):
    """The logits of ``reference`` (a family's) at every served
    position, teacher-forced along prompt + served tokens."""
    import numpy as np

    tokens = list(prompt) + list(served[:-1])
    padded = np.zeros(bucket(len(tokens), 256), np.int32)
    padded[:len(tokens)] = tokens
    rows = np.full(bucket(len(served), 64), len(tokens) - 1, np.int32)
    rows[:len(served)] = np.arange(len(prompt) - 1, len(tokens))
    logits, margins = reference.logits(
        model, weights, padded, rows, lower, margins=True
    )
    return logits[:len(served)], np.asarray(margins[:len(served)])


def chosen_gaps(logits, chosen):
    """Row by row: the best logit minus the logit of the chosen token."""
    import jax.numpy as jnp
    import numpy as np

    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(
        logits, jnp.asarray(chosen, jnp.int32)[:, None], -1
    )[:, 0]
    return np.asarray(best - mine, np.float64)


def compare(reference, model, weights, requests, limits, routing_margin=0.0,
            wide_gap=0.1):
    """(correct, {name: [value, limit]}, positions read, steady ones)."""
    import numpy as np

    gaps, margins = [], []
    for r in requests:
        logits, margin = served_logits(
            reference, model, weights, r["prompt"], r["served"]
        )
        gaps.append(chosen_gaps(logits, r["served"]))
        margins.append(margin)
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    steady = margins > routing_margin
    return judge(gaps, steady, limits, wide_gap) + (
        len(gaps), int(steady.sum())
    )


def judge(gaps, steady, limits, wide_gap=0.1):
    """(correct, {name: [value, limit]}) of the gaps read; ``steady``
    marks the positions whose routing no rounding can change."""
    values = {}
    for prefix, chosen in (("", gaps), ("steady_", gaps[steady])):
        if len(chosen):
            values[prefix + "max_gap"] = float(chosen.max())
            values[prefix + "mean_gap"] = float(chosen.mean())
            values[prefix + "mismatch_share"] = float((chosen > 0).mean())
            values[prefix + "wide_gap_share"] = float((chosen > wide_gap).mean())
    compared = {
        # a number that could not be read (no steady position) fails
        name: [values.get(name, float("inf")), limit]
        for name, limit in limits.items()
    }
    correct = all(value <= limit for value, limit in compared.values())
    return correct, compared


def load_job(path: str):
    """(job, model, family, weights, platform) of one check's input
    file."""
    sys.path.insert(0, CHECKOUT)
    with open(path) as f:
        job = json.load(f)
    with open(job["config_file"]) as f:
        model = json.load(f)
    import jax
    import jax.numpy as jnp

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from perfbench.harness.manifest import family_of
    from perfbench.harness.weights import make_weights

    family = family_of(job["config_file"])
    platform = jax.devices()[0].platform
    # the dtype the program serves in on this platform
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    weights = make_weights(family.weight_specs(model), job["seed"], dtype)
    return job, model, family, weights, platform


def main(argv) -> int:
    job, model, family, weights, platform = load_job(argv[1])
    correct, compared, positions, steady = compare(
        family.reference, model, weights, job["requests"], job["limits"],
        job.get("routing_margin", 0.0), job.get("wide_gap", 0.1),
    )
    print(f"reference of family {family.name} on {platform}: "
          f"{len(job['requests'])} requests, "
          f"{positions} served positions read, {steady} of them steady",
          flush=True)
    print(json.dumps({"correct": correct, "compared": compared}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
