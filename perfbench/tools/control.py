#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the
reference of the configuration's family, put in the program's place and computed with int8 weights
(the nearest precision below the configurations' bf16), has to come out
as NOT correct.  A SIMULATION of a lower-precision program, not a served
run: the program's own int8 weights cannot start at the cells' sizes
(PERF.md, section 7a).  It need not decode: at each served position of a sound
run's own prompts and tokens (the check's input file, kept by
``run.py --keep``) it reads the token that the lower precision puts
first and that token's gap under the float32 reference.  Prints, for
each file, the same three numbers for the served tokens (sound) and for
the control.  A benchmark PR runs it on the chip when it sets or
changes a limit; no run of the benchmark calls it.

    python3 perfbench/tools/control.py <cell.seed.check_in.json> ...
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench.harness import check  # noqa: E402

# the routing margins tried; a cell's file keeps the one its limits use
MARGINS = (0.0, 0.05, 0.1, 0.2)


def main(argv) -> int:
    import jax.numpy as jnp
    import numpy as np

    for path in argv[1:]:
        job, model, family, weights, platform = check.load_job(path)
        config_file = job["config_file"]
        if not os.path.exists(config_file):
            raise SystemExit(f"{config_file} of {path} is not here")
        sound, control, margins = [], [], []
        for r in job["requests"]:
            exact, margin = check.served_logits(
                family.reference, model, weights, r["prompt"], r["served"]
            )
            lower, _ = check.served_logits(
                family.reference, model, weights, r["prompt"], r["served"],
                lower="int8",
            )
            sound.append(check.chosen_gaps(exact, r["served"]))
            control.append(check.chosen_gaps(
                exact, np.asarray(jnp.argmax(lower, -1))
            ))
            margins.append(margin)
        margins = np.concatenate(margins)
        if os.environ.get("PERFBENCH_CONTROL_DUMP"):
            # every position's reading, for choosing a number offline
            with open(os.path.join(
                os.environ["PERFBENCH_CONTROL_DUMP"],
                os.path.basename(path) + ".positions.json",
            ), "w") as f:
                json.dump({
                    "margin": margins.tolist(),
                    "sound": np.concatenate(sound).tolist(),
                    "control": np.concatenate(control).tolist(),
                }, f)
        out = {"file": os.path.basename(path), "seed": job["seed"],
               "platform": platform, "positions": len(margins)}
        every = {k: float("inf") for k in (
            "max_gap", "mean_gap", "mismatch_share", "wide_gap_share",
            "steady_max_gap", "steady_mean_gap", "steady_mismatch_share",
            "steady_wide_gap_share")}
        for at in MARGINS:
            steady = margins > at
            row = {"steady_positions": int(steady.sum())}
            for name, gaps in (("sound", sound), ("control", control)):
                _ok, compared = check.judge(
                    np.concatenate(gaps), steady, every,
                    job.get("wide_gap", 0.1),
                )
                row[name] = {k: round(v[0], 6) for k, v in compared.items()}
            out[f"routing_margin_{at}"] = row
        del weights  # the next file's have to fit beside nothing
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
