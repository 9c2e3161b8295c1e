#!/usr/bin/env python3
"""Find an open-loop cell's knee: deploy the cell once, then offer its
traffic at each of a list of rates for a short window and report, for
each, whether the engine's queue grew and whether any request failed.
The knee is the highest rate at which neither happened.  A benchmark PR
runs this once, on the chip, and writes the sweep into PERF.md and
0.7 of the knee into the cell's file; no run of the benchmark calls it.

    python3 perfbench/tools/knee_sweep.py --workload <cell> --rates 4,6,8 \
        [--seconds 20] [--seed 1]
"""

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import manifest as manifests  # noqa: E402
from perfbench.harness import metrics  # noqa: E402
from perfbench.harness.deploy import Deployment, http_json  # noqa: E402
from perfbench.harness.loadgen import LoadRun  # noqa: E402
from perfbench.harness.traffic import schedule  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args()
    bench = manifests.Manifest(CHECKOUT)
    cell = bench.cell(args.workload)
    model = bench.config(cell["config"])
    params = bench.cell_params(cell["name"])
    mix = bench.traffic(cell["traffic"])
    config_path = bench.config_path(cell["config"])
    family = bench.family(cell["config"])
    env = bench_run.deployment_env(
        bench_run.sizing_env(family, model, config_path, mix), config_path,
        args.seed, os.path.join(CHECKOUT, "perfbench", "worker"),
    )
    workdir = os.path.join(CHECKOUT, ".perfbench_run")
    shutil.rmtree(workdir, ignore_errors=True)
    deployment = Deployment(
        CHECKOUT, os.path.join(CHECKOUT, "frameworks", "jax", "svc_serve.yml"),
        workdir, cell["chips"], env, dict(os.environ),
    )
    try:
        deployment.wait_listening()
        deployment.wait_deploy_complete(bench_run.TASK, 1100)
        _c, endpoint = http_json(f"{deployment.url}/v1/endpoints/http")
        address = endpoint["address"][0]
        for rate in (float(r) for r in args.rates.split(",")):
            requests = schedule(
                mix, dict(params, rate_rps=rate), model["vocab_size"],
                args.seconds, args.seed,
            )
            start = time.monotonic() + mix["ramp_s"]
            load = LoadRun(address, mix, requests, start, args.seconds,
                           int(mix["clients"]))
            poller = bench_run.StatsPoller(address)
            load.begin()
            poller.start()
            judged = load.drain()
            poller.stop()
            failed, _why = bench_run.check_answers(judged, model["vocab_size"])
            inside = [s for s in poller.samples
                      if load.start <= s["_t"] <= load.end]
            lat = metrics.normalised_latencies([
                (o.due, o.done, o.request.max_new_tokens)
                for o in judged if o.status == 200
            ])
            print(json.dumps({
                "rate_rps": rate, "judged": len(judged), "failed": failed,
                "queue_growth": metrics.slope(
                    [(s["_t"], s["queue_depth"]) for s in inside]),
                "queue_depth_max": max(s["queue_depth"] for s in inside),
                "queue_depth_end": inside[-1]["queue_depth"],
                "active_slots_mean": sum(
                    s["active_slots"] for s in inside) / len(inside),
                "norm_lat_p50_s": metrics.percentile(lat, 50) if lat else None,
                "norm_lat_p95_s": metrics.percentile(lat, 95) if lat else None,
                "drain_s": time.monotonic() - load.end,
            }), flush=True)
            # let the engine empty before the next rate
            while http_json(f"http://{address}/stats")[1]["active_slots"]:
                time.sleep(0.5)
    finally:
        left = deployment.stop()
    shutil.rmtree(workdir, ignore_errors=True)
    return 1 if left else 0


if __name__ == "__main__":
    sys.exit(main())
