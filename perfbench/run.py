#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Deploys ``frameworks/jax/svc_serve.yml`` through the real scheduler,
its agent and the serve worker at the cell's sizes, waits for the
deploy plan, drives ``/generate`` with the cell's traffic from this
process (which never touches JAX: the worker owns the chip), tears the
service down, checks a sample of the served tokens against the
benchmark's own reference in a child process, and prints one JSON line.

The cell, its configuration, its traffic mix and its metrics are data:
``BENCHMARK.json`` names them and the files under this directory hold
them.  What belongs to one architecture family (the env that makes the
program build it, its weight tree, its plain reference, what its calls
need of the chip) is four files under ``families/<family>/``, found by
the ``"family"`` key of the configuration's file; of a configuration
this file itself reads ``vocab_size`` alone (see ``harness/manifest.py``).
"""

import time

PROCESS_START = time.monotonic()
PROCESS_START_WALL = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)

from perfbench.harness import manifest as manifests  # noqa: E402
from perfbench.harness.deploy import (  # noqa: E402
    DeployFailure,
    Deployment,
    http_json,
)
from perfbench.harness.loadgen import LoadRun  # noqa: E402
from perfbench.harness.traffic import schedule  # noqa: E402

TASK = "server-0-api"
PROGRAM_FILES = (
    os.path.join("dcos_commons_tpu", "__main__.py"),
    os.path.join("frameworks", "jax", "svc_serve.yml"),
    os.path.join("frameworks", "jax", "serve_worker.py"),
)


# What a traced run waits for /trace/stop.  The stop's cost follows the
# device programs the traced span held (and the operations a program
# executes), so the wait does, and no fixed one is there for a faster
# program to outrun.  A program of `lfm2-24b.chat`, which executes the
# most operations of the three cells', costs the stop 0.05 s, of
# `evabyte.docqa` and `mixtral8x7b.chat` half that (PERF.md section 6,
# PR 34).  No decode step on this chip lasts under a millisecond.
STOP_FLOOR_S = 30.0
STOP_S_PER_PROGRAM = 0.15
UNCOUNTED_PROGRAMS_PER_S = 1000.0


class RunFailure(Exception):
    pass


def say(message: str) -> None:
    print(message, flush=True)


def sizing_env(family, model: dict, config_path: str, mix: dict) -> dict:
    """The env that SIZES the deployment: what the configuration's
    family says makes the program build it, and the mix's sizes over
    that; every other knob of the program stays at the repo's default."""
    try:
        env = dict(family.program_env(model, config_path))
    except ValueError as e:
        raise RunFailure(f"{config_path}: {e}")
    env.update({k: str(v) for k, v in mix["sizing_env"].items()})
    return env


def deployment_env(sizes: dict, config_path: str, seed: int,
                   worker_dir: str) -> dict:
    """What the scheduler is started with: the sizes, the benchmark's
    worker entry in place of the program's, and what that entry needs
    to build the seed's weights."""
    env = dict(sizes)
    env["JAX_FRAMEWORK_DIR"] = os.path.abspath(worker_dir)
    env["TASKCFG_ALL_PERFBENCH_CONFIG_FILE"] = config_path
    env["TASKCFG_ALL_PERFBENCH_SEED"] = str(seed)
    return env


class StatsPoller:
    """The worker's ``/stats`` once a second, stamped on this clock."""

    def __init__(self, address: str):
        self.address = address
        self.samples = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                _code, stats = http_json(
                    f"http://{self.address}/stats", timeout=5
                )
                stats["_t"] = time.monotonic()
                self.samples.append(stats)
            except (OSError, ValueError):
                pass
            self._stop.wait(1.0)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)


def control(deployment: Deployment, path: str = "", payload=None,
            timeout_s: float = 120.0):
    with open(os.path.join(
        deployment.sandbox(TASK), "perfbench_control.json"
    )) as f:
        port = json.load(f)["port"]
    return http_json(
        f"http://127.0.0.1:{port}{path}", payload, timeout=timeout_s
    )[1]


def device_calls(sample: dict):
    """The device programs the engine has dispatched so far, by its own
    counters in a ``/stats`` sample; None where it has none."""
    loop = sample.get("loop") or {}
    if "decode_calls" not in loop or "prefill_calls" not in loop:
        return None
    return loop["decode_calls"] + loop["prefill_calls"]


def traced_programs(samples, t0: float, t1: float):
    """About how many device programs the traced span [t0, t1] held:
    the rate at which the engine's count of calls grew over the
    poller's samples from the last one before the span to the newest,
    times the span.  None where fewer than two samples carry it."""
    counted = [
        (s["_t"], calls) for s in samples
        if (calls := device_calls(s)) is not None
    ]
    before = [i for i, (t, _n) in enumerate(counted) if t <= t0]
    counted = counted[before[-1] if before else 0:]
    if len(counted) < 2 or counted[-1][0] <= counted[0][0]:
        return None
    (ta, na), (tb, nb) = counted[0], counted[-1]
    return math.ceil((nb - na) / (tb - ta) * (t1 - t0))


def stop_limit_s(programs, traced_s: float) -> float:
    """How long a run waits for ``/trace/stop``: the stop's cost
    follows the device programs traced, so the wait does.  A span
    whose programs could not be counted is given the most it could
    have held."""
    if programs is None:
        programs = math.ceil(UNCOUNTED_PROGRAMS_PER_S * traced_s)
    return STOP_FLOOR_S + STOP_S_PER_PROGRAM * programs


def stop_trace(deployment: Deployment, programs, traced_s: float) -> dict:
    """``/trace/stop``, waited for as long as what was traced asks.
    Returns what was traced, the seconds the stop took and the bytes of
    profile it wrote."""
    limit = stop_limit_s(programs, traced_s)
    counted = "uncounted" if programs is None else f"about {programs}"
    began = time.monotonic()
    try:
        reply = control(deployment, "/trace/stop", {}, timeout_s=limit)
    except OSError as e:
        raise RunFailure(
            f"/trace/stop gave no answer in {time.monotonic() - began:.1f} s "
            f"({counted} device programs traced in {traced_s:.1f} s; the "
            f"limit for them is {limit:.1f} s): {e!r}"
        )
    stop_s, written = time.monotonic() - began, reply.get("profile_bytes")
    say(f"traced {traced_s:.2f}s, {counted} device programs; /trace/stop "
        f"took {stop_s:.2f}s of the {limit:.1f}s it may and wrote {written} "
        "bytes")
    return {"traced_s": traced_s, "programs": programs, "stop_s": stop_s,
            "profile_bytes": written}


def check_device(device: dict, chips: int, peaks: dict) -> None:
    """The measurement path runs on the accelerator the cell asks for,
    of a kind whose peaks the benchmark knows, or not at all."""
    if device["platform"] != "tpu":
        raise RunFailure(f"no TPU: the worker runs on {device}")
    if device["count"] < chips:
        raise RunFailure(
            f"the cell asks for {chips} chip(s), JAX sees {device['count']}"
        )
    if device["kind"] not in peaks:
        raise RunFailure(
            f"device kind {device['kind']!r} is not in the benchmark's "
            "table of peaks"
        )


def check_answers(judged, vocab: int):
    """Every judged request: answered 200 with exactly the tokens asked
    for, each inside the vocabulary.  Returns (failed, reasons)."""
    failed, reasons = 0, []
    for o in judged:
        why = ""
        if o.status != 200:
            why = f"status {o.status} {o.error}"
        elif len(o.tokens) != o.request.max_new_tokens:
            why = (f"{len(o.tokens)} tokens for "
                   f"{o.request.max_new_tokens} asked")
        elif not all(0 <= t < vocab for t in o.tokens):
            why = "token outside the vocabulary"
        if why:
            failed += 1
            if len(reasons) < 5:
                reasons.append(f"request {o.request.index}: {why}")
    return failed, reasons


def run_children(argv, env, timeout_s):
    """A child of this run (the reference check, the trace reduction):
    its output goes through, its last line is its JSON result."""
    proc = subprocess.run(
        argv, env=env, cwd=CHECKOUT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=timeout_s,
    )
    lines = proc.stdout.splitlines()
    for line in lines[:-1]:
        say("  " + line)
    if proc.returncode != 0 or not lines:
        raise RunFailure(
            f"{os.path.basename(argv[2])} exited {proc.returncode}:\n"
            + "\n".join(lines[-5:]) + "\n" + proc.stderr[-3000:]
        )
    return json.loads(lines[-1])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # what follows is for tests and for the builder's own chip runs;
    # the driver passes none of it
    parser.add_argument("--root", default=CHECKOUT, help=argparse.SUPPRESS)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--worker-dir", default=os.path.join(HERE, "worker"),
                        help=argparse.SUPPRESS)
    parser.add_argument("--program-env", action="append", default=[],
                        help=argparse.SUPPRESS)
    parser.add_argument("--keep", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    try:
        return run(args)
    except (RunFailure, DeployFailure) as e:
        print(f"perfbench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


def run(args) -> int:
    missing = [
        p for p in PROGRAM_FILES
        if not os.path.exists(os.path.join(CHECKOUT, p))
    ]
    if missing:
        print(
            "perfbench: FAILED: not inside a tpu-service-sdk checkout "
            f"(missing {', '.join(missing)})", file=sys.stderr,
        )
        return 2
    bench = manifests.Manifest(args.root)
    cell = bench.cell(args.workload)
    model = bench.config(cell["config"])
    config_path = bench.config_path(cell["config"])
    family = bench.family(cell["config"])
    mix = bench.traffic(cell["traffic"])
    params = bench.cell_params(cell["name"])
    peaks = bench.peaks()
    sizes = sizing_env(family, model, config_path, mix)
    say(f"family {family.name}: sizing env {json.dumps(sizes)}")
    trace = bool(args.trace)
    rehearse = args.rehearse_cpu

    child_env = dict(os.environ)
    child_env.pop("BENCH_RUN", None)
    if rehearse:
        say("CPU REHEARSAL: platform cpu, no device metric is written")
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env.pop("XLA_FLAGS", None)
    elif "tpu" not in child_env.get("JAX_PLATFORMS", "tpu").lower():
        raise RunFailure(
            f"no TPU: JAX_PLATFORMS={child_env['JAX_PLATFORMS']!r} keeps "
            "JAX off the accelerator"
        )

    requests = schedule(
        mix, params, model["vocab_size"], args.seconds, args.seed
    )
    env = deployment_env(sizes, config_path, args.seed, args.worker_dir)
    for item in args.program_env:
        key, _, value = item.partition("=")
        env[f"TASKCFG_ALL_{key}"] = value
        say(f"program env (not the cell's): {key}={value}")

    # the run's files: at a fixed place beside BENCHMARK.json (inside
    # the checkout, unless a test brought a root of its own), emptied
    workdir = os.path.join(bench.root, ".perfbench_run")
    shutil.rmtree(workdir, ignore_errors=True)
    deployment = Deployment(
        CHECKOUT, os.path.join(CHECKOUT, "frameworks", "jax", "svc_serve.yml"),
        workdir, cell["chips"], env, child_env,
    )
    poller = load = None
    left, stuck = [], 0
    try:
        deployment.wait_listening()
        listening_s = time.monotonic() - PROCESS_START
        deploy_plan_s = deployment.wait_deploy_complete(TASK, 1100)
        _code, endpoint = http_json(f"{deployment.url}/v1/endpoints/http")
        address = endpoint["address"][0]
        _code, stats = http_json(f"http://{address}/stats")
        device = {
            "platform": stats["platform"], "kind": stats["device_kind"],
            "count": stats["device_count"],
        }
        say("device " + json.dumps(device))
        if not rehearse:
            check_device(device, cell["chips"], peaks)
        for key, want in sizes.items():
            field = key.replace("TASKCFG_ALL_", "").lower()
            if field in stats["model"] and str(stats["model"][field]) != want:
                raise RunFailure(
                    f"the worker built {stats['model']}, asked {key}={want}"
                )
        setup_s = time.monotonic() - PROCESS_START
        say(f"setup_s {setup_s:.3f} (deploy plan COMPLETE after "
            f"{deploy_plan_s:.3f}s, worker warm_s {stats.get('warm_s')})")

        # ramp, window, drain
        clients = int(mix["clients"])
        start = time.monotonic() + mix["ramp_s"]
        load = LoadRun(address, mix, requests, start, args.seconds, clients)
        poller = StatsPoller(address)
        say(f"ramp {mix['ramp_s']}s, window {args.seconds}s, "
            f"{mix['loop']} loop, {params['rate_rps']} requests/s")
        ramp_wall = time.time()
        load.begin()
        poller.start()
        trace_dir = os.path.join(workdir, "trace")
        trace_window = trace_stop = None
        if trace:
            begin = start + mix["trace_after_s"]
            time.sleep(max(0.0, begin - time.monotonic()))
            t0 = time.monotonic()
            control(deployment, "/trace/start", {"dir": trace_dir})
            time.sleep(mix["trace_s"])
            t1 = time.monotonic()
            programs = traced_programs(list(poller.samples), t0, t1)
            trace_stop = stop_trace(deployment, programs, t1 - t0)
            trace_window = (t0, time.monotonic())
        judged = load.drain()
        poller.stop()
        info = control(deployment)
        _code, final_stats = http_json(f"http://{address}/stats")
    finally:
        if poller is not None:
            poller.stop()
        left = deployment.stop()
        if load is not None:
            stuck = load.finish()
    if left:
        raise RunFailure(f"the service left processes alive: {left}")
    if stuck:
        raise RunFailure(f"{stuck} load threads did not end")

    # where the set-up's time went, for PERF.md: nothing is judged on it
    worker_at = info["started"] - PROCESS_START_WALL
    say("set-up, seconds from this process's start: scheduler listening "
        f"{listening_s:.2f}, worker process {worker_at:.2f}, " + ", ".join(
            f"{name} {worker_at + t:.2f}"
            for name, t in sorted(info["setup_times"].items(),
                                  key=lambda kv: kv[1])
        ) + f", ready {setup_s:.2f}")

    window_compiles = [
        e for e in info["compile_events"] if e["t"] >= ramp_wall
    ]
    say(f"compilations inside ramp and window: {len(window_compiles)}")
    if window_compiles:
        raise RunFailure(
            f"{len(window_compiles)} compilation(s) inside the measured "
            f"window: {window_compiles[:3]}"
        )

    failed, reasons = check_answers(judged, model["vocab_size"])
    for reason in reasons:
        say("failed: " + reason)
    say(f"judged requests {len(judged)}, failed {failed}, "
        f"all requests sent {len(load.outcomes)}")

    place = {id(o): i for i, o in enumerate(load.outcomes)}
    run_data = {
        "cell": cell, "model": model, "mix": mix, "params": params,
        "seconds": args.seconds,
        "window": [load.start, load.end],
        "outcomes": [
            {
                "phase": o.request.phase, "due": o.due, "sent": o.sent,
                "done": o.done, "ok": o.status == 200,
                "prompt_tokens": o.request.prompt_len,
                "output_tokens": o.request.max_new_tokens,
            } for o in load.outcomes
        ],
        "judged": [place[id(o)] for o in judged],
        "stats_samples": poller.samples,
        "final_stats": final_stats,
        "setup_s": setup_s, "deploy_plan_s": deploy_plan_s,
        "trace_window": trace_window, "trace_stop": trace_stop,
        "peaks": peaks.get(device["kind"]),
        "device": device,
        "config_file": config_path,
    }

    # the reference check, now that the chip is free
    sample = pick_sample(load.outcomes, judged, load.start, load.end,
                         mix, args.seed)
    say(f"reference reads {len(sample)} requests: "
        f"{sum(n == len(o.tokens) for o, n in sample)} whole, the others' "
        f"first {mix['check_tokens']} tokens")
    check_file = os.path.join(workdir, "check_in.json")
    with open(check_file, "w") as f:
        json.dump({
            "config_file": config_path,
            "seed": args.seed, "limits": params["correct_limits"],
            "routing_margin": params.get("routing_margin", 0.0),
            "wide_gap": params.get("wide_gap", 0.1),
            "requests": [
                {"prompt": o.request.tokens.tolist(), "served": o.tokens[:n]}
                for o, n in sample
            ],
        }, f)
    verdict = run_children(
        [sys.executable, "-m", "perfbench.harness.check", check_file],
        child_env, 900,
    )
    correct = bool(verdict["correct"]) and failed == 0

    device["memory_peak_bytes"] = info["memory_peak_bytes"]
    result = {
        "correct": correct, "attempted": len(judged), "failed": failed,
        "metrics": {}, "device": device,
    }
    if trace:
        cpu_env = dict(child_env, JAX_PLATFORMS="cpu")
        reduced = run_children(
            [sys.executable, "-m", "perfbench.harness.trace_reduce",
             trace_dir, os.path.join(workdir, "trace.json")],
            cpu_env, 600,
        )
        run_data["trace"] = reduced
        if not rehearse:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
    else:
        run_data["trace"] = None

    kind = "per_layer" if trace else "end_to_end"
    for metric in bench.metrics(kind, cell["name"]):
        value = bench.reader(kind, metric["name"])(run_data)
        if value is None:
            continue
        if rehearse and metric["source"] == "device_trace":
            continue
        result["metrics"][metric["name"]] = {
            "value": value, "unit": metric["unit"],
        }
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(workdir, "run.json"), "w") as f:
            json.dump(run_data, f)
        for name in ("run.json", "trace.json", "check_in.json"):
            path = os.path.join(workdir, name)
            if os.path.exists(path):
                shutil.copy(path, os.path.join(
                    args.keep, f"{cell['name']}.{args.seed}.{name}"
                ))
    shutil.rmtree(workdir, ignore_errors=True)
    # each number compared beside its limit, printed once: the last
    # lines of stderr, and the last key of the result's line (a number
    # that could not be read is infinite, which JSON cannot hold)
    result["compared"] = {
        name: [value if math.isfinite(value) else str(value), limit]
        for name, (value, limit) in verdict["compared"].items()
    }
    for name, (value, limit) in result["compared"].items():
        print(f"correct: {name} {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    say(json.dumps(result))
    return 0


def pick_sample(outcomes, judged, start: float, end: float, mix: dict,
                seed: int):
    """The answered requests the reference reads, as (outcome, served
    tokens to read).  Whole: the longest of the window and a seeded
    draw of ``check_draw`` more of it.  Over their first
    ``check_tokens`` served tokens: every request that was in flight
    at the instant of the window at which the most were.  Requests in
    flight together hold different rows of the engine, so those cover
    every row in use at that instant."""
    import random

    def answered(o):
        return o.status == 200 and bool(o.tokens)

    window = [o for o in judged if answered(o)]
    if not window:
        raise RunFailure("no request of the window was answered")
    longest = max(window, key=lambda o: o.request.prompt_len + len(o.tokens))
    rest = [o for o in window if o is not longest]
    random.Random(seed).shuffle(rest)
    whole = [longest] + rest[:int(mix["check_draw"])]

    everything = [o for o in outcomes if answered(o)]

    def in_flight(t):
        return [o for o in everything if o.sent <= t < o.done]

    # the number in flight rises only where a request is sent
    instants = [o.sent for o in everything if start <= o.sent <= end]
    together = max((in_flight(t) for t in instants), key=len, default=[])
    head = int(mix["check_tokens"])
    return [(o, len(o.tokens)) for o in whole] + [
        (o, min(head, len(o.tokens))) for o in together
        if not any(o is w for w in whole)
    ]


if __name__ == "__main__":
    sys.exit(main())
