#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the product still starts on
the chip.

Drives the deploy path once, through the entry points an operator
uses, at the full width of the repo's dense flagship (seeded random
weights): ``python -m dcos_commons_tpu serve <svc.yml> --topology ...``
brings up the scheduler with its in-process agent, the agent launches
the JAX worker, and this script talks to the scheduler's HTTP API and
then to the worker.

Three legs, strictly one after another — a chip belongs to one process
at a time, so this parent is stdlib-only and NEVER imports jax, and
every process that touches the chip is a child that has exited before
the next one starts:

  kernels  one child: flash attention forward + backward and RMSNorm,
           compiled by Mosaic (not interpreted), executed at the train
           leg's shapes and compared with the jnp references.
  train    frameworks/jax/svc.yml: deploy plan COMPLETE, a few steps
           whose loss is finite and falls, one steplog record per
           step, a checkpoint, and the Mosaic kernels present in the
           worker's own train step at per-device shapes.  Full width,
           ONE layer: the trainer's checkpoint (params + adam moments)
           goes to the host's disk, 0.75 GiB a save at this depth and
           4.9 GiB at the flagship's twelve, and a chip host may cap
           the size of a file.
  serve    frameworks/jax/svc_serve.yml (full width and depth: it
           writes nothing but logs): readiness gates on warm, the
           address comes from /v1/endpoints/http, POST /generate
           answers single, batched and concurrent mixed-length
           requests with the requested token counts, greedy replies
           repeat exactly, /stats answers.

Every leg reports what the WORKER says it runs on.  Anything other
than platform "tpu", any failed check, any timeout: non-zero exit and
no result line.  Without an accelerator this fails in seconds and says
so.  ``--tiny-cpu`` is the only CPU mode: toy widths, JAX_PLATFORMS=cpu,
kernels in interpret mode — it exists so tier-1 tests run this same
control flow, and it proves nothing about the device.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import math
import os
import resource
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
JAX_DIR = os.path.join(HERE, "frameworks", "jax")

# the dense flagship (bench.py flagship_config)
FLAGSHIP = {
    "VOCAB": "32768", "D_MODEL": "2048", "N_LAYERS": "12",
    "N_HEADS": "16", "N_KV_HEADS": "16", "D_FF": "8192",
}
# ... cut to one layer where it trains: every save puts params + both
# adam moments (6 bytes a parameter) on the host's disk, and the first
# chip host this ran on outside the builder's tool refused the
# twelve-layer file with EFBIG
TRAIN_N_LAYERS = "1"
TOY = {
    "VOCAB": "64", "D_MODEL": "32", "N_LAYERS": "2",
    "N_HEADS": "2", "N_KV_HEADS": "2", "D_FF": "64",
}
TRAIN_STEPS = 8  # the synthetic path repeats one batch: its loss falls
MOSAIC_KERNELS = (
    "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv",
    "rms_norm_fwd",
)
# kernel-vs-reference bounds, relative to the reference's largest
# magnitude.  Inputs are bf16 (8 mantissa bits, half-ulp 2**-9 ~ 0.002
# per rounding) and the kernels round p / ds to bf16 once more before
# their MXU dots; the backward chains three such roundings.
FWD_TOL = 0.02
BWD_TOL = 0.04


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def tail(path: str, lines: int = 25) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError:
        return f"<no {os.path.basename(path)}>"


# -- the kernel leg (the only code here that imports jax) --------------


def kernel_leg(tiny: bool, chips: int) -> int:
    """Child process: compile, run and check the Pallas kernels."""
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say("DEVICE " + json.dumps(device))
    if not tiny:
        if device["platform"] != "tpu":
            say(
                f"no TPU: JAX reports platform {device['platform']!r} "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})"
            )
            return 3
        if device["count"] != chips:
            say(f"asked for {chips} chip(s), JAX sees {device['count']}")
            return 3

    from dcos_commons_tpu.ops.attention import flash_attention
    from dcos_commons_tpu.ops.introspect import mosaic_calls
    from dcos_commons_tpu.ops.rmsnorm import _reference as rms_reference
    from dcos_commons_tpu.ops.rmsnorm import rms_norm
    from dcos_commons_tpu.parallel.ring import reference_attention
    from dcos_commons_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    interpret = tiny
    if tiny:
        b, h, s, hd, tiles = 1, 2, 256, 64, ((128, 128),)
        rows, d = 512, 128
    else:
        # the train leg's per-device shapes
        b, h, s, hd = 2, 16, 2048, 128
        tiles = ((128, 128), (512, 512))
        rows, d = 4096, 2048

    def rel_err(got, want):
        got = jnp.asarray(got, jnp.float32)
        want = jnp.asarray(want, jnp.float32)
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    ok = True
    keys = jax.random.split(jax.random.key(0), 6)
    q, k, v, w = (
        jax.random.normal(key, (b, h, s, hd), jnp.bfloat16)
        for key in keys[:4]
    )

    def ref_loss(q, k, v):
        out = reference_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    ref_out = jax.jit(lambda q, k, v: reference_attention(q, k, v, True))(
        q, k, v
    )
    ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for block_q, block_k in tiles:
        def attn(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                force_pallas=True, interpret=interpret,
            )

        def loss(q, k, v):
            return jnp.sum(
                attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)
            )

        t0 = time.time()
        fwd = jax.jit(attn).lower(q, k, v)
        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v)
        names = sorted(
            set(mosaic_calls(fwd.as_text()))
            | set(mosaic_calls(bwd.as_text()))
        )
        out = jax.block_until_ready(fwd.compile()(q, k, v))
        grads = jax.block_until_ready(bwd.compile()(q, k, v))
        result = {
            "kernel": "flash_attention", "shape": [b, h, s, hd],
            "tiles": [block_q, block_k], "interpret": interpret,
            "mosaic_calls": names,
            "fwd_rel_err": round(rel_err(out, ref_out), 5),
            "bwd_rel_err": [
                round(rel_err(g, r), 5) for g, r in zip(grads, ref_grads)
            ],
            "compile_and_run_s": round(time.time() - t0, 1),
        }
        good = (
            result["fwd_rel_err"] <= FWD_TOL
            and max(result["bwd_rel_err"]) <= BWD_TOL
            and all(math.isfinite(e) for e in result["bwd_rel_err"])
            and (interpret or names == sorted(MOSAIC_KERNELS[:3]))
        )
        ok = ok and good
        say(("KERNEL " if good else "KERNEL-FAILED ") + json.dumps(result))

    x = jax.random.normal(keys[4], (rows, d), jnp.bfloat16)
    g = (1.0 + 0.1 * jax.random.normal(keys[5], (d,))).astype(jnp.bfloat16)
    t0 = time.time()
    norm = jax.jit(
        lambda x, g: rms_norm(x, g, force_pallas=True, interpret=interpret)
    ).lower(x, g)
    names = sorted(mosaic_calls(norm.as_text()))
    out = jax.block_until_ready(norm.compile()(x, g))
    result = {
        "kernel": "rms_norm", "shape": [rows, d], "interpret": interpret,
        "mosaic_calls": names,
        "fwd_rel_err": round(rel_err(out, rms_reference(x, g, 1e-6)), 5),
        "compile_and_run_s": round(time.time() - t0, 1),
    }
    good = result["fwd_rel_err"] <= FWD_TOL and (
        interpret or names == [MOSAIC_KERNELS[3]]
    )
    ok = ok and good
    say(("KERNEL " if good else "KERNEL-FAILED ") + json.dumps(result))
    return 0 if ok else 1


# -- the parent: stdlib only -------------------------------------------


def host_limits(workdir: str) -> str:
    """What the host lets this run write: the legs keep their sandboxes
    (logs, the trainer's checkpoint) under ``workdir`` and the compile
    cache in the checkout."""
    soft, _hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    return json.dumps({
        "file_size_limit_bytes":
            None if soft == resource.RLIM_INFINITY else soft,
        "free_bytes": {
            path: shutil.disk_usage(path).free for path in (workdir, HERE)
        },
    })


def http_json(url: str, payload=None, timeout: float = 30.0):
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return response.status, json.loads(response.read())


class Deployment:
    """One scheduler process (with its in-process agent) serving one
    service YAML, and the task sandboxes under it."""

    def __init__(self, name: str, svc_yml: str, workdir: str, chips: int,
                 env: dict, child_env: dict):
        self.name = name
        self.workdir = os.path.join(workdir, name)
        self.sandboxes = os.path.join(self.workdir, "sandboxes")
        os.makedirs(self.workdir)
        topology = os.path.join(self.workdir, "topology.yml")
        block = {1: "[1, 1]", 4: "[2, 2]"}[chips]
        with open(topology, "w") as f:
            f.write(
                "hosts:\n"
                "  - host_id: smoke-0\n"
                "    hostname: 127.0.0.1\n"
                "    slice_id: smoke\n"
                "    generation: v5e\n"
                "    grid: [0, 0]\n"
                f"    chip_block: {block}\n"
                "    cpus: 8\n"
                "    memory_mb: 32768\n"
            )
        self.announce = os.path.join(self.workdir, "announce")
        self.log_path = os.path.join(self.workdir, "scheduler.log")
        argv = [
            sys.executable, "-m", "dcos_commons_tpu", "serve", svc_yml,
            "--topology", topology,
            "--port", "0",
            "--state-dir", os.path.join(self.workdir, "state"),
            "--sandbox-root", self.sandboxes,
            "--announce-file", self.announce,
        ]
        for key, value in {"JAX_FRAMEWORK_DIR": JAX_DIR, **env}.items():
            argv += ["--env", f"{key}={value}"]
        self._log = open(self.log_path, "ab")
        self.process = subprocess.Popen(
            argv, cwd=HERE, env=child_env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        self.url = ""

    def sandbox(self, task: str) -> str:
        return os.path.join(self.sandboxes, task)

    def fail(self, why: str, task: str = "") -> SmokeFailure:
        detail = [f"{self.name} leg: {why}"]
        if task:
            for stream in ("stderr", "stdout"):
                detail.append(f"--- {task} {stream} (tail)")
                detail.append(
                    tail(os.path.join(self.sandbox(task), stream))
                )
        detail.append("--- scheduler.log (tail)")
        detail.append(tail(self.log_path, 15))
        return SmokeFailure("\n".join(detail))

    def wait_listening(self, timeout_s: float = 60.0) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.process.poll() is not None:
                raise self.fail(
                    f"scheduler exited {self.process.returncode} at start"
                )
            try:
                with open(self.announce) as f:
                    self.url = f.read().strip()
                if self.url:
                    return
            except OSError:
                pass
            time.sleep(0.2)
        raise self.fail("scheduler never announced its API")

    def task_died(self, task: str) -> bool:
        return bool(glob.glob(
            os.path.join(self.sandbox(task), ".super", "*", "exit_status")
        ))

    def wait_deploy_complete(self, task: str, timeout_s: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.process.poll() is not None:
                raise self.fail("scheduler exited mid-deploy", task)
            if self.task_died(task):
                raise self.fail("the task exited during deploy", task)
            try:
                code, plan = http_json(f"{self.url}/v1/plans/deploy")
            except (OSError, ValueError):
                code, plan = 0, {}
            if code == 200 and plan.get("status") == "COMPLETE":
                return time.monotonic() - t0
            time.sleep(0.5)
        raise self.fail(
            f"deploy plan not COMPLETE within {timeout_s:.0f}s", task
        )

    def task_pids(self) -> list:
        pids = []
        for record in glob.glob(
            os.path.join(self.sandboxes, "*", ".super", "*")
        ):
            for name in ("task.pid", "child.pid"):
                try:
                    with open(os.path.join(record, name)) as f:
                        pids.append(int(f.read()))
                except (OSError, ValueError):
                    pass
            try:  # pure-Python supervision (no C++ toolchain)
                with open(os.path.join(record, "task.json")) as f:
                    pids.append(int(json.load(f).get("pid", 0)))
            except (OSError, ValueError):
                pass
        return sorted({p for p in pids if p > 1})

    def stop(self) -> list:
        """Stop the scheduler, then every task it launched (tasks
        outlive their scheduler by design).  Returns the task pids
        still alive afterwards — should be empty."""
        if self.process.poll() is None:
            self.process.send_signal(signal.SIGTERM)
            try:
                self.process.wait(timeout=20)
            except subprocess.TimeoutExpired:
                os.killpg(self.process.pid, signal.SIGKILL)
                self.process.wait(timeout=10)
        self._log.close()
        pids = self.task_pids()
        for sig, wait_s in ((signal.SIGTERM, 10.0), (signal.SIGKILL, 5.0)):
            for pid in pids:
                try:
                    os.killpg(pid, sig)
                except OSError:
                    pass
            deadline = time.monotonic() + wait_s
            while time.monotonic() < deadline and any(
                alive(p) for p in pids
            ):
                time.sleep(0.1)
        return [p for p in pids if alive(p)]


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            # a zombie is gone for our purposes (its parent reaps it)
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except (OSError, IndexError):
        return False


def prefixed(lines, prefix: str):
    """The JSON payloads of the log lines that start with ``prefix``."""
    out = []
    for line in lines:
        if line.startswith(prefix):
            try:
                out.append(json.loads(line[len(prefix):]))
            except ValueError:
                pass
    return out


def check_worker_platform(report: dict, tiny: bool, who: str) -> None:
    want = "cpu" if tiny else "tpu"
    if report.get("platform") != want:
        raise SmokeFailure(
            f"{who} reports platform {report.get('platform')!r}, "
            f"not {want!r}"
        )


def run_kernel_leg(tiny: bool, chips: int, child_env: dict) -> dict:
    say("== kernels")
    argv = [sys.executable, os.path.abspath(__file__), "--leg", "kernels",
            "--chips", str(chips)] + (["--tiny-cpu"] if tiny else [])
    try:
        proc = subprocess.run(
            argv, env=child_env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, timeout=600,
        )
    except subprocess.TimeoutExpired:
        raise SmokeFailure("kernel leg timed out after 600s")
    lines = proc.stdout.splitlines()
    for line in lines:
        say("  " + line)
    device = (prefixed(lines, "DEVICE ") or [None])[0]
    if proc.returncode != 0 or device is None:
        reason = [
            line for line in lines
            if line.startswith(("no TPU", "asked for", "KERNEL-FAILED"))
        ] or proc.stderr.strip().splitlines()[-15:]
        raise SmokeFailure(
            f"kernel leg exited {proc.returncode}: " + "\n".join(reason)
        )
    return device


def run_train_leg(tiny: bool, chips: int, workdir: str,
                  child_env: dict) -> dict:
    say("== train (frameworks/jax/svc.yml)")
    model = dict(TOY) if tiny else dict(FLAGSHIP, N_LAYERS=TRAIN_N_LAYERS)
    model["SEQ_LEN"] = "128" if tiny else "2048"
    env = {
        "TRAINER_COUNT": "1",
        "TPU_CHIPS_PER_HOST": str(chips),
        "TPU_TOPOLOGY": {1: "1x1", 4: "2x2"}[chips],
        "TRAIN_STEPS": str(TRAIN_STEPS),
        # the step-1 save goes once the last step's has landed
        "CHECKPOINT_KEEP": "1",
        # the worker lingers after training (goal RUNNING); bounded so
        # a leaked one cannot hold the chip for long
        "TASKCFG_ALL_KEEPALIVE_S": "120",
    }
    env.update({f"TASKCFG_ALL_{k}": v for k, v in model.items()})
    task = "trainer-0-worker"
    deployment = Deployment(
        "train", os.path.join(JAX_DIR, "svc.yml"), workdir, chips, env,
        child_env,
    )
    try:
        deployment.wait_listening()
        deploy_s = deployment.wait_deploy_complete(task, 120)
        stdout = os.path.join(deployment.sandbox(task), "stdout")
        deadline = time.monotonic() + (300 if tiny else 900)
        lines = []
        while True:
            try:
                with open(stdout, errors="replace") as f:
                    lines = f.read().splitlines()
            except OSError:
                lines = []
            if any(line.startswith("worker 0/1:") for line in lines):
                break
            if deployment.task_died(task):
                raise deployment.fail("the trainer exited early", task)
            if time.monotonic() > deadline:
                raise deployment.fail("training did not finish", task)
            time.sleep(0.5)

        devices = (prefixed(lines, "devices: ") or [{}])[0]
        check_worker_platform(devices, tiny, "train worker")
        if devices.get("device_count") != chips:
            raise deployment.fail(
                f"the worker sees {devices.get('device_count')} "
                f"device(s), its pod owns {chips}", task,
            )
        step = (prefixed(lines, "train step: ") or [{}])[0]
        for key, value in model.items():
            field = {"SEQ_LEN": "seq"}.get(key, key.lower())
            if str(step.get(field)) != value:
                raise deployment.fail(
                    f"worker built {field}={step.get(field)}, "
                    f"asked for {value}", task,
                )
        # the kernels the step was BUILT with, and over what
        calls = step.get("mosaic_calls", {})
        if not tiny:
            missing = [k for k in MOSAIC_KERNELS if k not in calls]
            if missing:
                raise deployment.fail(
                    f"train step lacks Mosaic kernels {missing}: the "
                    "dispatch took the jnp reference", task,
                )
            # batch 2 per device, whatever the global batch: attention
            # over [2*16 heads, 2048, 128], the norms over 2*2048 rows
            for name in MOSAIC_KERNELS:
                shard = (
                    "tensor<4096x2048x" if name == "rms_norm_fwd"
                    else "tensor<32x2048x128x"
                )
                for signature in calls[name]["operands"]:
                    operands = signature.split(", ")
                    if name == "rms_norm_fwd":
                        operands = operands[:1]  # the other is the weight
                    if not all(o.startswith(shard) for o in operands):
                        raise deployment.fail(
                            f"{name} runs over ({signature}), not the "
                            f"per-device {shard}...> shard", task,
                        )
        losses = {}
        for line in lines:
            if line.startswith("step ") and " loss=" in line:
                index, value = line[5:].split(" loss=")
                losses[int(index)] = float(value)
        first, last = losses.get(0), losses.get(TRAIN_STEPS - 1)
        if first is None or last is None or not (
            math.isfinite(first) and math.isfinite(last) and last < first
        ):
            raise deployment.fail(
                f"loss did not fall: step 0 {first}, "
                f"step {TRAIN_STEPS - 1} {last}", task,
            )
        records = []
        with open(os.path.join(deployment.sandbox(task), "steplog.jsonl")) as f:
            records = [json.loads(line) for line in f if line.strip()]
        if sorted(r["step"] for r in records) != list(range(TRAIN_STEPS)):
            raise deployment.fail(
                f"steplog has steps {[r['step'] for r in records]}", task
            )
        if {r.get("platform") for r in records} != {devices["platform"]}:
            raise deployment.fail("steplog platform disagrees", task)
        checkpoints = glob.glob(os.path.join(
            deployment.sandbox(task), "checkpoints", "step_*.npz"
        ))
        if not checkpoints:
            raise deployment.fail("no checkpoint step_*.npz", task)
        memory = (prefixed(lines, "device memory: ") or [[]])[0]
        if not tiny and (
            len(memory) != chips
            or not all((m.get("bytes_in_use") or 0) > 0 for m in memory)
        ):
            raise deployment.fail(
                f"not every device holds memory: {memory}", task
            )
        report = {
            "leg": "train", **devices,
            "mesh": step.get("mesh"), "batch": step.get("batch"),
            "model": {k.lower(): int(v) for k, v in model.items()},
            "deploy_plan_complete_s": round(deploy_s, 1),
            "lower_s": step.get("lower_s"),
            "compile_s": step.get("compile_s"),
            "compile_cache": step.get("compile_cache"),
            "first_step_wall_s": records[0].get("wall_s"),
            "loss_first": first, "loss_last": last,
            "mosaic_calls": {
                name: entry["operands"] for name, entry in calls.items()
            },
            "all_gathers": step.get("all_gathers"),
            "device_bytes_in_use": [m.get("bytes_in_use") for m in memory],
            "checkpoints": {
                os.path.basename(c): os.path.getsize(c)
                for c in sorted(checkpoints)
            },
        }
        say("  TRAIN " + json.dumps(report))
    finally:
        left = deployment.stop()
    if left:
        raise SmokeFailure(f"train leg left processes alive: {left}")
    return report


def run_serve_leg(tiny: bool, chips: int, workdir: str,
                  child_env: dict) -> dict:
    say("== serve (frameworks/jax/svc_serve.yml)")
    model = dict(TOY if tiny else FLAGSHIP)
    max_len, new_tokens = (48, 8) if tiny else (256, 32)
    env = {
        "VOCAB": model["VOCAB"], "D_MODEL": model["D_MODEL"],
        "N_LAYERS": model["N_LAYERS"],
        "SEQ_LEN": str(max_len), "MAX_LEN": str(max_len),
        "MAX_NEW_TOKENS": str(new_tokens),
        "TASKCFG_ALL_N_HEADS": model["N_HEADS"],
        "TASKCFG_ALL_N_KV_HEADS": model["N_KV_HEADS"],
        "TASKCFG_ALL_D_FF": model["D_FF"],
    }
    task = "server-0-api"
    deployment = Deployment(
        "serve", os.path.join(JAX_DIR, "svc_serve.yml"), workdir, chips,
        env, child_env,
    )
    try:
        deployment.wait_listening()
        # readiness gates on warm: COMPLETE means it can answer
        deploy_s = deployment.wait_deploy_complete(
            task, 300 if tiny else 900
        )
        _code, endpoint = http_json(f"{deployment.url}/v1/endpoints/http")
        address = endpoint["address"][0]
        base = f"http://{address}"

        def generate(rows, n):
            code, body = http_json(
                f"{base}/generate",
                {"tokens": rows, "max_new_tokens": n}, timeout=180,
            )
            if code != 200:
                raise deployment.fail(f"/generate answered {code}", task)
            got = body["tokens"]
            if len(got) != len(rows) or any(len(r) != n for r in got):
                raise deployment.fail(
                    f"asked {len(rows)} row(s) x {n} tokens, got "
                    f"{[len(r) for r in got]}", task,
                )
            vocab = int(model["VOCAB"])
            if any(not 0 <= t < vocab for r in got for t in r):
                raise deployment.fail("token outside the vocabulary", task)
            return got

        def prompt(length, start=2):
            return [(start + i) % int(model["VOCAB"]) for i in range(length)]

        batch = 8  # svc_serve.yml's SERVE_BATCH default
        single = generate([prompt(32)], new_tokens)
        again = generate([prompt(32)], new_tokens)
        if single != again:
            raise deployment.fail(
                "the same greedy prompt gave two different replies", task
            )
        full = generate(
            [prompt(8 + 3 * i, start=3 + i) for i in range(batch)],
            new_tokens // 2,
        )
        if full[0] == full[1] == full[2]:
            raise deployment.fail(
                "different prompts gave identical replies", task
            )
        with concurrent.futures.ThreadPoolExecutor(max_workers=2) as pool:
            futures = [
                pool.submit(generate, [prompt(length, start=5)], n)
                for length, n in ((5, new_tokens), (max_len // 2, 4))
            ]
            for future in futures:
                future.result(timeout=240)
        _code, stats = http_json(f"{base}/stats")
        # (a one-chip pod on a four-chip host sees all four: nothing
        # sets chip visibility yet, so the count is reported, not held)
        check_worker_platform(stats, tiny, "serve worker")
        for key in ("VOCAB", "D_MODEL", "N_LAYERS", "N_HEADS", "D_FF"):
            if str(stats.get("model", {}).get(key.lower())) != model[key]:
                raise deployment.fail(
                    f"server built {stats.get('model')}, asked {model}", task
                )
        if stats.get("requests_completed", 0) < 5:
            raise deployment.fail(f"/stats counted {stats}", task)
        report = {
            "leg": "serve",
            "platform": stats["platform"],
            "device_kind": stats["device_kind"],
            "device_count": stats["device_count"],
            "model": stats["model"],
            "deploy_plan_complete_s": round(deploy_s, 1),
            "warm_s": stats.get("warm_s"),
            "endpoint": address,
            "requests_completed": stats["requests_completed"],
            "tokens_out": stats.get("tokens_out"),
        }
        say("  SERVE " + json.dumps(report))
    finally:
        left = deployment.stop()
    if left:
        raise SmokeFailure(f"serve leg left processes alive: {left}")
    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Drive train and serve through scheduler -> agent "
                    "-> worker on the chip, once."
    )
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="chips of the host the train pod owns (4 = one 2x2 host)",
    )
    parser.add_argument(
        "--tiny-cpu", action="store_true",
        help="CPU dry run of the same control flow at toy widths; "
             "proves nothing about the device",
    )
    parser.add_argument("--leg", choices=("kernels",), help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.leg == "kernels":
        return kernel_leg(args.tiny_cpu, args.chips)

    needed = [
        os.path.join(HERE, "dcos_commons_tpu", "__main__.py"),
        os.path.join(JAX_DIR, "svc.yml"),
        os.path.join(JAX_DIR, "svc_serve.yml"),
    ]
    missing = [path for path in needed if not os.path.exists(path)]
    if missing:
        print(
            "chip_smoke: FAILED: not inside a tpu-service-sdk checkout "
            f"(missing {', '.join(os.path.relpath(m, HERE) for m in missing)})",
            file=sys.stderr,
        )
        return 2

    child_env = dict(os.environ)
    if args.tiny_cpu:
        say(
            "CPU DRY RUN (--tiny-cpu): toy widths on JAX_PLATFORMS=cpu, "
            "kernels interpreted.  This exercises the control flow only "
            "and proves nothing about the device."
        )
        child_env["JAX_PLATFORMS"] = "cpu"
        # the pod's chips, as host-platform devices
        child_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}"
        )
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    say("HOST " + host_limits(workdir))
    t0 = time.monotonic()
    try:
        device = run_kernel_leg(args.tiny_cpu, args.chips, child_env)
        run_train_leg(args.tiny_cpu, args.chips, workdir, child_env)
        run_serve_leg(args.tiny_cpu, args.chips, workdir, child_env)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        print(f"chip_smoke: host {host_limits(workdir)}", file=sys.stderr)
        if os.listdir(workdir):
            print(f"chip_smoke: logs kept in {workdir}", file=sys.stderr)
        else:
            os.rmdir(workdir)
        return 1
    shutil.rmtree(workdir, ignore_errors=True)
    say(f"all legs passed in {time.monotonic() - t0:.0f}s")
    result = {"ok": True, "device": device}
    if args.tiny_cpu:
        result["cpu_dry_run"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
