"""Deploys a service YAML through the real scheduler, its in-process
agent and the task the YAML names, and tears it down.  Stdlib only:
this process never touches JAX, the worker owns the chip.  (Taken from
``chip_smoke.py``'s ``Deployment``, which later PRs may change.)
"""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request


class DeployFailure(Exception):
    pass


def tail(path: str, lines: int = 25) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError:
        return f"<no {os.path.basename(path)}>"


def http_json(url: str, payload=None, timeout: float = 30.0):
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return response.status, json.loads(response.read())


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            # a zombie is gone for our purposes (its parent reaps it)
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except (OSError, IndexError):
        return False


class Deployment:
    """One scheduler process (with its in-process agent) serving one
    service YAML, and the task sandboxes under it."""

    def __init__(self, checkout: str, svc_yml: str, workdir: str,
                 chips: int, env: dict, child_env: dict):
        self.workdir = workdir
        self.sandboxes = os.path.join(workdir, "sandboxes")
        os.makedirs(workdir)
        topology = os.path.join(workdir, "topology.yml")
        block = {1: "[1, 1]", 4: "[2, 2]"}[chips]
        with open(topology, "w") as f:
            f.write(
                "hosts:\n"
                "  - host_id: bench-0\n"
                "    hostname: 127.0.0.1\n"
                "    slice_id: bench\n"
                "    generation: v5e\n"
                "    grid: [0, 0]\n"
                f"    chip_block: {block}\n"
                "    cpus: 8\n"
                "    memory_mb: 32768\n"
            )
        self.announce = os.path.join(workdir, "announce")
        self.log_path = os.path.join(workdir, "scheduler.log")
        argv = [
            sys.executable, "-m", "dcos_commons_tpu", "serve", svc_yml,
            "--topology", topology,
            "--port", "0",
            "--state-dir", os.path.join(workdir, "state"),
            "--sandbox-root", self.sandboxes,
            "--announce-file", self.announce,
        ]
        for key, value in env.items():
            argv += ["--env", f"{key}={value}"]
        self._log = open(self.log_path, "ab")
        self.started = time.monotonic()
        self.process = subprocess.Popen(
            argv, cwd=checkout, env=child_env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        self.url = ""

    def sandbox(self, task: str) -> str:
        return os.path.join(self.sandboxes, task)

    def fail(self, why: str, task: str = "") -> DeployFailure:
        detail = [why]
        if task:
            for stream in ("stderr", "stdout"):
                detail.append(f"--- {task} {stream} (tail)")
                detail.append(tail(os.path.join(self.sandbox(task), stream)))
        detail.append("--- scheduler.log (tail)")
        detail.append(tail(self.log_path, 15))
        return DeployFailure("\n".join(detail))

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
            time.sleep(0.1)
        raise self.fail("scheduler never announced its API")

    def task_died(self, task: str) -> bool:
        return bool(glob.glob(
            os.path.join(self.sandbox(task), ".super", "*", "exit_status")
        ))

    def wait_deploy_complete(self, task: str, timeout_s: float) -> float:
        """Seconds from the scheduler's launch to plan COMPLETE."""
        while time.monotonic() - self.started < timeout_s:
            if self.process.poll() is not None:
                raise self.fail("scheduler exited mid-deploy", task)
            if self.task_died(task):
                raise self.fail("the task exited during deploy", task)
            try:
                code, plan = http_json(f"{self.url}/v1/plans/deploy")
            except (OSError, ValueError):
                code, plan = 0, {}
            if code == 200 and plan.get("status") == "COMPLETE":
                return time.monotonic() - self.started
            time.sleep(0.25)
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
                time.sleep(0.05)
        return [p for p in pids if alive(p)]
