"""Host provisioning: agent --provision-cmd + compile-cache seeding.

The first deploy on a fresh host must not pay a full XLA compile —
provisioning seeds the persistent compilation cache
(frameworks/jax/warm_cache.py) before the daemon takes tasks.
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_warm_cache_seeds_compilation_cache(tmp_path):
    cache = tmp_path / "xla-cache"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(cache),
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "frameworks/jax/warm_cache.py")],
        env=env, capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 0, proc.stderr
    assert f"seeding {cache} on cpu" in proc.stdout
    assert "seeded mnist" in proc.stdout
    entries = os.listdir(cache)
    assert entries, "no cache entries written"


def test_warm_cache_defaults_to_the_checkouts_fixed_directory(tmp_path):
    """With no JAX_COMPILATION_CACHE_DIR the cache is ONE fixed
    directory inside the checkout the script runs from (the same one
    its tasks will read), never a temp name."""
    checkout = tmp_path / "checkout"
    (checkout / "frameworks" / "jax").mkdir(parents=True)
    os.symlink(
        os.path.join(REPO, "dcos_commons_tpu"), checkout / "dcos_commons_tpu"
    )
    script = checkout / "frameworks" / "jax" / "warm_cache.py"
    script.write_text(
        open(os.path.join(REPO, "frameworks/jax/warm_cache.py")).read()
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=180,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr
    assert f"seeding {checkout / '.jax_cache'} on cpu" in proc.stdout
    assert os.listdir(checkout / ".jax_cache")


def test_agent_provision_cmd_runs_before_serving(tmp_path):
    marker = tmp_path / "provisioned"
    announce = tmp_path / "announce"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dcos_commons_tpu", "agent",
            "--host-id", "h0",
            "--workdir", str(tmp_path / "sandboxes"),
            "--announce-file", str(announce),
            "--provision-cmd", f"echo ok > {marker}",
        ],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not announce.exists():
            time.sleep(0.1)
        # serving implies provisioning already finished
        assert announce.exists(), "daemon never announced"
        assert marker.read_text().strip() == "ok"
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_agent_provision_failure_aborts(tmp_path):
    proc = subprocess.run(
        [
            sys.executable, "-m", "dcos_commons_tpu", "agent",
            "--host-id", "h0",
            "--workdir", str(tmp_path / "sandboxes"),
            "--provision-cmd", "exit 7",
        ],
        cwd=REPO, capture_output=True, text=True, timeout=30,
    )
    assert proc.returncode == 7
    assert "provisioning failed" in proc.stderr
