"""The deploy path's chip contract, as far as a CPU can hold it.

chip_smoke.py proves on the TPU that scheduler -> agent -> worker runs
there; these tests keep what that needs from regressing between chip
runs: the smoke's own control flow (``--tiny-cpu``), one process per
chip (scheduler and bench parent never initialise a JAX backend), no
fallback that hides the device, a compile cache placed from outside,
checkout-relative workers, and kernels that a sharded program can
lower for the TPU at all.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env=None, cwd=None, timeout=300):
    return subprocess.run(
        argv, env=env, cwd=cwd, capture_output=True, text=True,
        timeout=timeout,
    )


def _clean_env(**extra):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS", "REPO_ROOT")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


# -- chip_smoke.py -----------------------------------------------------


def test_chip_smoke_tiny_cpu_from_another_checkout(tmp_path):
    """All three legs end to end at toy widths, from a copy of the
    committed tree that is NOT this checkout and has no built
    native/bin: the workers import the package next to their script,
    the supervisor is built on first use, and with no
    JAX_COMPILATION_CACHE_DIR every process caches in THAT checkout's
    fixed directory."""
    checkout = tmp_path / "elsewhere"
    ignore = shutil.ignore_patterns("__pycache__", "bin", "*.pyc")
    shutil.copytree(
        os.path.join(REPO, "dcos_commons_tpu"),
        checkout / "dcos_commons_tpu", ignore=ignore,
    )
    shutil.copytree(
        os.path.join(REPO, "frameworks", "jax"),
        checkout / "frameworks" / "jax", ignore=ignore,
    )
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), checkout)
    assert not (checkout / "dcos_commons_tpu" / "native" / "bin").exists()

    proc = _run(
        [sys.executable, str(checkout / "chip_smoke.py"), "--tiny-cpu"],
        env=_clean_env(), cwd=str(tmp_path), timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert "CPU DRY RUN" in lines[0] and "proves nothing" in lines[0]
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["cpu_dry_run"] is True
    assert result["device"]["platform"] == "cpu"
    train = json.loads(
        next(l for l in lines if l.startswith("  TRAIN "))[len("  TRAIN "):]
    )
    serve = json.loads(
        next(l for l in lines if l.startswith("  SERVE "))[len("  SERVE "):]
    )
    assert train["platform"] == serve["platform"] == "cpu"
    assert train["loss_last"] < train["loss_first"]
    assert len(train["checkpoints"]) >= 1
    # the worker found the package — and the cache — in ITS checkout
    assert train["compile_cache"] == str(checkout / ".jax_cache")
    assert os.listdir(checkout / ".jax_cache")


def test_chip_smoke_fails_fast_without_an_accelerator():
    proc = _run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_clean_env(), timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        env=_clean_env(), cwd=str(tmp_path), timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not inside a tpu-service-sdk checkout" in proc.stderr


# -- one process per chip ----------------------------------------------

_ADMIT = """
import sys, urllib.request
sys.path.insert(0, {repo!r})
from dcos_commons_tpu.http import ApiServer
from dcos_commons_tpu.multi import MultiServiceScheduler
from dcos_commons_tpu.offer.inventory import SliceInventory, TpuHost
from dcos_commons_tpu.scheduler import SchedulerConfig
from dcos_commons_tpu.storage import MemPersister
from dcos_commons_tpu.testing import FakeAgent

host = TpuHost(host_id="h0", slice_id="s", generation="v5e", grid=(0, 0),
               chip_block=(1, 1), cpus=8.0, memory_mb=32768)
multi = MultiServiceScheduler(
    persister=MemPersister(), inventory=SliceInventory([host]),
    agent=FakeAgent(), scheduler_config=SchedulerConfig(backoff_enabled=False),
)
server = ApiServer(multi=multi).start()
try:
    with open({svc!r}, "rb") as f:
        request = urllib.request.Request(
            server.url + "/v1/multi/jax-serve", method="PUT", data=f.read())
    with urllib.request.urlopen(request) as response:
        assert response.status == 200, response.status
    multi.run_cycle()
finally:
    server.stop()
# admission DID evaluate the jax workload profile (shardcheck) ...
assert "jax" in sys.modules and multi.service_names() == ["jax-serve"]
from jax._src import xla_bridge
# ... and the scheduler process still owns no device
print("BACKENDS_INITIALISED", xla_bridge.backends_are_initialized())
"""


def test_scheduler_admits_a_jax_service_without_a_backend():
    """PUT /v1/multi/<name> runs shardcheck's workload profile inside
    the scheduler process.  On a TPU host a backend init there takes
    the chip the agent-launched worker needs."""
    code = _ADMIT.format(
        repo=REPO,
        svc=os.path.join(REPO, "frameworks", "jax", "svc_serve.yml"),
    )
    proc = _run([sys.executable, "-c", code], env=_clean_env())
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "BACKENDS_INITIALISED False" in proc.stdout


_BENCH_PARENT = """
import json, os, sys
sys.path.insert(0, {repo!r})
import bench

children = []

def child_section(fn_name, timeout_s, env=None, rename=None):
    children.append(fn_name)
    if fn_name == "bench_transformer":
        raise RuntimeError("chip section failed")
    return {{}}

bench._run_subprocess_section = child_section
# the slow control-plane sections are not what this test is about
for name in ("bench_scheduler_scale", "bench_offer_cycle",
             "bench_fleet_scale", "bench_trace_overhead",
             "bench_health_overhead", "bench_failover",
             "bench_preemption_recovery", "bench_multislice",
             "bench_slo_recovery"):
    setattr(bench, name, lambda: {{}})
from dcos_commons_tpu.analysis import configcheck, durcheck
configcheck.analyze_all = durcheck.analyze_tree = None
# every function that computes on the chip must run in a child
for name in ("bench_rooflines", "bench_transformer", "bench_profile",
             "bench_mfu_frontier", "bench_decode", "bench_decode_int8",
             "bench_decode_w8", "bench_serve", "bench_moe"):
    def in_parent(name=name):
        raise AssertionError(name + " ran in the bench parent")
    setattr(bench, name, in_parent)
try:
    bench.main()   # helloworld + three real MNIST deploys (CPU tasks)
    code = 0
except SystemExit as e:
    code = e.code
bridge = sys.modules.get("jax._src.xla_bridge")
print("PARENT", json.dumps({{
    "exit": code,
    "children": children,
    "backend": bool(bridge and bridge.backends_are_initialized()),
    "cache_env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
}}))
"""


def test_bench_parent_stays_off_jax_and_fails_on_a_chip_error(tmp_path):
    """bench.py's parent deploys MNIST through the real control plane
    (the task is a child) and hands every chip section to a child of
    its own; it never initialises a backend, never moves the compile
    cache, and a chip section that errors fails the run AFTER the JSON
    line."""
    cache = tmp_path / "cache"
    proc = _run(
        [sys.executable, "-c", _BENCH_PARENT.format(repo=REPO)],
        env=_clean_env(
            JAX_COMPILATION_CACHE_DIR=str(cache), BENCH_MNIST_STEPS="12",
        ),
        timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    parent = json.loads(
        next(l for l in lines if l.startswith("PARENT "))[len("PARENT "):]
    )
    assert parent["backend"] is False
    assert parent["cache_env"] == str(cache)
    assert parent["children"][:3] == [
        "bench_router_scale", "bench_disagg", "bench_train_step",
    ]
    chip = parent["children"][parent["children"].index("bench_rooflines"):]
    assert chip[:3] == ["bench_rooflines", "bench_transformer",
                        "bench_profile"]
    assert "bench_serve" in chip and "bench_moe" in chip
    headline = json.loads(next(l for l in lines if l.startswith("{")))
    extras = headline["extras"]
    assert extras["deploy_completed"] and extras["deploy_warm_completed"]
    assert extras["deploy_true_cold_completed"]
    assert "transformer_error" in extras
    assert parent["exit"] == 1
    # the tasks cached where the variable said, nowhere else
    assert os.listdir(cache)


# -- no fallback that hides the device ---------------------------------


def test_claim_devices_refuses_a_silent_cpu_fallback():
    from dcos_commons_tpu.utils import claim_devices

    # this process runs on the CPU (conftest); a tpu: pod's worker that
    # did not ASK for the CPU must not carry on there
    with pytest.raises(RuntimeError, match="got platform 'cpu'"):
        claim_devices({"TPU_GENERATION": "v5e"})
    with pytest.raises(RuntimeError, match="got platform 'cpu'"):
        claim_devices({"TPU_GENERATION": "v5e", "JAX_PLATFORMS": "tpu,cpu"})
    report = claim_devices({"TPU_GENERATION": "v5e", "JAX_PLATFORMS": "cpu"})
    assert report["platform"] == "cpu" and report["device_count"] >= 1
    assert claim_devices({})["platform"] == "cpu"  # not a tpu: pod


def test_unknown_device_kind_is_an_error_not_a_default_peak():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert bench._peak_bf16_tflops(v5e) == 197.0
    for kind, platform in (("TPU v9", "tpu"), ("cpu", "cpu"), ("", "tpu")):
        device = types.SimpleNamespace(device_kind=kind, platform=platform)
        with pytest.raises(ValueError, match="no bf16 peak known"):
            bench._peak_bf16_tflops(device)


# -- compile cache placed from outside ---------------------------------

_CACHE = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
from dcos_commons_tpu.utils import enable_compilation_cache
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
print("UNSET", enable_compilation_cache(), jax.config.jax_compilation_cache_dir)
os.environ["JAX_COMPILATION_CACHE_DIR"] = {outside!r}
print("SET", enable_compilation_cache(), jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_is_fixed_in_checkout_or_where_the_env_says(tmp_path):
    outside = str(tmp_path / "from-outside")
    proc = _run(
        [sys.executable, "-c", _CACHE.format(repo=REPO, outside=outside)],
        env=_clean_env(), cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    fixed = os.path.join(REPO, ".jax_cache")
    assert f"UNSET {fixed} {fixed}" in proc.stdout
    assert f"SET {outside} {outside}" in proc.stdout


# -- kernels a sharded program can lower -------------------------------


def test_sharded_train_step_lowers_mosaic_kernels_per_shard(monkeypatch):
    """A pallas_call has no GSPMD partitioning rule: under a
    multi-device jit its TPU lowering raises unless the call sits in a
    shard_map.  Lower the mesh train step FOR the tpu platform (no
    compile, no chip) and read the kernels back: all four, each over
    ONE device's batch shard."""
    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.models import (
        TransformerConfig,
        init_params,
        make_train_step,
    )
    from dcos_commons_tpu.ops.introspect import mosaic_calls
    from dcos_commons_tpu.parallel.mesh import MeshSpec, make_mesh

    # the ops dispatch to Pallas by backend; say "tpu" while tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = TransformerConfig(
        vocab=256, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=256, max_seq=256, dtype=jnp.bfloat16,
    )
    optimizer = optax.adamw(3e-4)
    params = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    opt_state = jax.eval_shape(optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((8, 256), jnp.int32)
    mesh = make_mesh(MeshSpec(dp=4, tp=2))
    step = make_train_step(config, optimizer, mesh=mesh)
    lowered = step.trace(params, opt_state, tokens, tokens).lower(
        lowering_platforms=("tpu",)
    )
    calls = mosaic_calls(lowered.as_text())
    assert set(calls) == {
        "flash_attention_fwd", "flash_attention_dq",
        "flash_attention_dkv", "rms_norm_fwd",
    }
    # batch 8 over dp=4, 2 heads over tp=2: [2 * 1, 256, 128] per device
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        for signature in calls[name]["operands"]:
            assert all(
                operand.startswith("tensor<2x256x128x")
                for operand in signature.split(", ")
            ), (name, signature)
    # the norms: 2 rows of 256 tokens per device, the weight whole
    assert calls["rms_norm_fwd"]["operands"] == [
        "tensor<512x256xbf16>, tensor<256xbf16>"
    ]
