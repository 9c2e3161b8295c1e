"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding
(dp/tp/sp) is exercised without TPU hardware, mirroring how the
reference tests multi-node scheduling without a Mesos cluster
(reference: sdk/testing/ServiceTestRunner.java runs the full scheduler
against MemPersister + a mocked driver).
"""

import os

# force CPU even when a real TPU is attached: tests exercise sharding
# on the virtual mesh; chip_smoke.py and bench.py are what run on the
# chip.  Set before jax is imported, and inherited by every worker a
# test launches.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: jax-compiling or multi-process e2e (seconds to minutes); "
        "run the fast tier with -m 'not slow' (docs/testing.md)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: scheduler kill-matrix runs (testing/chaos.py) — real "
        "task processes, one run per kill point; always also marked "
        "slow so tier-1's -m 'not slow' skips them; select with "
        "-m chaos, replay failures with CHAOS_SEED=<seed> "
        "(docs/testing.md)",
    )


# whole modules that are inherently heavy: every test either compiles
# a jax model or spawns scheduler/agent processes.  Mixed files mark
# their heavy tests individually with @pytest.mark.slow.
_SLOW_FILES = {
    "test_serve.py",            # process-level scheduler e2e
    "test_workload.py",         # model training (jax compiles)
    "test_decode.py",           # KV-cache inference (jax compiles)
    "test_soak.py",             # event-loop churn soak
    "test_parallel_pp_ep.py",   # sharded training (jax compiles)
    "test_serve_inference.py",  # real serve_worker processes
    "test_data.py",             # device prefetch (jax)
    "test_provisioning.py",     # warm-cache subprocesses
}


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        if os.path.basename(str(item.fspath)) in _SLOW_FILES:
            item.add_marker(pytest.mark.slow)


# -- sdklint race checker (opt-in, SDKLINT_RACECHECK=1) ---------------
#
# Instruments threading.Lock/RLock/Condition and Thread.start/join for
# the whole session and fails the run if (a) the observed lock-nesting
# graph contains a cycle (deadlock risk) or (b) the vector-clock
# checker saw two unordered writes to a watched attribute (data race).
# SDKLINT_LOCKCHECK=1 is kept as a back-compat alias for the same
# switch.  tests/test_scheduler_e2e.py and tests/test_multi_service.py
# additionally enable the cycle check per-test regardless of the env
# var; the threaded modules (continuous batching, migration, HA
# failover, health, replication) add per-module write probes via
# racecheck_watch_guard().

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _sdklint_racecheck_session():
    from dcos_commons_tpu.analysis import racecheck

    if not racecheck.env_requested():
        yield
        return
    racecheck.install()
    yield
    report = racecheck.report()
    racecheck.unwatch_types()
    racecheck.uninstall()
    assert not report.cycles, report.describe()
    assert not report.races, report.describe()


def lockcheck_guard():
    """Shared body for the per-test lock-order fixtures in
    tests/test_scheduler_e2e.py and tests/test_multi_service.py
    (``yield from lockcheck_guard()``): install, run the test, fail it
    on any lock-order cycle.  Coexists with the session checker above
    — when that is active, the accumulated cross-test graph is left
    intact (no reset/uninstall)."""
    from dcos_commons_tpu.analysis import racecheck

    already = racecheck.is_enabled()
    racecheck.install()
    if not already:
        racecheck.reset()
    yield
    report = racecheck.report()
    if not already:
        racecheck.uninstall()
    assert not report.cycles, report.describe()


def racecheck_watch_guard(*classes):
    """Shared body for the per-module write-probe fixtures in the
    threaded test modules (``yield from racecheck_watch_guard(Cls,
    ...)``): when SDKLINT_RACECHECK=1 (or the legacy alias) is set,
    watch every attribute the static pass reports as cross-thread
    shared on the given classes, run the module's tests, and fail on
    any unordered write pair.  A no-op when the env var is unset so the
    fast tier pays nothing."""
    from dcos_commons_tpu.analysis import racecheck

    if not racecheck.env_requested():
        yield
        return
    import os as _os

    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    shared = racecheck.shared_write_map(root)
    for cls in classes:
        attrs = shared.get(cls.__name__)
        if attrs:
            racecheck.watch_type(cls, attrs)
    yield
    # session fixture asserts on the accumulated report at exit; probes
    # stay installed so later modules of the same run keep their watch.
