"""What a worker actually runs on, stated once at start-up.

With ``JAX_PLATFORMS`` unset, JAX answers a TPU it cannot get (held by
another process, or absent) by falling back to the CPU without an
error, and a worker that carries on would train or serve at CPU speed
under a ``tpu:`` pod's name.  :func:`claim_devices` is the first thing
every jax worker does after importing jax: it initialises the backend,
refuses the silent fallback, and returns the report the worker logs
and mirrors into its telemetry (steplog / servestats).
"""

from __future__ import annotations

import os
from typing import Dict, Mapping, Optional


def claim_devices(env: Optional[Mapping[str, str]] = None) -> Dict[str, object]:
    """Initialise the JAX backend and report it.

    Raises RuntimeError when the scheduler's env contract says this is
    a ``tpu:`` pod (``TPU_GENERATION``) but JAX landed on another
    platform — unless ``JAX_PLATFORMS=cpu`` asked for exactly that
    (tests, CPU fleets).
    """
    import jax

    env = os.environ if env is None else env
    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }
    asked_cpu = env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if env.get("TPU_GENERATION") and not asked_cpu \
            and report["platform"] != "tpu":
        raise RuntimeError(
            f"tpu: pod worker (TPU_GENERATION={env['TPU_GENERATION']}) "
            f"got platform {report['platform']!r}: the chip is absent "
            "or held by another process.  Set JAX_PLATFORMS=cpu to run "
            "on the CPU deliberately."
        )
    return report
