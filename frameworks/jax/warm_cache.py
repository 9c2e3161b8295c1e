"""Provision-time compile-cache seeding (VERDICT r3 #8).

Run ONCE when a host is provisioned (agent `--provision-cmd`, or by
hand): it compiles the framework's standard programs at their deployed
shapes into the persistent cache the tasks will read — the directory
`JAX_COMPILATION_CACHE_DIR` names, else the checkout's fixed default
(utils/compile_cache.py) — so the FIRST deploy on a fresh host pays
disk-cache-hit time instead of a full XLA compile.  Programs are compiled with `jax.jit(...).lower().
compile()` (no data, no training) and selected by WARM_TARGETS
(comma list; default: mnist).

The cache key covers the jaxpr + compile options + device kind, so a
seeded entry hits exactly when the real task would have compiled the
same program (utils/compile_cache.py).
"""

import os
import sys
import time

# the package is found from this file (frameworks/jax/ sits two levels
# under the checkout): tasks run with their sandbox as cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))


def warm_mnist() -> None:
    import jax
    import optax

    from dcos_commons_tpu.models import MlpConfig, mlp_init, mlp_train_step
    from dcos_commons_tpu.utils import synthetic_mnist

    config = MlpConfig()
    params = mlp_init(config, jax.random.key(0))
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step_fn = mlp_train_step(optimizer)
    x, y = synthetic_mnist(jax.random.key(1), 256)
    # lower + compile ONLY: provisioning must not run a training step
    jax.jit(step_fn).lower(params, opt_state, x, y).compile()


def main() -> int:
    from dcos_commons_tpu.utils import (
        claim_devices,
        enable_compilation_cache,
    )

    # the cache key covers the device kind: seeding from a CPU fallback
    # would write entries no TPU task ever reads
    devices = claim_devices()
    cache_dir = enable_compilation_cache()
    print(
        f"warm_cache: seeding {cache_dir} on {devices['platform']} "
        f"({devices['device_kind']})", flush=True,
    )
    targets = os.environ.get("WARM_TARGETS", "mnist").split(",")
    for target in targets:
        target = target.strip()
        fn = globals().get(f"warm_{target}")
        if fn is None:
            print(f"warm_cache: unknown target {target!r}",
                  file=sys.stderr)
            return 1
        t0 = time.time()
        fn()
        print(f"warm_cache: seeded {target} in {time.time()-t0:.1f}s",
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
