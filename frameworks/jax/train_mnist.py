"""Single-host MNIST training task (BASELINE.json config 3).

Launched by the scheduler inside a sandbox; trains the MLP on
synthetic MNIST for TRAIN_STEPS steps on the pod's TPU chip (or the
CPU when JAX_PLATFORMS=cpu asks for it, as the tests do), then exits 0
so the FINISH goal completes the deploy step.
"""

import json
import os
import sys
import time

# the package is found from this file (frameworks/jax/ sits two levels
# under the checkout): tasks run with their sandbox as cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))


def main() -> int:
    import jax
    import optax

    from dcos_commons_tpu.models import MlpConfig, mlp_init, mlp_train_step
    from dcos_commons_tpu.utils import (
        claim_devices,
        enable_compilation_cache,
        synthetic_mnist,
    )

    # a tpu: pod that fell back to the CPU stops here
    devices = claim_devices()
    print(f"devices: {json.dumps(devices)}", flush=True)
    # warm relaunches (scheduler restart, recovery, repeat deploys)
    # skip XLA recompilation entirely (utils/compile_cache.py)
    enable_compilation_cache()

    # demo-scale run: 60 steps converges MNIST; the options default
    # 100 sizes the full trainer
    # sdklint: disable=config-default-drift — demo scale
    steps = int(os.environ.get("TRAIN_STEPS", "60"))
    config = MlpConfig()
    params = mlp_init(config, jax.random.key(0))
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step_fn = mlp_train_step(optimizer)
    x, y = synthetic_mnist(jax.random.key(1), 256)

    t0 = time.time()
    first = last = None
    for i in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, x, y)
        if i == 0:
            loss.block_until_ready()
            first = float(loss)
            print(f"step 0 loss={first:.4f} (compile {time.time()-t0:.1f}s)",
                  flush=True)
    last = float(loss)
    print(
        f"trained {steps} steps on {devices['platform']}: "
        f"loss {first:.4f} -> {last:.4f}",
        flush=True,
    )
    return 0 if last < first else 1


if __name__ == "__main__":
    sys.exit(main())
