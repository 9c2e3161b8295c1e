"""XLA collective/compute overlap flags for the training fast path.

The step-loop restructuring (ISSUE 7: `make_train_step` microbatched
gradient accumulation, donated buffers, the worker's bounded
in-flight window) gives XLA per-microbatch ICI/DCN collectives it
*can* overlap with the next microbatch's compute.  Whether it *does*
is governed by the latency-hiding scheduler: on several libtpu
builds the async-collective fusion passes default off, and a step
that could hide its reduce-scatters behind the backward pass instead
serializes them at the end (the megatron/alpa overlap discipline,
lost by default).

:func:`enable_collective_overlap` prepends the flag set to
``LIBTPU_INIT_ARGS`` — BEFORE jax initializes its backend, which is
why the worker calls it first thing in ``main()``.  Rules of
engagement:

* the flags are libtpu vocabulary and travel in libtpu's own variable:
  jaxlib parses ``XLA_FLAGS`` itself and aborts the process on a name
  it does not know (every ``xla_tpu_*`` flag), so ``XLA_FLAGS`` is
  never touched;
* TPU-only: nothing is added unless the scheduler's env contract says
  this is a TPU task (``TPU_GENERATION``) and ``JAX_PLATFORMS`` is not
  forcing cpu;
* the operator wins: a flag already spelled in ``LIBTPU_INIT_ARGS``
  (either polarity) is never overridden — ours are PREPENDED and the
  later spelling wins;
* ``TRAIN_XLA_OVERLAP=0`` opts the whole set out (the same escape
  hatch family as ``TRAIN_INFLIGHT_STEPS=0``).
"""

from __future__ import annotations

import os
from typing import List, MutableMapping, Optional

LIBTPU_ENV = "LIBTPU_INIT_ARGS"

# the latency-hiding scheduler set: fuse collectives with async
# start/done pairs and let the scheduler float compute between them
OVERLAP_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
)


def enable_collective_overlap(
    env: Optional[MutableMapping[str, str]] = None,
) -> List[str]:
    """Prepend the overlap flag set to ``env['LIBTPU_INIT_ARGS']``.

    Returns the flags actually added (empty when opted out, not a TPU
    task, or every flag was already spelled by the operator).  Pass a
    dict for tests; defaults to ``os.environ`` — call before the
    first jax import in the process.
    """
    env = os.environ if env is None else env
    if env.get("TRAIN_XLA_OVERLAP", "1") in ("0", "false"):
        return []
    if not env.get("TPU_GENERATION"):
        return []
    if "cpu" in env.get("JAX_PLATFORMS", "").lower():
        return []
    current = env.get(LIBTPU_ENV, "")
    # token-wise name match: a substring test would let the operator's
    # --..._fusion_fuse_all_gather spelling silently suppress the
    # shorter --..._fusion flag they never set
    current_names = {
        token.split("=", 1)[0] for token in current.split()
    }
    added = [
        flag for flag in OVERLAP_FLAGS
        if flag.split("=", 1)[0] not in current_names
    ]
    if added:
        env[LIBTPU_ENV] = " ".join(
            added + ([current] if current else [])
        )
    return added
