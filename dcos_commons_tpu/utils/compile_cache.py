"""Persistent XLA compilation cache plumbing.

Everything under jit is traced once and compiled; on a fresh process
that compile dominates small-workload wall-clock.  The persistent
cache keys compiled executables by HLO + platform + compile flags, so
any repeat deploy — scheduler restart, recovery relaunch, a second
bench pass — skips straight to execution.  The reference has no
analogue (its tasks are arbitrary binaries); this is TPU-first
operational surface.

Where the cache lives is decided OUTSIDE the program: the directory
``$JAX_COMPILATION_CACHE_DIR`` names when it is set, and otherwise one
fixed directory inside the checkout.  The directory is part of nothing
the key hashes, but a cache that moves between runs never hits, so no
code path may invent a temp name, a pid or a timestamp for it.  The
agent passes its env through to tasks, so scheduler, agent and workers
all land on the same directory.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (git-ignored): derived from this file, so every
# process of one checkout agrees on it whatever its cwd
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its
    directory.  Safe to call before or after first device use.

    The min-compile-time floor is zeroed: a scheduler deploy launches
    MANY short-compile programs (MLP train step, eval, host transfers)
    and the default 1s floor would skip exactly the programs a warm
    relaunch needs."""
    import jax

    cache_dir = os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
