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

Beside the cache, under ``<directory>/programs/``, lies the store of
the serving pool's two device programs (utils/stored_program.py; one
file ``<program>-<key>.program`` a set of argument types, the newest
few a program kept).  The cache alone leaves a warm start tracing and
lowering each program only to compute the key of an executable it then
reads in a fraction of a second; the store is keyed by what the host
knows without tracing (the package's sources, the versions, the
device, the flags, the model's configuration and the arguments' types),
so a warm start deserializes both programs and lowers nothing.  The
worker's ``/stats`` says which happened: ``startup.warm[_prefill /
_decode].source`` is ``"stored"`` (with ``load_s``, and ``trace_s``,
``lower_s`` and ``compile_s`` all 0) or ``"compiled"`` (with
``store_s``, the serialization and the write, where the entry was
written), and ``startup.programs`` counts both.  A process that has
not turned the cache on stores nothing.  Deleting the directory, or
any file in it, is always safe: the next start compiles (the cache
serves it) and stores again.
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


def programs_dir():
    """Where this process's stored programs lie: ``programs/`` under
    the compile cache's directory, None while the cache is off."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    return os.path.join(cache_dir, "programs") if cache_dir else None


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
