"""The three jax names the parallelism layer needs, in one place.

The repo runs on one installation (jax 0.9): ``shard_map`` is the
top-level export with the ``check_vma`` spelling, ``axis_size`` is a
``lax`` primitive helper, and marking a value device-varying for
shard_map's replication checker is ``lax.pcast(..., to="varying")``.
Everything in this repo (and its tests) imports them from here.
"""

from __future__ import annotations

from jax import lax
from jax import shard_map  # noqa: F401 — re-exported
from jax.lax import axis_size  # noqa: F401 — re-exported


def pvary(x, axis_names):
    """Mark ``x`` device-varying over ``axis_names`` for shard_map's
    replication (vma) checker."""
    return lax.pcast(x, tuple(axis_names), to="varying")


__all__ = ["axis_size", "pvary", "shard_map"]
