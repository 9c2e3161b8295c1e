"""Device mesh construction + named sharding helpers.

Axes vocabulary (scaling-book conventions):
    dcn   cross-slice data parallel — batch split ACROSS ICI slices,
          gradient allreduce rides the data-center network (the only
          collective that should: params replicate over dcn)
    dp    data parallel — batch split, gradient allreduce
    fsdp  fully-sharded data parallel — params/optimizer sharded,
          all-gathered per layer
    ep    expert parallel — MoE experts split, all_to_all dispatch
    pp    pipeline parallel — layer stages split, ppermute activations
    tp    tensor parallel — heads/ffn split, activation collectives
    sp    sequence/context parallel — ring attention over sequence
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; axes with size 1 are kept (harmless)."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    dcn: int = 1

    @property
    def total(self) -> int:
        return (self.dcn * self.dp * self.fsdp * self.tp * self.sp
                * self.pp * self.ep)

    def axes(self) -> Dict[str, int]:
        return {
            "dcn": self.dcn,
            "dp": self.dp,
            "fsdp": self.fsdp,
            "ep": self.ep,
            "pp": self.pp,
            "sp": self.sp,
            "tp": self.tp,
        }


def make_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh whose device order follows the hardware order.

    jax puts same-host devices adjacent in jax.devices(); keeping the
    fastest-varying mesh axis (tp) innermost maps tp collectives onto
    intra-host ICI first — the scaling-book layout rule.
    """
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < spec.total:
        raise ValueError(
            f"mesh {spec} needs {spec.total} devices, have {len(devices)}"
        )
    devices = devices[: spec.total]
    # tp innermost (intra-host ICI), then sp ring, then pp neighbors,
    # then ep all_to_alls; dp/fsdp outer, and dcn OUTERMOST — jax
    # orders devices slice-by-slice, so the leading axis is exactly
    # the slice boundary and only dcn collectives cross it
    arr = np.array(devices).reshape(
        spec.dcn, spec.dp, spec.fsdp, spec.ep, spec.pp, spec.sp, spec.tp
    )
    return Mesh(arr, ("dcn", "dp", "fsdp", "ep", "pp", "sp", "tp"))


def derive(env: Dict[str, str], n_devices: Optional[int] = None) -> MeshSpec:
    """Derive the MeshSpec from the scheduler's env contract — PURE
    shape math, no device queries, so analyzers (analysis/shardcheck)
    evaluate it abstractly and :func:`mesh_from_env` builds the real
    mesh from the same derivation.

    TPU_TOPOLOGY "XxY" at TPU_CHIPS_PER_HOST chips/host: default to
    dp over hosts x tp within host — the layout the torus placement
    guarantees is ICI-contiguous.  Multi-slice gangs (TPU_NUM_SLICES)
    lay a dcn axis over the slice boundary.

    Without ``n_devices`` the chip count comes from the declared
    topology (times slices), i.e. what the spec promises at deploy.
    A declared TPU_TOPOLOGY whose per-slice chip count
    TPU_CHIPS_PER_HOST does not divide raises SpecError: that spec can
    never lay the promised host-aligned mesh, and silently falling
    back to a pure-dp layout would train with a layout the operator
    never asked for.  With no topology declared (ad-hoc envs, local
    dryruns) the fallback stays graceful.
    """
    from dcos_commons_tpu.specification.specs import SpecError

    # 0 is the "probe the local runtime" sentinel, not a chip count;
    # options.json's 4 only applies to rendered deploys
    # sdklint: disable=config-default-drift — autodetect sentinel
    chips_per_host = int(env.get("TPU_CHIPS_PER_HOST", "0") or 0)
    n_slices = int(env.get("TPU_NUM_SLICES", "1") or 1)
    topology = env.get("TPU_TOPOLOGY", "")
    if n_devices is None:
        if topology:
            try:
                dims = [int(d) for d in topology.lower().split("x")]
            except ValueError:
                raise SpecError(f"bad topology {topology!r}")
            if not dims or any(d <= 0 for d in dims):
                raise SpecError(f"bad topology {topology!r}")
            per_slice = 1
            for d in dims:
                per_slice *= d
        else:
            per_slice = max(chips_per_host, 1)
        n = per_slice * max(n_slices, 1)
    else:
        n = n_devices
    if n_slices > 1 and n % n_slices == 0:
        # multi-slice gang: dcn (pure data parallel) over the slice
        # boundary, dp x tp within each slice over ICI
        per_slice = n // n_slices
        if chips_per_host and per_slice % chips_per_host == 0 \
                and per_slice >= chips_per_host:
            return MeshSpec(
                dcn=n_slices,
                dp=per_slice // chips_per_host,
                tp=chips_per_host,
            )
        if chips_per_host and per_slice % chips_per_host and topology:
            raise SpecError(
                f"TPU_CHIPS_PER_HOST={chips_per_host} does not divide "
                f"the {per_slice}-chip slice of topology {topology!r}: "
                "no host-aligned mesh exists for this spec"
            )
        return MeshSpec(dcn=n_slices, dp=per_slice)
    if chips_per_host and n % chips_per_host == 0 and n > chips_per_host:
        return MeshSpec(dp=n // chips_per_host, tp=chips_per_host)
    if chips_per_host and n % chips_per_host and topology:
        raise SpecError(
            f"TPU_CHIPS_PER_HOST={chips_per_host} does not divide the "
            f"{n} chips of topology {topology!r}: no host-aligned mesh "
            "exists for this spec"
        )
    return MeshSpec(dp=n)


def mesh_from_env(env: Dict[str, str], n_devices: Optional[int] = None) -> Mesh:
    """Build the Mesh :func:`derive` prescribes for this env contract."""
    n = n_devices if n_devices is not None else len(jax.devices())
    return make_mesh(derive(env, n))


def elastic_reshard_ok(old: MeshSpec, new: MeshSpec) -> bool:
    """True when a checkpoint written under ``old`` restores onto
    ``new`` as a pure re-layout — elastic-DP resize (ISSUE 13).

    The contract: only the batch axes (``dp``/``dcn``) may change.
    Params and optimizer state are REPLICATED over dp/dcn, so a
    changed width re-lays the same leaves; any model-sharding axis
    changing (tp/sp/pp/ep/fsdp) would change leaf SHARDS, and the
    host-gathered npz checkpoint would silently restore a different
    parallelism than the step function expects.  The worker refuses
    that resume loudly instead.

    A whole-slice drop or regrow (ISSUE 20 multi-slice elasticity)
    is exactly a dcn change — the per-slice topology, and with it
    every model axis, is untouched — so it rides this rule with no
    special case."""
    return (
        old.tp == new.tp
        and old.sp == new.sp
        and old.pp == new.pp
        and old.ep == new.ep
        and old.fsdp == new.fsdp
    )


# -- sharding rules ---------------------------------------------------

Rules = Tuple[Tuple[str, PartitionSpec], ...]


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


BATCH_AXES = ("dcn", "dp", "fsdp")  # batch shards over all data axes


def batch_spec() -> PartitionSpec:
    return PartitionSpec(BATCH_AXES, "sp")  # [batch, seq, ...]


def replicated() -> PartitionSpec:
    return PartitionSpec()


# -- kernels under a mesh ---------------------------------------------
#
# A pallas_call is an opaque custom call to GSPMD: it has no
# partitioning rule, so inside a sharded jit XLA all-gathers its
# operands and every chip runs the kernel over the GLOBAL array.  The
# ops therefore run their kernels through shard_map on each device's
# shard.  The mesh is the AMBIENT abstract mesh (jax.sharding.
# use_abstract_mesh / jax.set_mesh): make_train_step enters it while
# its step is traced, so the model code between the step and the
# kernels needs no mesh argument.


def ambient_axes(size: int, axes: Sequence[str]) -> Optional[Tuple[str, ...]]:
    """The ``axes`` of the ambient mesh a dimension of ``size`` shards
    over: those bound with more than one device, if their product
    divides ``size``.  None (= unsharded, the PartitionSpec spelling)
    with no ambient mesh, inside an enclosing shard_map (its manual
    axes are already per-shard), or when the dimension does not split
    evenly — the kernel then sees that dimension whole."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return None
    bound = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    parts = 1
    for a in bound:
        parts *= mesh.shape[a]
    return bound if bound and size % parts == 0 else None


def per_shard(fn, in_specs, out_specs):
    """``fn`` run on each device's shard under the ambient mesh, or
    ``fn`` itself when no spec shards anything."""
    from dcos_commons_tpu.parallel.compat import shard_map

    specs = list(in_specs) + [out_specs]
    if not any(axis is not None for spec in specs for axis in spec):
        return fn
    return shard_map(
        fn, in_specs=tuple(in_specs), out_specs=out_specs, check_vma=False
    )
