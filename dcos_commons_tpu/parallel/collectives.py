"""Collective and roofline benchmarks: the ICI north-star measurement.

BASELINE.json's second metric is pjit allreduce GB/s/chip at >80% of
ICI line rate on a multi-host slice.  This module measures it the
XLA-native way: a shard_map program per collective (psum, all_gather,
reduce_scatter, ppermute ring hop), iterated inside one compiled
lax.scan so dispatch overhead never touches the clock, timed end to
end, and converted to the standard algorithmic-bandwidth model
(ring allreduce moves 2(n-1)/n bytes per byte of payload per chip).

On a single chip the collectives degenerate, so the same module also
measures the chip rooflines the multi-chip numbers will sit under:
HBM copy bandwidth and bf16 matmul TFLOPs.

Reference analogue: none — the reference's "distributed communication
backend" is the Mesos scheduler API + ZooKeeper (SURVEY.md §5.8); the
data-plane bandwidth axis is green-field TPU work.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dcos_commons_tpu.parallel.compat import axis_size


# bytes moved over ICI per chip, per byte of the PER-CHIP shard S, on
# an n-ring (NCCL bus-bandwidth convention): allreduce carries S both
# ways in n-1 chunked steps (2(n-1)/n * S since the reduce+broadcast
# halves each move S/n per step over 2(n-1) steps... net 2(n-1)/n*S);
# all_gather forwards n-1 shard-sized chunks ((n-1)*S); tiled
# reduce_scatter reduces an n*S input down to S, also (n-1)*S per
# chip; a ring ppermute moves exactly S.
_ALGO_FACTOR = {
    "psum": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: float(n - 1),
    "reduce_scatter": lambda n: float(n - 1),
    "ppermute": lambda n: 1.0,
}


def _bench_fn(collective: str, axis: str, iters: int):
    """A shard_map body running `iters` chained collectives.

    The scan carries a data dependency through every iteration so XLA
    cannot elide or overlap the timed region away.
    """
    def body(x):
        def one(carry, _):
            if collective == "psum":
                out = lax.psum(carry, axis)
                # renormalize so values stay finite across iterations
                out = out / axis_size(axis)
            elif collective == "all_gather":
                gathered = lax.all_gather(carry, axis)
                out = gathered.mean(axis=0) + carry * 0.0
            elif collective == "reduce_scatter":
                out = lax.psum_scatter(
                    jnp.tile(carry, (axis_size(axis), 1)),
                    axis, scatter_dimension=0, tiled=True,
                ) / axis_size(axis)
            elif collective == "ppermute":
                n = axis_size(axis)
                perm = [(i, (i + 1) % n) for i in range(n)]
                out = lax.ppermute(carry, axis, perm)
            else:
                raise ValueError(collective)
            return out, None

        out, _ = lax.scan(one, x, None, length=iters)
        return out

    return body


def collective_bandwidth(
    mesh: Mesh,
    axis: str = "ici",
    payload_mb: float = 32.0,
    iters: int = 20,
    dtype=jnp.bfloat16,
) -> Dict[str, float]:
    """GB/s/chip for each collective over ``axis`` of ``mesh``.

    Payload is the per-chip shard size.  Returns
    {collective: algorithmic GB/s/chip} plus bookkeeping keys.
    """
    from dcos_commons_tpu.parallel.compat import shard_map

    n = mesh.shape[axis]
    bytes_per_elem = jnp.dtype(dtype).itemsize
    elems = int(payload_mb * 1e6 / bytes_per_elem)
    # 2D [rows, 128]: lane-friendly layout on TPU
    rows = max(elems // 128, 8)
    shard = jnp.ones((rows, 128), dtype)
    payload_bytes = shard.size * bytes_per_elem

    results: Dict[str, float] = {
        "axis_size": float(n),
        "payload_mb_per_chip": round(payload_bytes / 1e6, 2),
        "iters": float(iters),
    }
    if n < 2:
        return results
    # [n*rows, 128] sharded on rows: each chip's local block is `shard`
    replicated = jnp.tile(shard, (n, 1))

    for name, factor in _ALGO_FACTOR.items():
        fn = jax.jit(
            shard_map(
                _bench_fn(name, axis, iters),
                mesh=mesh,
                in_specs=P(axis),
                out_specs=P(axis),
                check_vma=False,
            )
        )
        jax.block_until_ready(fn(replicated))  # compile
        t0 = time.monotonic()
        jax.block_until_ready(fn(replicated))
        dt = time.monotonic() - t0
        moved = factor(n) * payload_bytes * iters
        results[name + "_gbps_per_chip"] = round(moved / dt / 1e9, 3)
    return results


def single_chip_rooflines(
    payload_mb: float = 256.0,
    iters: int = 20,
    chain_floor: int = 2000,
    matmul_dim: int = 4096,
) -> Dict[str, float]:
    """HBM copy GB/s and bf16 matmul TFLOPs on the default device —
    the ceilings any collective/compute number sits under.

    ``iters`` is a floor; chains are lengthened (chain_floor) so the
    one dispatch per timing is noise against the on-device work.  The
    times are raw: host clock around one call that ends in
    ``block_until_ready``.
    """
    out: Dict[str, float] = {}

    # HBM bandwidth: chained whole-array copies (read + write per iter)
    copy_iters = max(iters, chain_floor)
    elems = int(payload_mb * 1e6 / 2)
    rows = max(elems // 128, 8)
    x = jnp.ones((rows, 128), jnp.bfloat16)
    nbytes = x.size * 2

    @jax.jit
    def copy_chain(x):
        def one(carry, _):
            return carry + 1.0, None
        y, _ = lax.scan(one, x, None, length=copy_iters)
        return y

    jax.block_until_ready(copy_chain(x))  # compile
    t0 = time.monotonic()
    jax.block_until_ready(copy_chain(x))
    dt = time.monotonic() - t0
    out["hbm_copy_gbps"] = round(2 * nbytes * copy_iters / dt / 1e9, 3)

    # MXU roofline: chained bf16 matmuls (4k x 4k fills the MXU)
    mm_iters = max(iters, chain_floor)
    m = matmul_dim
    a = jnp.ones((m, m), jnp.bfloat16)

    @jax.jit
    def matmul_chain(a):
        def one(carry, _):
            prod = jnp.dot(carry, carry, preferred_element_type=jnp.bfloat16)
            return prod / jnp.float32(m).astype(jnp.bfloat16), None
        y, _ = lax.scan(one, a, None, length=mm_iters)
        return y

    jax.block_until_ready(matmul_chain(a))  # compile
    t0 = time.monotonic()
    jax.block_until_ready(matmul_chain(a))
    dt = time.monotonic() - t0
    out["matmul_bf16_tflops"] = round(2 * m ** 3 * mm_iters / dt / 1e12, 3)
    return out


def main(argv: Optional[list] = None) -> int:
    """CLI for the sidecar bench task (frameworks/jax collective plan).

    Multi-process mode rendezvous through jax.distributed using the
    gang env the evaluator injects (COORDINATOR_ADDRESS et al.); single
    chip falls back to rooflines.
    """
    import argparse
    import json
    import os

    parser = argparse.ArgumentParser(prog="collective-bench")
    parser.add_argument("--payload-mb", type=float, default=32.0)
    parser.add_argument("--iters", type=int, default=20)
    args = parser.parse_args(argv)

    if os.environ.get("COORDINATOR_ADDRESS"):
        from dcos_commons_tpu.parallel.distributed import initialize_from_env

        initialize_from_env()
    from dcos_commons_tpu.utils import claim_devices

    # the sidecar task of a tpu: pod must not measure the CPU instead
    claimed = claim_devices()
    devices = jax.devices()
    report: Dict[str, object] = {
        "devices": len(devices),
        "platform": claimed["platform"],
        "device_kind": claimed["device_kind"],
    }
    if len(devices) >= 2:
        mesh = Mesh(devices, ("ici",))
        report.update(
            collective_bandwidth(
                mesh, "ici", payload_mb=args.payload_mb, iters=args.iters
            )
        )
    report.update(single_chip_rooflines(iters=args.iters))
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
