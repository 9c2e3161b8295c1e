"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` axis.

TPU-first design, not a port: routing and dispatch are expressed as
one-hot einsums (dense matmuls the MXU eats) with a STATIC per-expert
capacity — no gather/scatter, no dynamic shapes, nothing XLA can't
tile.  Expert parallelism is two ``lax.all_to_all``s around the expert
FFN: dispatch local tokens to the ranks owning their experts, compute,
and send results back — the standard TPU MoE recipe (tokens ride ICI
both ways while the expert matmuls run).

Shapes (per device, inside shard_map over ``ep``):
    x            [tokens, d_model]      tokens sharded over ep
    dispatch     [tokens, E, C]         one-hot token->slot
    expert_in    [E, C, d]  --all_to_all-->  [E/ep, ep*C, d]
    expert_out   [E/ep, ep*C, d] --all_to_all--> [E, C, d]

Top-k routing with probability renormalisation over the chosen k, and
the switch-transformer load-balancing auxiliary loss.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dcos_commons_tpu.parallel.compat import axis_size

from dcos_commons_tpu.models.quantize import dequantize_weight as dq


@dataclass(frozen=True)
class MoEConfig:
    d_model: int = 512
    d_ff: int = 1024            # per-expert SwiGLU hidden
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.5
    dtype: Any = jnp.bfloat16

    def capacity(self, n_tokens: int) -> int:
        """Static per-expert slot count for an n_tokens batch."""
        cap = int(self.capacity_factor * self.top_k * n_tokens / self.n_experts)
        return max(cap, 1)


MoEParams = Dict[str, jax.Array]


def init_moe_params(config: MoEConfig, key: jax.Array) -> MoEParams:
    keys = jax.random.split(key, 4)
    d, f, e = config.d_model, config.d_ff, config.n_experts
    dt = config.dtype

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    return {
        # router stays f32: routing decisions are precision-sensitive
        "router": jax.random.normal(keys[0], (d, e), jnp.float32) * d ** -0.5,
        "w_gate": normal(keys[1], (e, d, f), d ** -0.5),
        "w_up": normal(keys[2], (e, d, f), d ** -0.5),
        "w_down": normal(keys[3], (e, f, d), f ** -0.5),
    }


def _routing(
    config: MoEConfig, params: MoEParams, x: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token->expert-slot assignment as dense one-hot tensors.

    Returns (dispatch [t,E,C], combine [t,E,C], aux_loss scalar).
    """
    t = x.shape[0]
    e, k = config.n_experts, config.top_k
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)                    # [t, E]
    gate_vals, expert_idx = lax.top_k(probs, k)                # [t, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )
    # switch load-balance loss: fraction-of-tokens * mean-prob per expert
    top1_hot = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(top1_hot.mean(0) * probs.mean(0))

    # slot assignment: k choices claim capacity in priority order, so
    # a token's 2nd choice never evicts another token's 1st choice
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    used = jnp.zeros((e,), jnp.float32)                        # slots taken
    for slot_k in range(k):
        hot = jax.nn.one_hot(expert_idx[:, slot_k], e, dtype=jnp.float32)  # [t,E]
        pos = jnp.cumsum(hot, axis=0) - 1.0 + used[None, :]    # [t,E]
        keep = hot * (pos < capacity)
        slot_hot = keep[:, :, None] * jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1).astype(jnp.int32),
            capacity, dtype=jnp.float32,
        )                                                       # [t,E,C]
        dispatch = dispatch + slot_hot
        combine = combine + slot_hot * gate_vals[:, slot_k][:, None, None]
        used = used + keep.sum(axis=0)
    return dispatch, combine, aux


def _routing_sorted(
    config: MoEConfig, params: MoEParams, x: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort-based token->slot assignment: no [t,E,C] one-hot tensors.

    Returns (slot [t*k], token [t*k], weight [t*k], keep [t*k], aux).
    The (t*k) routing entries are sorted by expert CHOICE-MAJOR (every
    token's 1st choice outranks any 2nd choice), positions within an
    expert come from the sorted order, and entries past the capacity
    are dropped — identical drop semantics to the one-hot path.  The
    per-entry work is O(t*k log(t*k)) sort + O(t*k) bookkeeping vs the
    one-hot path's O(t*E*C) tensor construction; dispatch becomes a
    row gather/scatter instead of a [t,E*C] matmul."""
    t = x.shape[0]
    e, k = config.n_experts, config.top_k
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)                    # [t, E]
    gate_vals, expert_idx = lax.top_k(probs, k)                # [t, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )
    top1_hot = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(top1_hot.mean(0) * probs.mean(0))
    # choice-major flatten: stable argsort then gives 1st choices
    # priority over 2nd choices for the last slots of a hot expert
    flat_expert = expert_idx.T.reshape(-1)                     # [k*t]
    flat_token = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gate_vals.T.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se = flat_expert[order]
    st = flat_token[order]
    sg = flat_gate[order]
    counts = jnp.bincount(flat_expert, length=e)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    pos = jnp.arange(k * t) - offsets[se]
    keep = pos < capacity
    slot = se * capacity + jnp.clip(pos, 0, capacity - 1)
    return slot, st, sg, keep, aux


def _moe_sorted(
    config: MoEConfig,
    params: MoEParams,
    x: jax.Array,
    capacity: int,
    axis_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """moe_ffn body over sorted dispatch (see _routing_sorted)."""
    t, d = x.shape
    e = config.n_experts
    if axis_name is not None:
        ep = axis_size(axis_name)
        if (e // ep) * ep != e:
            # fail like the one-hot path does — not with an opaque
            # all_to_all split-axis shape error
            raise ValueError(
                f"n_experts {e} not divisible by ep={ep}"
            )
    slot, st, sg, keep, aux = _routing_sorted(config, params, x, capacity)
    rows = x[st].astype(config.dtype) * keep[:, None].astype(config.dtype)
    # dropped entries are zeroed BEFORE the scatter-add, so the
    # clipped slot they alias contributes nothing
    expert_in = jnp.zeros(
        (e * capacity, d), config.dtype
    ).at[slot].add(rows).reshape(e, capacity, d)
    if axis_name is None:
        expert_out = _expert_ffn(config, params, expert_in)
    else:
        aux = lax.pmean(aux, axis_name)
        # same wire pattern as the one-hot path: ship slots to the
        # expert owners, compute, ship back (tokens ride ICI while
        # the expert matmuls run)
        expert_in = lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True
        )
        expert_out = _expert_ffn(config, params, expert_in)
        expert_out = lax.all_to_all(
            expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True
        )
    out_rows = expert_out.reshape(e * capacity, d)[slot]
    weight = (sg * keep).astype(jnp.float32)[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[st].add(
        out_rows.astype(jnp.float32) * weight
    )
    return y.astype(x.dtype), aux


def _expert_ffn(config: MoEConfig, params: MoEParams, h: jax.Array) -> jax.Array:
    """h [E_local, slots, d] -> [E_local, slots, d]: batched SwiGLU.

    Expert weights may be weight-only int8 (models/quantize.py): the
    [e, d, f] layout contracts axis -2 exactly like the dense path, so
    the same per-output-channel dequant fuses into each einsum."""
    h = h.astype(config.dtype)
    gate = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", h, dq(params["w_gate"], config.dtype))
    )
    up = jnp.einsum("ecd,edf->ecf", h, dq(params["w_up"], config.dtype))
    return jnp.einsum(
        "ecf,efd->ecd", gate * up, dq(params["w_down"], config.dtype)
    )


def moe_ffn(
    config: MoEConfig,
    params: MoEParams,
    x: jax.Array,
    axis_name: Optional[str] = None,
    capacity: Optional[int] = None,
    impl: str = "onehot",
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN on x [tokens, d_model] -> (y, aux_loss).

    Without ``axis_name``: all experts local (single device).  With
    ``axis_name`` (inside shard_map): tokens are sharded over ep and
    each rank owns n_experts / ep_size experts — params' expert axis
    must be sharded over ep accordingly.

    ``capacity`` overrides the factor-derived per-expert slot count;
    decode passes capacity = tokens so NO token is ever dropped (slot
    competition is a training-time load-balancing pressure, not a
    serving behavior).

    ``impl`` picks the dispatch: "onehot" (dense [t,E,C] one-hot
    einsums — every op a matmul) or "sorted" (argsort + row
    gather/scatter — no O(t*E*C) tensors, preferred for large token
    groups).  Drop semantics are identical (choice-major priority);
    tests hold numeric agreement in the drop-free regime.
    """
    t, d = x.shape
    capacity = capacity if capacity is not None else config.capacity(t)
    if impl == "sorted":
        return _moe_sorted(config, params, x, capacity, axis_name)
    if axis_name is None:
        # the two scopes name the parts in a device profile (the
        # serving programs' moe_router / moe_experts)
        with jax.named_scope("moe_router"):
            dispatch, combine, aux = _routing(config, params, x, capacity)
        # dispatch/combine matmuls run in the COMPUTE dtype: the
        # one-hot dispatch is exactly representable in bf16 and the
        # expert FFN consumes bf16 anyway.  Measured MFU-neutral on
        # v5e (XLA already folds the f32 convert into the matmul) —
        # kept for dtype consistency with the expert FFN, NOT as a
        # perf lever (r5 sweep notes in bench.py bench_moe).
        dt = config.dtype
        with jax.named_scope("moe_experts"):
            expert_in = jnp.einsum(
                "tec,td->ecd", dispatch.astype(dt), x.astype(dt)
            )
            expert_out = _expert_ffn(config, params, expert_in)
            y = jnp.einsum(
                "tec,ecd->td", combine.astype(dt), expert_out.astype(dt)
            )
        return y.astype(x.dtype), aux

    ep = axis_size(axis_name)
    e_local = config.n_experts // ep
    if e_local * ep != config.n_experts:
        raise ValueError(
            f"n_experts {config.n_experts} not divisible by ep={ep}"
        )
    # every rank routes its LOCAL tokens against the global router
    # (router weights replicated), then ships slots to expert owners
    dispatch, combine, aux = _routing(config, params, x, capacity)
    aux = lax.pmean(aux, axis_name)
    # same compute dtype as the single-device branch: the two paths
    # must not silently differ in precision
    dt = config.dtype
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt), x.astype(dt))
    # [E, C, d] -> [E/ep, ep*C, d]: each rank receives every other
    # rank's slots for the experts it owns
    expert_in = lax.all_to_all(
        expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True
    )
    expert_out = _expert_ffn(config, params, expert_in)
    # reverse trip: [E/ep, ep*C, d] -> [E, C, d] back at the senders
    expert_out = lax.all_to_all(
        expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True
    )
    y = jnp.einsum(
        "tec,ecd->td", combine.astype(dt), expert_out.astype(dt)
    )
    return y.astype(x.dtype), aux


def expert_shard_spec():
    """PartitionSpec rules for the param tree under ep sharding."""
    from jax.sharding import PartitionSpec as P

    return {
        "router": P(None, None),
        "w_gate": P("ep", None, None),
        "w_up": P("ep", None, None),
        "w_down": P("ep", None, None),
    }


def moe_sharding_rules(prefix: str = "", stacked: bool = False):
    """Param path -> PartitionSpec for the jit/GSPMD path: experts over
    ``ep``, then the scaling-book fsdp/tp split within each expert.

    This is the layout transformer.sharding_rules consumes for the MoE
    flagship (``stacked=True`` prepends the lax.scan layer axis);
    keeping it beside the dispatch code means a dispatch-layout change
    and its sharding change land in the same file.  The router stays
    fully replicated — routing logits are f32 and tiny, and every
    chip needs them before dispatch.
    """
    from jax.sharding import PartitionSpec as P

    lead = (None,) if stacked else ()
    return {
        f"{prefix}router": P(*lead, None, None),
        f"{prefix}w_gate": P(*lead, "ep", "fsdp", "tp"),
        f"{prefix}w_up": P(*lead, "ep", "fsdp", "tp"),
        f"{prefix}w_down": P(*lead, "ep", "tp", "fsdp"),
    }
