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
the switch-transformer load-balancing auxiliary loss.  HOW a token
chooses is data (``MoEConfig``): a softmax or a sigmoid over the
router's logits, an ``expert_bias`` that moves the selection and not
the weights, the renormalisation over the chosen, a scaling factor.

Serving has ONE dispatch, ``moe_serve_ffn``, and it drops nothing:
ONE dispatch plan an expert layer (ops/grouped_matmul.py
``dispatch_plan``) says where each of the step's assignments stands
among the rows sorted by expert, each expert's rows form a group, and a
grouped matmul (the repo's own forward ``gmm`` kernel there, handed the
plan's tile metadata; ``lax.ragged_dot`` off a TPU and under a mesh)
runs the groups that hold rows, three times from the one plan.  The
plan is counted, not sorted: a handful of fused compare-and-sums where
two argsorts, a bincount and a kernel wrapper's own metadata in every
product stood (138 operations a layer beside the kernels at
``lfm2-24b.chat``'s decode shape, now 47: PERF.md section 6, PR 49).
Rows that stand for nothing (slots with no live request, a chunk's
padding) are given to no group, so the experts read follow the live
rows.  The capacity-bound dispatches above are the training forward's.
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
    # routing, as data: the score of an expert is a ``"softmax"`` over
    # all experts' logits or a ``"sigmoid"`` of its own; the ``top_k``
    # largest of score + ``expert_bias`` (a float32 ``[E]`` leaf, where
    # ``expert_bias`` is set) are chosen; their weights are their
    # SCORES (never the bias), renormalised over the chosen where
    # ``norm_topk``, times ``scaling``
    score: str = "softmax"
    expert_bias: bool = False
    norm_topk: bool = True
    scaling: float = 1.0
    # what the renormalisation adds to the chosen scores' sum; under 0:
    # the published forms (1e-6 on a sigmoid's, a softmax's held over
    # 1e-9)
    norm_eps: float = -1.0
    # shared experts: SwiGLUs of width ``d_ff`` (leaves ``shared_gate``
    # / ``shared_up`` / ``shared_down``) that EVERY token goes through,
    # added to the routed sum with weight 1, outside the routing
    n_shared: int = 0

    def __post_init__(self) -> None:
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"routing score {self.score!r} not in ('softmax', 'sigmoid')"
            )

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

    params = {
        # router stays f32: routing decisions are precision-sensitive
        "router": jax.random.normal(keys[0], (d, e), jnp.float32) * d ** -0.5,
        "w_gate": normal(keys[1], (e, d, f), d ** -0.5),
        "w_up": normal(keys[2], (e, d, f), d ** -0.5),
        "w_down": normal(keys[3], (e, f, d), f ** -0.5),
    }
    if config.expert_bias:
        params["expert_bias"] = jax.random.normal(
            jax.random.fold_in(key, 4), (e,), jnp.float32
        ) * 0.01
    if config.n_shared:
        fs = f * config.n_shared
        shared = jax.random.split(jax.random.fold_in(key, 5), 3)
        params["shared_gate"] = normal(shared[0], (d, fs), d ** -0.5)
        params["shared_up"] = normal(shared[1], (d, fs), d ** -0.5)
        params["shared_down"] = normal(shared[2], (fs, d), fs ** -0.5)
    return params


def route(
    config: MoEConfig, params: MoEParams, x: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Each token's choice: (weights ``[t, k]`` float32, experts
    ``[t, k]``, every expert's score ``[t, E]``), as ``MoEConfig``
    says a token chooses."""
    k = config.top_k
    logits = x.astype(jnp.float32) @ params["router"]
    if config.score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    if config.expert_bias:
        _, expert_idx = lax.top_k(scores + params["expert_bias"], k)
        gate_vals = jnp.take_along_axis(scores, expert_idx, axis=-1)
    else:
        gate_vals, expert_idx = lax.top_k(scores, k)
    if config.norm_topk:
        total = gate_vals.sum(-1, keepdims=True)
        # the published forms: a sigmoid's sum gets 1e-6 added, a
        # softmax's is held over 1e-9
        if config.norm_eps >= 0:
            gate_vals = gate_vals / (total + config.norm_eps)
        else:
            gate_vals = gate_vals / (
                jnp.maximum(total, 1e-9) if config.score == "softmax"
                else total + 1e-6
            )
    if config.scaling != 1.0:
        gate_vals = gate_vals * config.scaling
    return gate_vals, expert_idx, scores


def _routing(
    config: MoEConfig, params: MoEParams, x: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token->expert-slot assignment as dense one-hot tensors.

    Returns (dispatch [t,E,C], combine [t,E,C], aux_loss scalar).
    """
    t = x.shape[0]
    e, k = config.n_experts, config.top_k
    gate_vals, expert_idx, probs = route(config, params, x)    # [t, k]
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
    gate_vals, expert_idx, probs = route(config, params, x)    # [t, k]
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


def moe_serve_ffn(
    config: MoEConfig,
    routing: MoEParams,
    experts: MoEParams,
    layer,
    x: jax.Array,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The serving mixture on ``x [t, d]``: drop-free, and its cost
    follows the rows that are ``live [t]`` (all, where None).

    ``routing`` holds THIS layer's ``router`` (and ``expert_bias``);
    ``experts`` holds EVERY expert layer's ``w_gate`` / ``w_up`` /
    ``w_down`` stacked ``[n, E, ...]`` and ``layer`` says which of the
    ``n`` this is (ops/grouped_matmul.py reads the layer's experts in
    place).  ONE dispatch plan a layer (ops/grouped_matmul.py
    ``dispatch_plan``) says where each of the ``t * k`` assignments
    stands among the rows sorted by expert, a dead row's behind every
    group, and holds the kernel's tile metadata; the three grouped
    products run over the sorted rows from that one plan; the results
    go back to their tokens by the plan's ``back`` (a gather, no
    scatter), are weighted in float32 and summed.  A dead row's
    assignments are owned by no group, so no product writes their rows:
    they are left out here, once, with the row's weights.  A shared
    expert (``routing`` then holds its three leaves) is a dense SwiGLU
    over every row beside them, under the scope ``shared_expert``: its
    3 x d x d_ff weights are read once by a plain product, and the
    plan, the groups and the counts stay the ROUTED experts' alone.
    Returns (``y [t, d]``, int32 ``[2]``: the live assignments and the
    expert groups that hold at least one)."""
    from dcos_commons_tpu.ops.grouped_matmul import (
        dispatch_plan,
        grouped_matmul,
        take_rows,
    )

    t, d = x.shape
    e, k = config.n_experts, config.top_k
    dt = config.dtype
    with jax.named_scope("moe_router"):
        gate_vals, expert_idx, _scores = route(config, routing, x)
        plan = dispatch_plan(expert_idx, live, e)
    with jax.named_scope("moe_experts"):
        layer = jnp.asarray(layer, jnp.int32)

        def product(rows, name):
            w = experts[name]
            if isinstance(w, dict):
                # weight-only int8 (models/quantize.py): the layer's
                # experts are widened, never the whole stack
                w = dq(jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                    a, layer, axis=0, keepdims=False
                ), w), dt)
                return grouped_matmul(rows, w, plan, 0)
            return grouped_matmul(
                rows, w.reshape((-1,) + w.shape[-2:]), plan, layer * e
            )

        rows = take_rows(x.astype(dt), plan.src)               # [m, d]
        gate = jax.nn.silu(product(rows, "w_gate"))
        out = product(gate * product(rows, "w_up"), "w_down")
        weighted = (
            take_rows(out, plan.back).reshape(t, k, d).astype(jnp.float32)
            * gate_vals[:, :, None]
        )
        if live is not None:
            # what a dead row's assignments point at was never written
            weighted = jnp.where(live[:, None, None], weighted, 0.0)
        y = jnp.sum(weighted, axis=1)
    if config.n_shared:
        with jax.named_scope("shared_expert"):
            h = x.astype(dt)
            hidden = jax.nn.silu(h @ dq(routing["shared_gate"], dt)) * (
                h @ dq(routing["shared_up"], dt)
            )
            y = y + (hidden @ dq(routing["shared_down"], dt)).astype(
                jnp.float32
            )
    return y.astype(x.dtype), plan.counts


def expert_shard_spec():
    """PartitionSpec rules for the param tree under ep sharding."""
    from jax.sharding import PartitionSpec as P

    return {
        "router": P(None, None),
        "w_gate": P("ep", None, None),
        "w_up": P("ep", None, None),
        "w_down": P("ep", None, None),
    }


def moe_sharding_rules(prefix: str = "", stacked: bool = False,
                       expert_bias: bool = False, shared: bool = False):
    """Param path -> PartitionSpec for the jit/GSPMD path: experts over
    ``ep``, then the scaling-book fsdp/tp split within each expert.

    This is the layout transformer.sharding_rules consumes for the MoE
    flagship (``stacked=True`` prepends the lax.scan layer axis);
    keeping it beside the dispatch code means a dispatch-layout change
    and its sharding change land in the same file.  The router (and
    the selection bias, where there is one) stays fully replicated —
    routing logits are f32 and tiny, and every chip needs them before
    dispatch.
    """
    from jax.sharding import PartitionSpec as P

    lead = (None,) if stacked else ()
    rules = {
        f"{prefix}router": P(*lead, None, None),
        f"{prefix}w_gate": P(*lead, "ep", "fsdp", "tp"),
        f"{prefix}w_up": P(*lead, "ep", "fsdp", "tp"),
        f"{prefix}w_down": P(*lead, "ep", "tp", "fsdp"),
    }
    if expert_bias:
        rules[f"{prefix}expert_bias"] = P(*lead, None)
    if shared:
        rules[f"{prefix}shared_gate"] = P(*lead, "fsdp", "tp")
        rules[f"{prefix}shared_up"] = P(*lead, "fsdp", "tp")
        rules[f"{prefix}shared_down"] = P(*lead, "tp", "fsdp")
    return rules
