"""Flagship decoder-only transformer LM, designed TPU-first.

Design choices (not a port of anything):
- pure-JAX pytree params; layers STACKED and iterated with lax.scan so
  XLA compiles one layer once regardless of depth (compile-time and
  code-size win over unrolled Python loops)
- bf16 activations + params with f32 RMSNorm statistics, f32 logits
  for the loss: the MXU-native mixed precision recipe
- RoPE positions, grouped-query attention, SwiGLU MLP
- attention via ops.flash_attention (pallas) on one device, or
  parallel.ring.ring_attention when the sequence is sharded on "sp"
- sharding rules map every param to a PartitionSpec over
  (dp, fsdp, tp, sp) for pjit; batch shards over (dp, fsdp), heads
  and ffn over tp, params over fsdp
- optional jax.checkpoint (remat) per layer: recompute activations in
  backward to trade FLOPs for HBM
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dcos_commons_tpu.parallel.compat import axis_size

from dcos_commons_tpu.models.quantize import dequantize_weight as dq
from dcos_commons_tpu.ops.attention import flash_attention
from dcos_commons_tpu.ops.rmsnorm import rms_norm
from dcos_commons_tpu.parallel.pipeline import (
    last_stage_value,
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
)


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32768
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8          # < n_heads => GQA
    d_ff: int = 1408             # SwiGLU hidden
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # remat granularity: "full" recomputes the whole layer in backward;
    # "save-attn" additionally SAVES each layer's attention output
    # (b*s*d bf16 per layer) so the flash kernel never re-runs, at the
    # price of extra residual traffic.  "full" is the default; which
    # wins is not measured on the current toolchain (ROADMAP D4).
    remat_policy: str = "full"
    # mixed remat: the last k layers store activations instead of
    # recomputing (see _layer_scan) — each costs ~2.2 GB HBM at the
    # flagship shape and buys back 1/n_layers of the recompute pass
    no_remat_layers: int = 0

    def __post_init__(self) -> None:
        if self.remat_policy not in ("full", "save-attn"):
            raise ValueError(
                f"remat_policy {self.remat_policy!r} not in "
                "('full', 'save-attn')"
            )
        if self.use_ring_attention and self.use_ulysses_attention:
            raise ValueError(
                "pick ONE sequence-parallel recipe: ring or ulysses"
            )
        if self.attention not in ("gqa", "eva"):
            raise ValueError(
                f"attention {self.attention!r} not in ('gqa', 'eva')"
            )
        if self.attention == "eva" and (
            self.chunk_size < 1 or self.window_size < self.chunk_size
            or self.window_size % self.chunk_size
        ):
            raise ValueError(
                "eva attention needs window_size a positive multiple of "
                f"chunk_size, got {self.window_size} / {self.chunk_size}"
            )
        if self.attention == "eva" and self.n_experts > 0:
            raise ValueError("eva attention serves a dense FFN only")
        if self.attention == "eva" and (
            self.attention_gate or self.sandwich_norm or self.embed_scale
        ):
            raise ValueError(
                "eva attention has no output gate, second norms or "
                "embedding scale: the grouped-query walk builds those"
            )
        unknown = set(self.layer_types) - {"attention", "conv", "sliding"}
        if unknown or (
            self.layer_types and len(self.layer_types) != self.n_layers
        ):
            raise ValueError(
                f"layer_types names {self.n_layers} operators, each "
                f"'attention', 'sliding' or 'conv', got {self.layer_types!r}"
            )
        if "sliding" in self.layer_types and (
            self.attention != "gqa" or self.sliding_window < 1
            or "attention" not in self.layer_types
            or "conv" in self.layer_types
        ):
            raise ValueError(
                "sliding (window) attention layers go with at least one "
                "full grouped-query attention layer, a sliding_window >= 1 "
                f"and no conv layer, got attention {self.attention!r}, "
                f"sliding_window {self.sliding_window}, {self.layer_types!r}"
            )
        if self.n_shared_experts not in (0, 1):
            raise ValueError(
                f"n_shared_experts {self.n_shared_experts}: one shared "
                "expert of the routed experts' width is built, no more"
            )
        if "conv" in self.layer_types and (
            self.attention != "gqa" or self.conv_l_cache < 2
        ):
            raise ValueError(
                "conv layers go with grouped-query attention layers and "
                f"a kernel of >= 2 taps, got attention {self.attention!r}, "
                f"conv_l_cache {self.conv_l_cache}"
            )
        if self.layer_types and "attention" not in self.layer_types:
            raise ValueError(
                "a pattern of conv layers alone is not built: the "
                "serving programs walk a paged arena of attention layers"
            )
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers {self.n_dense_layers} of {self.n_layers}"
            )
    use_ring_attention: bool = False     # sp: K/V rotate (ppermute)
    use_ulysses_attention: bool = False  # sp: all_to_all head regroup
    sp_axis: str = "sp"
    # MoE flagship variant: n_experts > 0 swaps every layer's dense
    # SwiGLU for a mixture of experts (router + per-expert SwiGLU,
    # models/moe.py) with the switch load-balancing aux loss.  Under
    # jit the expert axis shards over the mesh's ep axis (sharding
    # rules below) and GSPMD inserts the dispatch collectives.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.5
    moe_aux_weight: float = 0.01
    # routing group size for the jit path: tokens route in groups of
    # up to this many, bounding the one-hot dispatch tensors at
    # group * E * C (C scales with the GROUP, not the global batch —
    # an ungrouped b*s routing would be O(tokens^2) memory)
    moe_group_size: int = 1024
    # dispatch implementation: "onehot" (dense [t,E,C] einsums) or
    # "sorted" (argsort + row gather/scatter, no O(t*E*C) tensors —
    # the pick for large groups); see models/moe.py moe_ffn
    moe_impl: str = "onehot"
    # sequence-chunked cross entropy: the [b, s, vocab] f32 logits are
    # never materialized — each chunk's logits are computed, reduced to
    # a scalar, and rematerialized in backward.  0 = unchunked.
    loss_chunk: int = 0
    # flash-attention tile sizes (VMEM-tunable per chip generation)
    attn_block_q: int = 128
    attn_block_k: int = 128
    # what only a configuration FILE can say (``config_from_env``,
    # MODEL_CONFIG): the norm's epsilon, a norm that scales by
    # ``1 + weight`` (the stored weight is the offset from one), an
    # output head of its own (``[d_model, n_pred_heads * vocab]``;
    # columns ``[0, vocab)`` are the next-token head, the others are
    # self-speculation heads that plain decoding does not read), and
    # the attention class of the serving path
    rms_norm_eps: float = 1e-6
    norm_unit_offset: bool = False
    tie_embeddings: bool = True
    n_pred_heads: int = 1
    # "gqa": every token of a row is kept and attended for ever.
    # "eva" (models/decode.py): an exact window of ``window_size``
    # positions beside one summary per ``chunk_size`` positions of every
    # window that is past; ``eva_init_std`` scales its two learned
    # vectors a head (``eva_phi``, ``eva_mu``) at initialisation
    attention: str = "gqa"
    window_size: int = 0
    chunk_size: int = 0
    eva_init_std: float = 0.02
    # the layer pattern, as data.  ``layer_types`` names each layer's
    # OPERATOR: "attention" (the class above) or "conv", a gated short
    # convolution of ``conv_l_cache`` taps whose whole memory is a
    # row's last ``conv_l_cache - 1`` gated inputs; () is attention
    # everywhere.  A layer's FFN is the mixture where there is one
    # (``n_experts`` > 0), except in the ``n_dense_layers`` leading
    # layers, which keep the dense block of width ``d_ff``; the experts
    # have width ``moe_d_ff`` (0: ``d_ff``).  ``layer_kinds`` is the
    # pattern; models/decode.py ``layer_plan`` walks it.
    layer_types: tuple = ()
    n_dense_layers: int = 0
    conv_l_cache: int = 3
    moe_d_ff: int = 0
    # how a token chooses its experts (models/moe.py MoEConfig)
    moe_score: str = "softmax"
    moe_expert_bias: bool = False
    moe_norm_topk: bool = True
    moe_scaling: float = 1.0
    moe_norm_eps: float = -1.0
    n_shared_experts: int = 0
    # queries and keys RMS-normed a head, over head_dim, before RoPE
    qk_norm: bool = False
    # a head's width where the file states one (0: d_model / n_heads);
    # the query and output projections are then n_heads * d_head wide,
    # whatever d_model is
    d_head: int = 0
    # "sliding" layers of ``layer_types`` attend to the last
    # ``sliding_window`` positions alone (query i sees keys j with
    # i - sliding_window < j <= i) and keep no more of a row than a
    # ring of them (serve/paging.py RowLayout); "attention" layers the
    # whole history.  ``nope_full_attention``: the full layers of such
    # a pattern rotate nothing (no position encoding), the sliding ones
    # keep RoPE
    sliding_window: int = 0
    nope_full_attention: bool = False
    # the attention output times sigmoid(x Wg), a head and a lane at a
    # time, before the output projection (leaf ``wg``)
    attention_gate: bool = False
    # a norm after each block as well as before it: x += norm_post(
    # block(norm_pre(x))) (leaves ``attn_post_norm``, ``mlp_post_norm``)
    sandwich_norm: bool = False
    # the embedding's rows times sqrt(d_model)
    embed_scale: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def serving_only(self) -> str:
        """What of this configuration the TRAINING forward does not
        build ("" where it builds all of it)."""
        found = [
            name for name in (
                "qk_norm", "attention_gate", "sandwich_norm", "embed_scale",
                "nope_full_attention", "n_shared_experts",
            ) if getattr(self, name)
        ]
        return ", ".join(found)

    @property
    def layer_kinds(self) -> tuple:
        """(operator, ffn) of every layer: "attention" | "sliding" |
        "conv" and "dense" | "moe"."""
        operators = self.layer_types or ("attention",) * self.n_layers
        return tuple(
            (op, "moe" if self.n_experts > 0 and i >= self.n_dense_layers
             else "dense")
            for i, op in enumerate(operators)
        )

    @property
    def one_kind(self) -> bool:
        """Every layer the same operator and the same FFN: the
        parameter tree is then ONE stack, ``params["layers"]``."""
        return len(set(self.layer_kinds)) <= 1

    def n_layers_of(self, part: str) -> int:
        """Layers that have ``part``: an operator or an FFN kind."""
        return sum(part in kind for kind in self.layer_kinds)


Params = Dict[str, Any]


# a configuration file's key (the names of a published ``config.json``)
# -> the TransformerConfig field it sets
_FILE_KEYS = {
    "vocab_size": "vocab", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "num_local_experts": "n_experts", "num_experts_per_tok": "moe_top_k",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "norm_add_unit_offset": "norm_unit_offset",
    "tie_word_embeddings": "tie_embeddings",
    "num_pred_heads": "n_pred_heads", "window_size": "window_size",
    "chunk_size": "chunk_size", "init_std": "eva_init_std",
    # a second published name for the same field wins where both stand
    "num_experts": "n_experts", "norm_eps": "rms_norm_eps",
    "moe_intermediate_size": "moe_d_ff",
    "num_dense_layers": "n_dense_layers", "conv_L_cache": "conv_l_cache",
    "norm_topk_prob": "moe_norm_topk", "use_expert_bias": "moe_expert_bias",
    "routed_scaling_factor": "moe_scaling",
    "router_activation": "moe_score", "qk_norm": "qk_norm",
    "head_dim": "d_head", "sliding_window": "sliding_window",
    "num_shared_experts": "n_shared_experts", "score_func": "moe_score",
    "route_norm": "moe_norm_topk", "route_scale": "moe_scaling",
    "route_norm_eps": "moe_norm_eps", "mup_enabled": "embed_scale",
    # what a published config.json has no key for and a file states
    # under the program's own names (its ``assumed`` says from where)
    "attention_gate": "attention_gate", "sandwich_norm": "sandwich_norm",
    "nope_on_full_attention": "nope_full_attention",
}
# a published ``layer_types`` entry -> the operator it names
_OPERATORS = {
    "full_attention": "attention", "sliding_attention": "sliding",
    "conv": "conv",
}
# published keys that ask for what the program does not build, each
# with the most it may say
_UNBUILT = {
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "num_shared_experts": 1,
}


def config_fields_from_file(path: str) -> Dict[str, Any]:
    """The TransformerConfig fields that the configuration file at
    ``path`` states: a JSON object under the key names of a published
    ``config.json`` (``_FILE_KEYS``; ``attention_class`` names the
    attention, ``layer_types`` each layer's operator,
    ``rope_parameters.rope_theta`` is read where it is nested).  A key this table lacks is not the program's to
    interpret and is passed over; a ``head_dim`` is taken as stated
    (the heads need not fill ``hidden_size``); what the program cannot
    build (an attention class, a ``rope_scaling``, a routing limited
    to groups of experts, a second shared expert) is an error by its
    name, not a silent other model."""
    import json

    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    fields = {
        field: type(TransformerConfig.__dataclass_fields__[field].default)(
            data[key]
        )
        for key, field in _FILE_KEYS.items() if data.get(key) is not None
    }
    for key, most in _UNBUILT.items():
        if data.get(key) is not None and int(data[key]) > most:
            raise ValueError(
                f"{path}: {key} {data[key]} is not built (at most {most}): "
                "every expert stands in one group and one shared expert "
                "of the routed width beside them"
            )
    if data.get("rope_scaling") is not None:
        raise ValueError(
            f"{path}: rope_scaling {data['rope_scaling']!r} is not built; "
            "the program rotates by rope_theta alone"
        )
    rope = data.get("rope_parameters") or {}
    if rope.get("rope_theta") is not None:
        if rope.get("rope_type", "default") != "default":
            raise ValueError(
                f"{path}: rope_type {rope['rope_type']!r} is not built; "
                "the program rotates by rope_theta alone"
            )
        fields["rope_theta"] = float(rope["rope_theta"])
    if data.get("layer_types") is not None:
        unknown = set(data["layer_types"]) - set(_OPERATORS)
        if unknown:
            raise ValueError(
                f"{path}: layer_types names {sorted(unknown)}; the "
                f"program builds {sorted(_OPERATORS)}"
            )
        fields["layer_types"] = tuple(
            _OPERATORS[name] for name in data["layer_types"]
        )
    # a name TransformerConfig does not know is refused there
    fields["attention"] = data.get("attention_class") or "gqa"
    if fields["attention"] != "eva":
        fields.pop("window_size", None)
        fields.pop("chunk_size", None)
    if "sliding" not in fields.get("layer_types", ()):
        # a window that no layer of the pattern keeps
        fields.pop("sliding_window", None)
    return fields


def config_from_env(env: Dict[str, str], **overrides) -> TransformerConfig:
    """The scheduler-env -> TransformerConfig contract, in ONE place.

    Every worker script (frameworks/jax/{train_worker,serve_worker,
    serve_gang_worker}.py) AND the static sharding analyzer
    (analysis/shardcheck.py) build their config through this function:
    if the mapping drifted between a worker and the analyzer, the
    analyzer would vouch for a model the pod never runs.  ``overrides``
    are keyword fields applied on top (dtype, remat, ...).

    ``MODEL_CONFIG`` names a configuration FILE
    (``config_fields_from_file``): what it states wins over the eight
    size names below, which a service YAML always sends with their
    defaults, and it alone can state what they cannot (rope_theta, the
    norm's epsilon and offset, an untied head, the attention class).
    """
    fields = dict(
        vocab=int(env.get("VOCAB", "8192")),
        d_model=int(env.get("D_MODEL", "512")),
        n_layers=int(env.get("N_LAYERS", "4")),
        n_heads=int(env.get("N_HEADS", "8")),
        n_kv_heads=int(env.get("N_KV_HEADS", "8")),
        d_ff=int(env.get("D_FF", "1408")),
        max_seq=int(env.get("SEQ_LEN", "1024")),
        # MoE flagship: N_EXPERTS > 0 swaps dense SwiGLU for the
        # ep-sharded mixture (models/moe.py)
        n_experts=int(env.get("N_EXPERTS", "0")),
    )
    model_config = env.get("MODEL_CONFIG", "")
    if model_config:
        fields.update(config_fields_from_file(model_config))
    fields.update(overrides)
    return TransformerConfig(**fields)


def moe_config_of(config: TransformerConfig):
    """The mixture of ``config`` as models/moe.py states one."""
    from dcos_commons_tpu.models.moe import MoEConfig

    return MoEConfig(
        d_model=config.d_model, d_ff=config.moe_d_ff or config.d_ff,
        n_experts=config.n_experts, top_k=config.moe_top_k,
        capacity_factor=config.moe_capacity_factor, dtype=config.dtype,
        score=config.moe_score, expert_bias=config.moe_expert_bias,
        norm_topk=config.moe_norm_topk, scaling=config.moe_scaling,
        norm_eps=config.moe_norm_eps, n_shared=config.n_shared_experts,
    )


def init_params(config: TransformerConfig, key: jax.Array) -> Params:
    """One stack a KIND of layer part, each leaf with a leading axis
    over the layers that have that part: ``attention`` and ``conv``
    operators, ``dense`` and ``moe`` FFNs.  ``config.layer_kinds`` says
    which part a layer has; a layer reads index (the layers before it
    that have the same part) of the part's stack.  Where every layer is
    of one kind the two stacks it has are ONE, ``params["layers"]``,
    consumed by lax.scan; a mixed pattern keeps them apart,
    ``params["layers"][part]``."""
    keys = jax.random.split(key, 8)
    d, h, kv, hd, f = (
        config.d_model,
        config.n_heads,
        config.n_kv_heads,
        config.head_dim,
        config.d_ff,
    )
    dt = config.dtype

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    # a unit-offset norm stores its weight's distance from one
    norm_init = jnp.zeros if config.norm_unit_offset else jnp.ones

    def attention_stack(n, keys=keys):
        stack = {
            "attn_norm": norm_init((n, d), dt),
            "wq": normal(keys[1], (n, d, h * hd), d ** -0.5),
            "wk": normal(keys[2], (n, d, kv * hd), d ** -0.5),
            "wv": normal(keys[3], (n, d, kv * hd), d ** -0.5),
            "wo": normal(keys[4], (n, h * hd, d), (h * hd) ** -0.5),
        }
        if config.qk_norm:
            stack["q_norm"] = jnp.ones((n, hd), dt)
            stack["k_norm"] = jnp.ones((n, hd), dt)
        if config.attention_gate:
            stack["wg"] = normal(
                jax.random.fold_in(keys[1], 1), (n, d, h * hd), d ** -0.5
            )
        if config.sandwich_norm:
            stack["attn_post_norm"] = norm_init((n, d), dt)
        if config.attention == "eva":
            # the two learned vectors a head of the chunk summaries
            # (models/decode.py ``_eva_summaries``)
            eva_keys = jax.random.split(jax.random.fold_in(key, 8), 2)
            stack["eva_phi"] = normal(
                eva_keys[0], (n, kv, hd), config.eva_init_std
            )
            stack["eva_mu"] = normal(
                eva_keys[1], (n, kv, hd), config.eva_init_std
            )
        return stack

    def conv_stack(n):
        # ``conv_in`` holds the three gates side by side ([B, C, X]);
        # ``conv_w [d, taps]`` is depthwise, its last tap the newest
        conv_keys = jax.random.split(jax.random.fold_in(key, 10), 3)
        taps = config.conv_l_cache
        return {
            "conv_norm": norm_init((n, d), dt),
            "conv_in": normal(conv_keys[0], (n, d, 3 * d), d ** -0.5),
            "conv_w": normal(conv_keys[1], (n, d, taps), taps ** -0.5),
            "conv_out": normal(conv_keys[2], (n, d, d), d ** -0.5),
        }

    def post_norm(n):
        return (
            {"mlp_post_norm": norm_init((n, d), dt)}
            if config.sandwich_norm else {}
        )

    def dense_stack(n):
        return {
            "mlp_norm": norm_init((n, d), dt),
            "w_gate": normal(keys[5], (n, d, f), d ** -0.5),
            "w_up": normal(keys[6], (n, d, f), d ** -0.5),
            "w_down": normal(keys[7], (n, f, d), f ** -0.5),
            **post_norm(n),
        }

    def moe_stack(n):
        # one source of truth for the expert init recipe (router-f32
        # policy, scales): moe.init_moe_params, vmapped over layers
        from dcos_commons_tpu.models.moe import init_moe_params

        moe_config = moe_config_of(config)
        return {"mlp_norm": norm_init((n, d), dt), **jax.vmap(
            lambda k: init_moe_params(moe_config, k)
        )(jax.random.split(keys[5], n)), **post_norm(n)}

    builders = {
        "attention": attention_stack, "conv": conv_stack,
        # the same leaves as a full layer's, drawn from keys of its own
        "sliding": functools.partial(
            attention_stack, keys=jax.random.split(
                jax.random.fold_in(key, 11), 8
            ),
        ),
        "dense": dense_stack, "moe": moe_stack,
    }
    stacks = {
        part: build(config.n_layers_of(part))
        for part, build in builders.items() if config.n_layers_of(part)
    }
    if config.one_kind:
        layers = {}
        for part in ("attention", "sliding", "conv", "moe", "dense"):
            layers.update(stacks.get(part, {}))
    else:
        layers = stacks
    params = {
        "embed": normal(keys[0], (config.vocab, d), d ** -0.5),
        "layers": layers,
        "final_norm": norm_init((d,), dt),
    }
    if not config.tie_embeddings:
        params["lm_head"] = normal(
            jax.random.fold_in(key, 9),
            (d, config.n_pred_heads * config.vocab), d ** -0.5,
        )
    return params


def sharding_rules(config: TransformerConfig) -> Dict[str, P]:
    """Param path -> PartitionSpec (scaling-book layout):
    heads/ffn over tp, the other big axis over fsdp; MoE expert axes
    over ep (GSPMD then inserts the dispatch collectives).  Paths
    follow ``init_params``: ``layers/<leaf>`` where every layer is of
    one kind, ``layers/<part>/<leaf>`` in a mixed pattern."""
    parts = {
        "attention": {
            "attn_norm": P(None, None),
            "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"),
            "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"),
        },
        "conv": {
            "conv_norm": P(None, None),
            "conv_in": P(None, "fsdp", "tp"),
            "conv_w": P(None, None, None),
            "conv_out": P(None, "tp", "fsdp"),
        },
        "dense": {
            "mlp_norm": P(None, None),
            "w_gate": P(None, "fsdp", "tp"),
            "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),
        },
    }
    if config.qk_norm:
        parts["attention"]["q_norm"] = P(None, None)
        parts["attention"]["k_norm"] = P(None, None)
    if config.attention_gate:
        parts["attention"]["wg"] = P(None, "fsdp", "tp")
    if config.attention == "eva":
        parts["attention"]["eva_phi"] = P(None, "tp", None)
        parts["attention"]["eva_mu"] = P(None, "tp", None)
    if config.n_experts > 0:
        # the expert-axis rules live next to the MoE model so the
        # dispatch layout and its sharding can't drift apart
        from dcos_commons_tpu.models.moe import moe_sharding_rules

        parts["moe"] = {"mlp_norm": P(None, None), **moe_sharding_rules(
            stacked=True, expert_bias=config.moe_expert_bias,
            shared=config.n_shared_experts > 0,
        )}
    if config.sandwich_norm:
        parts["attention"]["attn_post_norm"] = P(None, None)
        for ffn in ("dense", "moe"):
            if ffn in parts:
                parts[ffn]["mlp_post_norm"] = P(None, None)
    parts["sliding"] = parts["attention"]
    rules = {"embed": P("tp", "fsdp"), "final_norm": P(None)}
    for part, leaves in parts.items():
        if not config.n_layers_of(part):
            continue
        prefix = "layers/" if config.one_kind else f"layers/{part}/"
        rules.update({prefix + name: spec for name, spec in leaves.items()})
    if not config.tie_embeddings:
        rules["lm_head"] = P("fsdp", "tp")
    return rules


def param_shardings(config: TransformerConfig, mesh: Mesh, shapes=None):
    rules = sharding_rules(config)

    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            return {
                name: walk(sub, f"{prefix}/{name}" if prefix else name)
                for name, sub in tree.items()
            }
        return NamedSharding(mesh, rules[prefix])

    if shapes is None:
        shapes = jax.eval_shape(
            functools.partial(init_params, config), jax.random.key(0)
        )
    return walk(shapes)


def _norm(config: TransformerConfig, x: jax.Array, w: jax.Array) -> jax.Array:
    """The configuration's RMSNorm: its epsilon, and ``1 + w`` for the
    weight where the stored one is the offset from one (widened first:
    ``1 + w`` in bf16 would round the offset away)."""
    if config.norm_unit_offset:
        w = 1.0 + w.astype(jnp.float32)
    return rms_norm(x, w, eps=config.rms_norm_eps)


def head_logits(
    config: TransformerConfig, params: Params, x: jax.Array
) -> jax.Array:
    """Final hidden states ``[..., d]`` (already normed) -> float32
    next-token logits ``[..., vocab]``: against the embedding where the
    head is tied, else against the first ``vocab`` columns of
    ``lm_head`` (its other columns are the self-speculation heads)."""
    x = x.astype(jnp.float32)
    if config.tie_embeddings:
        return jnp.einsum(
            "...d,vd->...v", x, params["embed"].astype(jnp.float32)
        )
    head = params["lm_head"][:, :config.vocab]
    return jnp.einsum("...d,dv->...v", x, head.astype(jnp.float32))


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings; x [b, s, heads, head_dim]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def _attention_block(config: TransformerConfig, layer, x, positions):
    b, s, d = x.shape
    h, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    if config.attention != "gqa":
        raise NotImplementedError(
            f"attention {config.attention!r} has a serving path only "
            "(models/decode.py paged_prefill_chunk / paged_decode_step)"
        )
    normed = _norm(config, x, layer["attn_norm"])
    q = (normed @ dq(layer["wq"], x.dtype)).reshape(b, s, h, hd)
    k = (normed @ dq(layer["wk"], x.dtype)).reshape(b, s, kv, hd)
    v = (normed @ dq(layer["wv"], x.dtype)).reshape(b, s, kv, hd)
    q = _rope(q, positions, config.rope_theta)
    k = _rope(k, positions, config.rope_theta)
    if kv != h:
        reps = h // kv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    # [b, heads, s, hd] layout for the kernels
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if config.use_ring_attention:
        from dcos_commons_tpu.parallel.ring import ring_attention

        attn = ring_attention(q, k, v, axis_name=config.sp_axis, causal=True)
    elif config.use_ulysses_attention:
        from dcos_commons_tpu.parallel.ulysses import ulysses_attention

        attn = ulysses_attention(
            q, k, v, axis_name=config.sp_axis, causal=True,
            block_q=config.attn_block_q, block_k=config.attn_block_k,
        )
    else:
        attn = flash_attention(
            q, k, v, causal=True,
            block_q=config.attn_block_q, block_k=config.attn_block_k,
        )
    if config.remat and config.remat_policy == "save-attn":
        from jax.ad_checkpoint import checkpoint_name

        attn = checkpoint_name(attn, "attn_out")
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return x + attn @ dq(layer["wo"], x.dtype)


def _mlp(config: TransformerConfig, layer, x):
    """The dense SwiGLU of ``x``'s norm, before the residual."""
    normed = _norm(config, x, layer["mlp_norm"])
    gate = jax.nn.silu(normed @ dq(layer["w_gate"], x.dtype))
    up = normed @ dq(layer["w_up"], x.dtype)
    return (gate * up) @ dq(layer["w_down"], x.dtype)


def _mlp_block(config: TransformerConfig, layer, x):
    return x + _mlp(config, layer, x)


def _ffn_block(config: TransformerConfig, layer, x):
    """The per-layer FFN of the TRAINING forward: dense SwiGLU or MoE.
    Returns (x, aux).

    MoE notes: tokens route in groups of <= moe_group_size (bounding
    the one-hot dispatch tensors; groups never span batch rows, so the
    slot cumsum stays within a dp shard) under the capacity factor's
    pressure.  A server must not drop: the serving programs go through
    models/decode.py ``_serve_ffn`` (moe.py ``moe_serve_ffn``)."""
    if config.n_experts <= 0:
        return _mlp_block(config, layer, x), jnp.zeros((), jnp.float32)
    from dcos_commons_tpu.models.moe import moe_ffn

    b, s, d = x.shape
    moe_config = moe_config_of(config)
    moe_params = {
        key: layer[key] for key in (
            "router", "w_gate", "w_up", "w_down", "expert_bias"
        ) if key in layer
    }
    normed = _norm(config, x, layer["mlp_norm"])
    # group = a whole number of sequence positions per batch row so
    # groups never straddle rows; fall back to one row per group
    group = s if s <= config.moe_group_size else (
        config.moe_group_size if s % config.moe_group_size == 0 else s
    )
    tokens = normed.reshape(b * s // group, group, d)
    # axis_name=None: under jit, GSPMD partitions the expert einsums
    # from the param shardings (expert axis over ep) and inserts the
    # dispatch collectives — the shard_map path stays available for
    # explicit all_to_all control (dryrun's ep section)
    y, aux = jax.vmap(
        lambda g: moe_ffn(
            moe_config, moe_params, g, impl=config.moe_impl,
        )
    )(tokens)
    return x + y.reshape(b, s, d), aux.mean()


def _layer_scan(config: TransformerConfig, layers, x, positions):
    """Run x through a (sub)stack of layers with lax.scan.

    Mixed remat (``no_remat_layers`` = k > 0): the LAST k layers scan
    WITHOUT jax.checkpoint, storing their activations instead of
    recomputing them in backward.  Full-layer remat costs a whole
    extra forward (2NP FLOPs, ~24% of the train step at the flagship
    size); every layer that fits its activations in leftover HBM buys
    that fraction of the recompute back.  The non-remat span is the
    tail because those activations die first in backward."""

    if not config.one_kind or config.serving_only:
        raise NotImplementedError(
            "the training forward scans ONE kind of layer, plain "
            "attention and a routed mixture alone; this layer pattern "
            f"({sorted(set(config.layer_kinds))}, with "
            f"{config.serving_only or 'nothing else'}) has a serving path "
            "only (models/decode.py paged_prefill_chunk / "
            "paged_decode_step)"
        )

    def layer_fn(x, layer):
        x = _attention_block(config, layer, x, positions)
        x, aux = _ffn_block(config, layer, x)
        return x, aux

    remat_fn = layer_fn
    if config.remat:
        if config.remat_policy == "save-attn":
            from jax.ad_checkpoint import checkpoint_policies

            remat_fn = jax.checkpoint(
                layer_fn,
                policy=checkpoint_policies.save_only_these_names(
                    "attn_out"
                ),
            )
        else:
            remat_fn = jax.checkpoint(layer_fn)
    k = config.no_remat_layers if config.remat else 0
    if k <= 0:
        x, aux = lax.scan(remat_fn, x, layers)
        return x, aux.sum()
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    k = min(k, n_layers)
    head = jax.tree.map(lambda a: a[: n_layers - k], layers)
    tail = jax.tree.map(lambda a: a[n_layers - k:], layers)
    aux_total = jnp.zeros((), jnp.float32)
    if n_layers - k > 0:
        x, aux = lax.scan(remat_fn, x, head)
        aux_total = aux_total + aux.sum()
    x, aux = lax.scan(layer_fn, x, tail)
    return x, aux_total + aux.sum()


def _logits(config: TransformerConfig, params: Params, x: jax.Array) -> jax.Array:
    x = _norm(config, x, params["final_norm"])
    if not config.tie_embeddings:
        return head_logits(config, params, x)
    # tied embeddings; f32 logits for a stable softmax
    return jnp.einsum(
        "bsd,vd->bsv", x.astype(jnp.float32),
        params["embed"].astype(jnp.float32),
    )


def _nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def forward(
    config: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """tokens [b, s] -> logits [b, s, vocab] (f32)."""
    x, _aux = _trunk(config, params, tokens, positions)
    return _logits(config, params, x)


def _trunk(
    config: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    positions: Optional[jax.Array] = None,
):
    """tokens [b, s] -> (final hidden states [b, s, d], moe aux)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if config.use_ring_attention or config.use_ulysses_attention:
            # each sp shard holds a consecutive chunk; RoPE needs the
            # GLOBAL position of every token, so offset by the shard
            idx = lax.axis_index(config.sp_axis)
            positions = positions + idx * s
    x = params["embed"][tokens].astype(config.dtype)
    return _layer_scan(config, params["layers"], x, positions)


def _nll_mean(
    config: TransformerConfig,
    params: Params,
    x: jax.Array,
    targets: jax.Array,
) -> jax.Array:
    """Mean NLL over [b, s] positions from final hidden states.

    With ``loss_chunk`` set, scans the sequence in chunks so only
    [b, chunk, vocab] f32 logits are ever live; jax.checkpoint makes
    the backward recompute each chunk instead of saving it — the same
    FLOPs-for-HBM trade the layer remat makes.
    """
    b, s, _ = x.shape
    chunk = config.loss_chunk
    if chunk <= 0 or s % chunk != 0 or s == chunk:
        return _nll(_logits(config, params, x), targets).mean()
    n_chunks = s // chunk
    xs = x.reshape(b, n_chunks, chunk, -1).swapaxes(0, 1)
    ts = targets.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def chunk_sum(total, operand):
        xc, tc = operand
        return total + _nll(_logits(config, params, xc), tc).sum(), None

    total, _ = lax.scan(jax.checkpoint(chunk_sum), jnp.zeros((), jnp.float32),
                        (xs, ts))
    return total / (b * s)


def loss_fn(
    config: TransformerConfig, params: Params, tokens: jax.Array,
    targets: jax.Array,
) -> jax.Array:
    x, aux = _trunk(config, params, tokens)
    loss = _nll_mean(config, params, x, targets)
    if config.n_experts > 0:
        # switch-transformer load-balancing term, averaged per layer
        loss = loss + config.moe_aux_weight * aux / config.n_layers
    return loss


def _pipeline_trunk(
    config: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    n_micro: int,
    axis_name: str,
) -> jax.Array:
    """Embed + pipelined layer stack.  Returns microbatched
    activations [n_micro, mb, s, d] — valid on the LAST pp rank only.
    """
    if config.n_experts > 0:
        raise NotImplementedError(
            "MoE layers are not pipelined yet: run ep x dp/fsdp/tp "
            "meshes for the MoE flagship"
        )
    b, s = tokens.shape
    mb = b // n_micro
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (mb, s))
    x = params["embed"][tokens].astype(config.dtype)
    micro = split_microbatches(x, n_micro)
    stage_fn = lambda layers, x: _layer_scan(config, layers, x, positions)[0]
    return pipeline_apply(stage_fn, params["layers"], micro, axis_name)


def pipeline_forward(
    config: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    n_micro: int,
    axis_name: str = "pp",
) -> jax.Array:
    """Forward with the layer trunk pipelined over the ``pp`` axis.

    Call inside shard_map with ``axis_name`` bound.  ``params`` holds
    this rank's stage: every ``layers`` leaf carries only the local
    n_layers/pp slice of the stack (shard the leading axis over pp);
    embed/final_norm are replicated and computed identically on every
    rank.  Batch is split into ``n_micro`` GPipe microbatches.
    Returns replicated logits (an activation-sized psum — prefer
    :func:`pipeline_loss_fn` for training, which only psums a scalar).
    """
    out = _pipeline_trunk(config, params, tokens, n_micro, axis_name)
    out = last_stage_value(out, axis_name)
    return _logits(config, params, merge_microbatches(out))


def pipeline_loss_fn(
    config: TransformerConfig,
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    n_micro: int,
    axis_name: str = "pp",
) -> jax.Array:
    """Mean NLL, replicated over pp ranks.

    The vocab logits matmul + softmax run ONLY on the last pp rank
    (a runtime branch on the rank index); the cross-rank collective is
    a single scalar psum, not an activation broadcast.
    """
    out = _pipeline_trunk(config, params, tokens, n_micro, axis_name)
    x = merge_microbatches(out)
    idx = lax.axis_index(axis_name)
    n = axis_size(axis_name)

    def last_rank_loss(operands):
        params, x, targets = operands
        return _nll(_logits(config, params, x), targets).mean()

    loss_local = lax.cond(
        idx == n - 1,
        last_rank_loss,
        lambda operands: jnp.zeros((), jnp.float32),
        (params, x, targets),
    )
    return lax.psum(loss_local, axis_name)


def pipeline_param_specs(params_or_shapes) -> Dict[str, Any]:
    """PartitionSpec tree for pp sharding: layer stacks split on the
    leading axis, everything else replicated (shard_map in_specs)."""
    from jax.sharding import PartitionSpec as P

    def walk(tree, under_layers=False):
        if isinstance(tree, dict):
            return {
                name: walk(sub, under_layers or name == "layers")
                for name, sub in tree.items()
            }
        return P("pp") if under_layers else P()

    return walk(params_or_shapes)


def train_state_shardings(config: TransformerConfig, optimizer, mesh: Mesh):
    """(params, optimizer-state) NamedSharding trees — the layout the
    mesh train step pins with in/out_shardings.  A caller that
    ``jax.device_put``s its fresh state onto them BEFORE the first
    step gives every step the same input types; left default-placed,
    step 0 runs on one type and step 1 on step 0's mesh-sharded
    outputs, and the same step is traced and compiled twice."""
    from dcos_commons_tpu.parallel.mesh import replicated as rep

    params_shapes = jax.eval_shape(
        lambda: init_params(config, jax.random.key(0))
    )
    p_shard = param_shardings(config, mesh, params_shapes)
    opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
    replicated = NamedSharding(mesh, rep())

    # optimizer state shardings: any leaf shaped like a param (whose
    # path ends with that param's path) inherits the param's sharding;
    # everything else (adam counts, scalars) is replicated
    def path_key(path):
        return tuple(
            str(getattr(k, "key", getattr(k, "idx", "?"))) for k in path
        )

    flat_params = {
        path_key(path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]
    }
    flat_pshard = {
        path_key(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(p_shard)[0]
    }

    def opt_leaf_sharding(path, leaf):
        for ppath, pshape in flat_params.items():
            if leaf.shape == pshape and path[-len(ppath):] == ppath:
                return flat_pshard[ppath]
        return replicated

    opt_shard = jax.tree_util.tree_map_with_path(
        lambda path, leaf: opt_leaf_sharding(path_key(path), leaf),
        opt_shapes,
    )
    return p_shard, opt_shard


def make_train_step(
    config: TransformerConfig,
    optimizer,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    grad_accum: int = 1,
):
    """Build a jitted (params, opt_state, tokens, targets) ->
    (params, opt_state, loss) step.

    With a mesh, in/out shardings pin params to the rule layout and
    batch to (dp, fsdp) x sp; XLA inserts the dp/fsdp gradient
    reduce-scatters and tp activation collectives.

    ``grad_accum`` > 1 splits the batch into that many microbatches
    and accumulates gradients over a ``lax.scan`` before the single
    optimizer update.  Numerics: equal-size splits make the mean of
    per-microbatch mean-losses (and gradients) EQUAL to the full-batch
    mean up to float reassociation — accumulation runs in f32 so k
    bf16 partial sums don't eat mantissa.  Perf: each microbatch's
    dp/fsdp reduce-scatter contributions become scan-carried partial
    sums, so XLA's latency-hiding scheduler can overlap microbatch
    i's ICI/DCN traffic with microbatch i+1's compute instead of
    serializing one giant gradient exchange behind the whole backward
    (megatron/alpa overlap discipline); remat (``config.remat``)
    composes per microbatch, shrinking live activations by the same
    factor.
    """
    grad_accum = max(1, int(grad_accum))
    kernel_mesh = (
        functools.partial(
            jax.sharding.use_abstract_mesh, mesh.abstract_mesh
        )
        if mesh is not None else contextlib.nullcontext
    )

    def grads_of(params, tokens, targets):
        return jax.value_and_grad(
            lambda p: loss_fn(config, p, tokens, targets)
        )(params)

    def accumulate(params, tokens, targets):
        micro = (
            split_microbatches(tokens, grad_accum),
            split_microbatches(targets, grad_accum),
        )

        def one_microbatch(carry, mb):
            loss_sum, grad_sum = carry
            loss, grads = grads_of(params, *mb)
            grad_sum = jax.tree.map(
                lambda acc, g: acc + g.astype(jnp.float32),
                grad_sum, grads,
            )
            return (loss_sum + loss, grad_sum), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, grad_sum), _ = lax.scan(
            one_microbatch, (jnp.zeros((), jnp.float32), zeros), micro
        )
        grads = jax.tree.map(
            lambda p, g: (g / grad_accum).astype(p.dtype), params,
            grad_sum,
        )
        return loss_sum / grad_accum, grads

    def step(params, opt_state, tokens, targets):
        # the kernels find the mesh as the ambient abstract mesh while
        # this body is traced and run per shard (parallel/mesh.py
        # per_shard); without it XLA replicates them on every chip
        with kernel_mesh():
            if grad_accum == 1:
                loss, grads = grads_of(params, tokens, targets)
            else:
                loss, grads = accumulate(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(
            lambda p, u: (p + u.astype(p.dtype)), params, updates
        )
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())

    from dcos_commons_tpu.parallel.mesh import batch_spec, replicated as rep

    p_shard, opt_shard = train_state_shardings(config, optimizer, mesh)
    batch_sharding = NamedSharding(mesh, batch_spec())
    replicated = NamedSharding(mesh, rep())
    return jax.jit(
        step,
        in_shardings=(p_shard, opt_shard, batch_sharding, batch_sharding),
        out_shardings=(p_shard, opt_shard, replicated),
        donate_argnums=(0, 1) if donate else (),
    )
