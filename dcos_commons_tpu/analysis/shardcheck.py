"""shardcheck: static sharding, HBM-footprint, and collective-cost
analysis for the JAX frameworks.

The one launch failure none of the other analyzers can see is a
LAYOUT failure: a ServiceSpec whose declared torus cannot lay the
mesh its worker derives, a PartitionSpec axis the mesh does not
divide into a param dim, or a model whose per-chip HBM footprint
exceeds what the spec reserved — all of which today surface as an
XLA error (or an OOM) minutes into a multi-host pjit deploy.  This
pass closes that gap at lint time, GSPMD-style partitioning
validation moved ahead of the scheduler: for every
``frameworks/jax/*.yml`` rendered with its ``options.json`` defaults
it rebuilds the EXACT workload the task command would run —
``models.config_from_env`` for the model, ``parallel.mesh.derive``
for the mesh (both the very functions the worker calls), real
``sharding_rules`` / ``init_params`` / ``init_paged_kv_cache`` evaluated
ABSTRACTLY via ``jax.eval_shape`` (shape/dtype only: no devices, no
FLOPs, JAX_PLATFORMS=cpu-safe) — and walks params + optimizer state
+ gradient + activation/KV estimates through the PartitionSpec rules.

Rules (YAML-suppressible like speccheck findings, anchored to the
pod's declaring line; absorbable by ``.sdklint-baseline.json``):

- ``shard-mesh``          the declared topology cannot lay a
  host-aligned mesh (``derive`` raises SpecError), the workload's
  mesh spans a different chip count than the pod reserves (idle or
  oversubscribed chips), or a mesh axis of size > 1 shards nothing.
- ``shard-divisibility``  a mesh axis product does not divide the
  param/activation dim its PartitionSpec shards — GSPMD would pad or
  the pjit would fail outright.
- ``shard-unknown-axis``  a PartitionSpec names an axis outside the
  mesh-axis vocabulary (``MeshSpec`` fields plus spmdcheck's
  harvested ``Mesh(...)``/``axis_name=`` vocabulary).
- ``shard-replicated-giant``  a param above ``--giant-mb`` is
  replicated across mesh axes of size > 1 — usually a missing fsdp/tp
  entry in the rules, each replica burning HBM on every chip.
- ``shard-hbm-overcommit``  the per-chip footprint exceeds the
  generation's HBM (``--hbm-mb`` overrides the table), or the
  per-host footprint exceeds the task's declared ``memory:``.

Beyond findings, every analyzed pod emits a footprint breakdown and
a ring-vs-all-gather collective-cost estimate per training step over
the ICI torus (``--json`` keys ``shard.footprint`` / ``shard.cost``)
so bench trends can track layout regressions.

Footprint model (documented in developer-guide §10): params at their
init dtype (int8 + per-channel scale when ``WEIGHT_DTYPE=int8``),
gradients mirroring params (training), optimizer state via
``jax.eval_shape(optimizer.init)`` with param-shaped leaves
inheriting the param's sharding, live activations = per-layer
residual boundaries (remat's floor) + the f32 logits block, and the
KV cache via the real ``init_paged_kv_cache`` (serving).  Per-chip bytes
divide each dim by the product of its mesh-axis sizes; everything a
spec does not shard replicates.
"""

from __future__ import annotations

import functools
import math
import os
import re
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Dict,
    FrozenSet,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
)

from dcos_commons_tpu.analysis.linter import (
    Finding,
    LintResult,
    Suppressions,
)

# per-chip HBM by TPU generation (MB) — the capacity the footprint is
# judged against when the spec's host memory is roomier than the chip
GENERATION_HBM_MB = {
    "v4": 32 * 1024,
    "v5e": 16 * 1024,
    "v5p": 95 * 1024,
    "v6e": 32 * 1024,
}
# per-link ICI bandwidth (GB/s, one direction) for the cost estimate
ICI_GBPS = {"v4": 45.0, "v5e": 45.0, "v5p": 90.0, "v6e": 90.0}
DEFAULT_ICI_GBPS = 45.0
# cross-slice data-center network (dcn axis collectives)
DCN_GBPS = 12.5


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def normalize_spec(spec, rank: int) -> Tuple[Tuple[str, ...], ...]:
    """PartitionSpec -> per-dim tuples of axis names, length ``rank``.

    ``P("tp", ("dp", "fsdp"), None)`` at rank 4 becomes
    ``(("tp",), ("dp", "fsdp"), (), ())``.
    """
    entries: List[Tuple[str, ...]] = []
    for entry in tuple(spec or ()):
        if entry is None:
            entries.append(())
        elif isinstance(entry, str):
            entries.append((entry,))
        else:
            entries.append(tuple(entry))
    while len(entries) < rank:
        entries.append(())
    return tuple(entries[:rank])


@dataclass(frozen=True)
class AbstractLeaf:
    """One abstract array: a param, grad, optimizer, activation, or
    KV-cache tensor with its sharding rule."""

    path: str                       # e.g. "params/layers/wq"
    shape: Tuple[int, ...]
    dtype_bytes: int
    spec: Tuple[Tuple[str, ...], ...]
    section: str                    # params|grads|opt|activations|kv
    # the sharding-rule path this leaf's spec came from: the dedup
    # identity, so the params/grads/opt copies of one bad rule report
    # ONE finding (defaults to the path minus its section prefix)
    rule_path: str = ""

    @property
    def bytes(self) -> int:
        return _prod(self.shape) * self.dtype_bytes

    @property
    def dedup_path(self) -> str:
        return self.rule_path or self.path.split("/", 1)[-1]


@dataclass
class LeafReport:
    """The sharding arithmetic of one leaf over one mesh."""

    leaf: AbstractLeaf
    per_chip_bytes: int = 0
    shard_product: int = 1
    replication: int = 1
    # (rule-id, dedup-key, message) triples
    problems: List[Tuple[str, str, str]] = field(default_factory=list)


def shard_leaf(
    leaf: AbstractLeaf,
    axes: Mapping[str, int],
    vocab: FrozenSet[str] = frozenset(),
) -> LeafReport:
    """Divide one leaf over the mesh; the exactness property
    (tests/test_shard_properties.py) is
    ``per_chip_bytes * total_chips == bytes * replication``
    whenever every sharded dim divides evenly."""
    report = LeafReport(leaf)
    bare = leaf.dedup_path
    per_chip_elems = 1
    for i, dim in enumerate(leaf.shape):
        names = leaf.spec[i] if i < len(leaf.spec) else ()
        q = 1
        for name in names:
            size = axes.get(name)
            if size is None:
                if name not in vocab:
                    report.problems.append((
                        "shard-unknown-axis",
                        f"{bare}:{name}",
                        f"{leaf.path} dim {i}: PartitionSpec names "
                        f"axis {name!r}, which is in no mesh-axis "
                        "vocabulary of the tree",
                    ))
                # harvested-but-unlaid axes act as size 1 (replicated)
                continue
            q *= size
        if q > 1 and dim % q:
            report.problems.append((
                "shard-divisibility",
                f"{bare}:{i}",
                f"{leaf.path}: mesh axes {'*'.join(names)} (size {q}) "
                f"do not divide dim {i} of shape "
                f"{tuple(leaf.shape)} ({dim} % {q} = {dim % q})",
            ))
        report.shard_product *= q
        per_chip_elems *= math.ceil(dim / q)
    total = _prod(axes.values()) or 1
    report.per_chip_bytes = per_chip_elems * leaf.dtype_bytes
    report.replication = max(total // report.shard_product, 1)
    return report


def _walk_shapes(tree, rules: Mapping[str, Any], section: str,
                 dtype_bytes=None, prefix: str = "") -> List[AbstractLeaf]:
    """Flatten an eval_shape dict tree into AbstractLeafs via the
    path->PartitionSpec rules (the transformer's sharding_rules
    layout)."""
    out: List[AbstractLeaf] = []
    if isinstance(tree, dict):
        for name, sub in sorted(tree.items()):
            out += _walk_shapes(
                sub, rules, section, dtype_bytes,
                f"{prefix}/{name}" if prefix else name,
            )
        return out
    shape = tuple(int(d) for d in tree.shape)
    spec = normalize_spec(rules.get(prefix), len(shape))
    out.append(AbstractLeaf(
        path=f"{section}/{prefix}",
        shape=shape,
        dtype_bytes=int(dtype_bytes or tree.dtype.itemsize),
        spec=spec,
        section=section,
    ))
    return out


@dataclass
class Workload:
    """The abstract workload one pod task runs: its mesh and every
    tensor the footprint model tracks."""

    script: str
    mesh: Any                       # parallel.mesh.MeshSpec
    leaves: List[AbstractLeaf]
    train: bool = False
    # tp-axis activation payload per train step (bytes, pre-sharding)
    # for the cost model; 0 when the profile has no layer activations
    tp_act_bytes: int = 0


# -- workload profiles -------------------------------------------------
#
# script basename -> builder(env, tpu, pod, task) -> Workload.  The
# env is the task's YAML env merged under TpuSpec.mesh_env() — the
# same contract offer/evaluate.py assembles at launch.  Tests (and
# future frameworks) register new entries by assignment.


def _abstract_params(config):
    """(eval_shape param tree, sharding rules) for one config — built
    once per workload and threaded to every consumer."""
    import jax

    from dcos_commons_tpu.models.transformer import (
        init_params,
        sharding_rules,
    )

    # the key is made UNDER the eval_shape trace: a concrete
    # jax.random.key(0) argument is a device array, i.e. a backend
    # init, and this runs inside the scheduler process (PUT
    # /v1/multi admission) — which must never own the chip its
    # agent-launched workers need
    shapes = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    return shapes, sharding_rules(config)


def _param_leaves(shapes, rules, quantized: bool = False,
                  section: str = "params") -> List[AbstractLeaf]:
    # quantized (serve workers' WEIGHT_DTYPE=int8): matmul weights at
    # ~1 byte/elem (per-output-channel f32 scales, <1%, folded in).
    # Training never quantizes, so its profile never sets this.
    leaves = _walk_shapes(shapes, rules, section)
    if quantized:
        leaves = [
            AbstractLeaf(l.path, l.shape, 1, l.spec, l.section)
            if len(l.shape) >= 2 and l.dtype_bytes > 1 else l
            for l in leaves
        ]
    return leaves


def _opt_leaves(params_shapes, rules, optimizer) -> List[AbstractLeaf]:
    """Optimizer-state leaves: any leaf shaped like a param (path
    suffix matching) inherits the param's sharding; scalars/counters
    replicate — the same inheritance make_train_step applies."""
    import jax

    opt_shapes = jax.eval_shape(optimizer.init, params_shapes)

    def path_key(path):
        return tuple(
            str(getattr(k, "key", getattr(k, "idx", "?"))) for k in path
        )

    flat_params = {
        path_key(path): tuple(leaf.shape)
        for path, leaf in
        jax.tree_util.tree_flatten_with_path(params_shapes)[0]
    }
    out: List[AbstractLeaf] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_shapes)[0]:
        key = path_key(path)
        shape = tuple(int(d) for d in leaf.shape)
        spec: Tuple[Tuple[str, ...], ...] = ()
        matched = ""
        for ppath, pshape in flat_params.items():
            if shape == pshape and key[-len(ppath):] == ppath:
                matched = "/".join(ppath)
                spec = normalize_spec(rules.get(matched), len(shape))
                break
        out.append(AbstractLeaf(
            path="opt/" + "/".join(key),
            shape=shape,
            dtype_bytes=int(leaf.dtype.itemsize),
            spec=spec or normalize_spec(None, len(shape)),
            section="opt",
            rule_path=matched,
        ))
    return out


def _batch_entry() -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(batch-dim axes, seq-dim axes) from the REAL batch_spec()."""
    from dcos_commons_tpu.parallel.mesh import batch_spec

    spec = normalize_spec(batch_spec(), 2)
    return spec[0], spec[1]


def _train_profile(env, tpu, pod, task) -> Workload:
    from dcos_commons_tpu.models.transformer import config_from_env
    from dcos_commons_tpu.parallel.mesh import derive

    config = config_from_env(env)
    mesh = derive(env)          # SpecError -> shard-mesh at the caller
    shapes, rules = _abstract_params(config)
    leaves = _param_leaves(shapes, rules)
    leaves += [
        AbstractLeaf(l.path.replace("params/", "grads/", 1), l.shape,
                     l.dtype_bytes, l.spec, "grads")
        for l in leaves
    ]
    try:
        import optax

        leaves += _opt_leaves(shapes, rules, optax.adamw(3e-4))
    except ImportError:         # container without optax: adam-shaped
        leaves += [             # f32 mu/nu mirror of the params
            AbstractLeaf(l.path.replace("params/", f"opt/{m}/", 1),
                         l.shape, 4, l.spec, "opt")
            for l in leaves if l.section == "params" for m in ("mu", "nu")
        ]
    import numpy as np

    batch_axes, seq_axes = _batch_entry()
    b = max(2, 2 * mesh.total)
    s, d = config.max_seq, config.d_model
    act_bytes = int(np.dtype(config.dtype).itemsize)
    # remat's floor: one residual-stream boundary per layer stays live
    leaves.append(AbstractLeaf(
        "act/layer-boundaries", (config.n_layers, b, s, d), act_bytes,
        ((), batch_axes, seq_axes, ()), "activations",
    ))
    # the f32 logits block (loss_chunk bounds it when set)
    chunk = config.loss_chunk if 0 < config.loss_chunk < s else s
    leaves.append(AbstractLeaf(
        "act/logits", (b, chunk, config.vocab), 4,
        (batch_axes, seq_axes, ()), "activations",
    ))
    # fwd+bwd activation collectives over tp ride 2 allreduces/layer
    tp_act = 4 * config.n_layers * b * s * d * act_bytes
    return Workload(
        script="train_worker.py", mesh=mesh, leaves=leaves, train=True,
        tp_act_bytes=tp_act,
    )


def _mnist_profile(env, tpu, pod, task) -> Workload:
    import jax

    from dcos_commons_tpu.models.mlp import MlpConfig, mlp_init
    from dcos_commons_tpu.parallel.mesh import MeshSpec

    config = MlpConfig()
    # key made under the trace: see _abstract_params
    shapes = jax.eval_shape(lambda: mlp_init(config, jax.random.key(0)))
    leaves = _walk_shapes(shapes, {}, "params")
    leaves += [
        AbstractLeaf(l.path.replace("params/", f"opt/{m}/", 1), l.shape,
                     l.dtype_bytes, l.spec, "opt")
        for l in leaves for m in ("mu", "nu")
    ]
    # train_mnist.py runs a plain single-device jit: its "mesh" is one
    # chip, whatever the pod reserves
    return Workload(
        script="train_mnist.py", mesh=MeshSpec(), leaves=leaves,
        train=True,
    )


def _serve_leaves(env, mesh_total_tp: int) -> Tuple[Any, List[AbstractLeaf]]:
    import jax

    from dcos_commons_tpu.models.decode import (
        init_paged_kv_cache,
        whole_lanes,
    )
    from dcos_commons_tpu.models.transformer import config_from_env
    from dcos_commons_tpu.serve.paging import paged_config_from_env

    config = config_from_env(env, remat=False)
    shapes, rules = _abstract_params(config)
    leaves = _param_leaves(
        shapes, rules,
        quantized=env.get("WEIGHT_DTYPE", "native") == "int8",
    )
    # the serving KV footprint IS the runtime allocation, exactly:
    # the PAGED ARENA (serve/paging.py, ISSUE 11) — KV_PAGES usable
    # pages + the trash page, each KV_PAGE_TOKENS entries (a
    # position's K/V, or a chunk summary of a windowed row layout:
    # one shape, so the arena's bytes do not depend on the layout —
    # only how many rows it admits does, and the default KV_PAGES
    # follows the layout's table), shaped by the SAME
    # paged_config_from_env contract the workers and the PR 9
    # admission gate consume (a geometry that cannot serve is a
    # SpecError at derivation, so admission rejects page-budget
    # overcommit at PUT time).  It honors KV_DTYPE (int8 halves the
    # bytes).
    # A managed budget, not a per-request guess: occupancy within
    # this allocation is the runtime gauge (kv_occupancy /
    # kv_pages_free), the allocation itself is what HBM must hold.
    paged = paged_config_from_env(env)
    slots = paged.slots
    cache_shapes = jax.eval_shape(functools.partial(
        init_paged_kv_cache, config, paged.arena_pages,
        paged.page_tokens, env.get("KV_DTYPE", "native"), slots,
        # as the chip holds it: entries in whole 128-lane rows
        whole_lanes(config.head_dim),
        # the window layers' arena, every slot's ring and a trash page
        # (0 where the pattern has no such layer)
        paged.window_arena_pages,
    ))
    # cache dims (layers, pages, tokens, kv_heads, head_dim): heads
    # ride tp like the attention weights when divisible (the gang
    # worker's cache_sharding — kv heads sit on dim 3), else the
    # cache replicates; pages replicate across the gang (every rank
    # steps the same broadcast pool)
    kv_sharded = (
        mesh_total_tp > 1 and config.n_kv_heads % mesh_total_tp == 0
    )
    kv_spec = {
        # what a row keeps outside its pages (layers, slots, taps - 1,
        # d_model) is one chip's, as the window layers' rings are: the
        # gang refuses such a pattern
        name: ((), (), (), ()) if name == "conv_state"
        else ((), (), (), (), ()) if name.endswith("_window")
        else ((), (), (), ("tp",) if kv_sharded else (), ())
        for name in cache_shapes
    }
    leaves += _walk_shapes(cache_shapes, kv_spec, "kv")
    import numpy as np

    # pool decode-step residual + final logits: every slot computes
    # each step (static shapes); small next to params + the pool
    leaves.append(AbstractLeaf(
        "act/decode-step", (slots, 1, config.d_model),
        int(np.dtype(config.dtype).itemsize),
        ((), (), ()), "activations",
    ))
    leaves.append(AbstractLeaf(
        "act/logits", (slots, 1, config.vocab), 4,
        ((), (), ()), "activations",
    ))
    return config, leaves


def _serve_profile(env, tpu, pod, task) -> Workload:
    from dcos_commons_tpu.parallel.mesh import MeshSpec

    # serve_worker.py is the dispatch-free single-chip path
    _, leaves = _serve_leaves(env, mesh_total_tp=1)
    return Workload(script="serve_worker.py", mesh=MeshSpec(),
                    leaves=leaves)


def _serve_gang_profile(env, tpu, pod, task) -> Workload:
    from dcos_commons_tpu.parallel.mesh import MeshSpec

    # serve_gang_worker.py lays the WHOLE gang as one tp axis
    total = tpu.total_chips * max(tpu.slices, 1)
    _, leaves = _serve_leaves(env, mesh_total_tp=total)
    return Workload(script="serve_gang_worker.py",
                    mesh=MeshSpec(tp=total), leaves=leaves)


PROFILES: Dict[str, Callable] = {
    "train_worker.py": _train_profile,
    "train_mnist.py": _mnist_profile,
    "serve_worker.py": _serve_profile,
    "serve_gang_worker.py": _serve_gang_profile,
}


# -- the analysis ------------------------------------------------------


@dataclass
class ShardReport:
    """Machine-readable per-pod output (--json shard.footprint/cost)."""

    key: str                        # "frameworks/jax/svc.yml:trainer"
    script: str
    mesh: Dict[str, int]
    chips: int
    footprint: Dict[str, Any]
    cost: Optional[Dict[str, Any]] = None


@dataclass
class ShardResult(LintResult):
    reports: List[ShardReport] = field(default_factory=list)


def _axis_vocabulary(root: str) -> FrozenSet[str]:
    """spmdcheck's harvest: every axis a Mesh(...)/MeshSpec/axis_name=
    default declares across the data-plane tree."""
    from dcos_commons_tpu.analysis import spmdcheck

    try:
        files = spmdcheck._collect_files(
            root, ("dcos_commons_tpu/parallel", "dcos_commons_tpu/models")
        )
        return frozenset(spmdcheck.build_summary(files).axis_vocab)
    except OSError:
        return frozenset()


def _check_workload(
    workload: Workload,
    vocab: FrozenSet[str],
) -> Tuple[List[LeafReport], List[Tuple[str, str, str]]]:
    """Shard every leaf; returns (reports, deduped problems)."""
    axes = workload.mesh.axes()
    reports = [shard_leaf(leaf, axes, vocab) for leaf in workload.leaves]
    seen: Dict[Tuple[str, str], str] = {}
    for report in reports:
        for rule, key, message in report.problems:
            seen.setdefault((rule, key), message)
    problems = [(rule, key, msg) for (rule, key), msg in seen.items()]
    # a laid mesh axis no PartitionSpec consumes is dead weight: every
    # chip along it computes the identical program
    used = {
        name
        for leaf in workload.leaves
        for names in leaf.spec
        for name in names
    }
    for name, size in axes.items():
        if size > 1 and name not in used:
            problems.append((
                "shard-mesh", f"idle-axis:{name}",
                f"mesh lays axis {name}={size} but no PartitionSpec "
                "of the workload shards anything over it",
            ))
    return reports, sorted(problems)


def _footprint(
    workload: Workload, reports: Sequence[LeafReport]
) -> Dict[str, Any]:
    sections: Dict[str, float] = {}
    for report in reports:
        mb = report.per_chip_bytes / (1024.0 * 1024.0)
        sections[report.leaf.section] = (
            sections.get(report.leaf.section, 0.0) + mb
        )
    per_chip = sum(sections.values())
    return {
        "per_chip_mb": round(per_chip, 2),
        "sections_mb": {k: round(v, 2) for k, v in sorted(sections.items())},
        "mesh_chips": workload.mesh.total,
    }


def _ring_vs_allgather(payload_bytes: float, k: int, gbps: float,
                       axis: str, op: str) -> Dict[str, Any]:
    """Wire bytes per chip for a k-way exchange of ``payload_bytes``:
    ring allreduce moves 2(k-1)/k × B; the all-gather-then-reduce
    spelling moves (k-1) × B (every chip pulls every shard).  For
    all_to_all both spellings move (k-1)/k × B."""
    if op == "all_to_all":
        ring = gather = payload_bytes * (k - 1) / k
    else:
        ring = 2.0 * payload_bytes * (k - 1) / k
        gather = payload_bytes * (k - 1)
    to_us = 1e6 / (gbps * 2 ** 30)
    return {
        "axis": axis,
        "participants": k,
        "op": op,
        "payload_mb": round(payload_bytes / 2 ** 20, 3),
        "ring_mb_per_chip": round(ring / 2 ** 20, 3),
        "allgather_mb_per_chip": round(gather / 2 ** 20, 3),
        "ring_us": round(ring * to_us, 1),
        "allgather_us": round(gather * to_us, 1),
        "recommend": "ring" if ring <= gather else "all-gather",
    }


def _cost_model(
    workload: Workload,
    reports: Sequence[LeafReport],
    generation: str,
) -> Optional[Dict[str, Any]]:
    """Per-training-step collective bytes/latency over the ICI torus.

    Gradient reduction rides the data axes (dcn over DCN, dp/fsdp over
    ICI) at the PER-CHIP gradient size; tp moves 2 activation
    allreduces per layer each direction; ep moves the two dispatch
    all_to_alls.  Estimates, not measurements — their value is the
    TREND across config changes, tracked via ``--json``.
    """
    if not workload.train:
        return None
    axes = workload.mesh.axes()
    ici = ICI_GBPS.get(generation, DEFAULT_ICI_GBPS)
    grad_per_chip = sum(
        r.per_chip_bytes for r in reports if r.leaf.section == "grads"
    )
    entries: List[Dict[str, Any]] = []
    for axis in ("dcn", "dp", "fsdp"):
        k = axes[axis]
        if k <= 1:
            continue
        gbps = DCN_GBPS if axis == "dcn" else ici
        op = "reduce_scatter+all_gather" if axis == "fsdp" else "allreduce"
        entries.append(
            _ring_vs_allgather(grad_per_chip, k, gbps, axis, op)
        )
    if axes["tp"] > 1 and workload.tp_act_bytes:
        batch_shard = _prod(
            axes[a] for a in ("dcn", "dp", "fsdp", "sp")
        )
        entries.append(_ring_vs_allgather(
            workload.tp_act_bytes / max(batch_shard, 1), axes["tp"],
            ici, "tp", "allreduce",
        ))
    if axes["ep"] > 1:
        moe_per_chip = sum(
            r.per_chip_bytes for r in reports
            if r.leaf.section == "activations"
            and "layer-boundaries" in r.leaf.path
        )
        entries.append(_ring_vs_allgather(
            2.0 * moe_per_chip, axes["ep"], ici, "ep", "all_to_all",
        ))
    if not entries:
        return {"per_step": [], "total_ring_us": 0.0,
                "total_allgather_us": 0.0}
    return {
        "per_step": entries,
        "total_ring_us": round(sum(e["ring_us"] for e in entries), 1),
        "total_allgather_us": round(
            sum(e["allgather_us"] for e in entries), 1
        ),
    }


def stepcompare(
    cost: Optional[Dict[str, Any]],
    records: Sequence[Dict[str, Any]],
    floor_us: float = 0.0,
    slack: float = 0.25,
    skip: int = 1,
) -> Dict[str, Any]:
    """Predicted-vs-measured step time: the ``shard.cost`` wire-time
    model held against a worker's steplog JSONL records (ISSUE 7).

    ``cost`` is a :func:`_cost_model` dict (or None when the mesh has
    no collectives — a single chip); its wire floor sums, PER AXIS,
    the cheaper of the ring and all-gather spellings — each axis's
    collective picks its own spelling independently, and the dcn
    entry rides the DCN bandwidth table, so a multi-slice gang's
    floor includes its cross-slice gradient leg instead of letting
    the slow DCN hop hide inside a whole-model min (ISSUE 20: the
    gate would otherwise read an honest multi-slice step as a
    regression — or a regressed one as fine).  ``floor_us`` is the
    caller's calibrated compute floor (the cost model speaks only for
    the interconnect; bench_train_step calibrates compute by running
    the bare device loop).  ``records`` are steplog dicts — ``wall_s``
    is what each step actually took, ``blocked_s`` what the gang skew
    cost on top.

    The verdict: ``measured_over_floor_x`` is MEAN measured wall over
    the combined floor, and ``regression`` trips when it exceeds
    ``1 + slack`` — the perf gate "measured step time regressed >X%
    against the cost-model floor".  The mean is the gate statistic
    (not p50) because the window's billing conserves TOTAL wall —
    each step is billed ready-to-ready time, so host-side stalls and
    pipeline-fill land somewhere in the stream even when event
    clustering skews individual records; p50/p95 are reported for
    shape.  ``regression`` is None (ungated) when there is nothing to
    gate against: no records, or a zero combined floor.

    ``skip`` drops the first records in LOG ORDER (default 1): a cold
    worker's step 0 bills the jit compile plus pipeline fill — one
    multi-second record that would dominate the mean of a short log
    and is not a property of the steady-state step.
    """
    from dcos_commons_tpu.metrics.registry import percentile
    from dcos_commons_tpu.trace.steplog import step_records

    # steps only: a serve worker's start-up phases share the steplog
    records = step_records(records)[max(0, int(skip)):]
    walls = sorted(
        float(r["wall_s"]) for r in records
        if isinstance(r.get("wall_s"), (int, float))
    )
    blocked = sorted(
        float(r["blocked_s"]) for r in records
        if isinstance(r.get("blocked_s"), (int, float))
    )
    wire_us = 0.0
    dcn_wire_us = 0.0
    if cost and cost.get("per_step"):
        # per-axis cheaper-of: each collective runs ONE spelling, so
        # the floor is the sum of per-axis minima (<= the min of the
        # whole-model sums — the gate only loosens for old specs)
        for e in cost["per_step"]:
            leg = min(
                float(e.get("ring_us", 0.0)),
                float(e.get("allgather_us", 0.0)),
            )
            wire_us += leg
            if e.get("axis") == "dcn":
                dcn_wire_us += leg
    predicted_floor_us = wire_us + max(0.0, float(floor_us))
    out: Dict[str, Any] = {
        "steps": len(walls),
        "predicted_wire_us": round(wire_us, 1),
        "predicted_wire_dcn_us": round(dcn_wire_us, 1),
        "compute_floor_us": round(float(floor_us), 1),
        "predicted_floor_us": round(predicted_floor_us, 1),
        "slack": slack,
        "measured_mean_us": None,
        "measured_p50_us": None,
        "measured_p95_us": None,
        "blocked_p50_us": None,
        "measured_over_floor_x": None,
        "regression": None,
    }
    if not walls:
        return out
    mean_us = sum(walls) / len(walls) * 1e6
    out["measured_mean_us"] = round(mean_us, 1)
    out["measured_p50_us"] = round(percentile(walls, 50) * 1e6, 1)
    out["measured_p95_us"] = round(percentile(walls, 95) * 1e6, 1)
    if blocked:
        out["blocked_p50_us"] = round(percentile(blocked, 50) * 1e6, 1)
    if predicted_floor_us > 0:
        ratio = mean_us / predicted_floor_us
        out["measured_over_floor_x"] = round(ratio, 3)
        out["regression"] = bool(ratio > 1.0 + slack)
    return out


def _yml_files(framework_dir: str) -> List[str]:
    return sorted(
        os.path.join(framework_dir, f)
        for f in os.listdir(framework_dir)
        if f.endswith(".yml")
    )


def _match_profile(cmd: str) -> Optional[Callable]:
    for script, builder in PROFILES.items():
        if script in (cmd or ""):
            return builder
    return None


def analyze_framework(
    framework_dir: str,
    root: str,
    vocab: FrozenSet[str],
    hbm_mb: int = 0,
    giant_mb: float = 256.0,
) -> ShardResult:
    from dcos_commons_tpu.specification.yaml_spec import from_yaml_file
    from dcos_commons_tpu.tools import options as options_mod

    result = ShardResult()
    disabled: set = set()
    try:
        schema = options_mod.load_schema(framework_dir)
        if schema is not None:
            disabled = {str(r) for r in schema.get("x-sdklint-disable") or []}
        env = options_mod.render_options(schema, {}) if schema else {}
    except options_mod.OptionsError:
        env = {}  # speccheck owns schema errors

    for path in _yml_files(framework_dir):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as f:
            lines = f.read().splitlines()
        try:
            spec = from_yaml_file(path, env)
        except Exception:  # sdklint: disable=swallowed-exception — speccheck owns render/spec errors; shardcheck only reads specs that render
            continue
        anchor = _make_anchor(lines)
        suppressions = Suppressions(lines)
        checked_any = False
        raw: List[Finding] = []
        for pod in spec.pods:
            if pod.tpu is None:
                continue
            for task in pod.tasks:
                builder = _match_profile(task.cmd)
                if builder is None:
                    continue
                checked_any = True
                raw += _analyze_pod_task(
                    rel, pod, task, builder, anchor, vocab,
                    hbm_mb, giant_mb, result.reports,
                )
        if checked_any:
            result.files_checked += 1
        for finding in raw:
            if finding.rule in disabled or "all" in disabled \
                    or suppressions.covers(finding):
                result.suppressed.append(finding)
            else:
                result.findings.append(finding)
    result.findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return result


def _make_anchor(lines: Sequence[str]):
    """Pod findings anchor to (and suppress at) the declaring
    ``<name>:`` line, like speccheck's."""
    def anchor(name: str) -> int:
        pattern = re.compile(rf"^\s*{re.escape(str(name))}\s*:")
        for i, text in enumerate(lines, start=1):
            if pattern.match(text):
                return i
        return 1
    return anchor


def pod_task_mesh_env(pod, task) -> dict:
    """The one env→mesh contract shared with the admission gate
    (multi/admission.py): the task's env overlaid with the pod's tpu
    mesh env, exactly what the launch path hands the worker."""
    env = dict(task.env)
    env.update(pod.tpu.mesh_env())
    return env


def declared_chips(pod) -> int:
    """Chips the spec reserves for ONE workload: the whole gang for
    gang/topology pods, one instance's host chips otherwise.  Shared
    with the admission gate so the two enforcement points can never
    drift."""
    tpu = pod.tpu
    return (
        tpu.total_chips * max(tpu.slices, 1)
        if pod.gang or tpu.topology else tpu.chips_per_host
    )


def mesh_span_message(where: str, declared: int, total: int,
                      laid_by: str) -> str:
    """The shard-mesh reserved-vs-laid mismatch text, shared by CI and
    admission."""
    return (
        f"{where}: the spec reserves {declared} chip(s) but "
        f"{laid_by} spans {total} — "
        + ("reserved chips sit idle" if declared > total
           else "the workload cannot get the chips it lays")
    )


def fleet_slice_count(inventory, generation: str) -> Optional[int]:
    """Distinct registered slices of ``generation`` TPU hosts — the
    one formula the multi-slice admission gate sizes `tpu: slices: N`
    against (multi/admission.py).  None when the inventory holds no
    TPU hosts at all (scheduler bootstrap): sizing against an empty
    fleet would reject every multi-slice spec exactly when
    registration must not depend on fleet availability."""
    if inventory is None:
        return None
    slices = set()
    any_tpu = False
    for host in inventory.hosts():
        if not host.generation:
            continue
        any_tpu = True
        if host.generation == generation:
            slices.add(host.slice_id)
    return len(slices) if any_tpu else None


def _analyze_pod_task(
    rel: str, pod, task, builder, anchor, vocab,
    hbm_mb: int, giant_mb: float, reports_out: List[ShardReport],
) -> List[Finding]:
    from dcos_commons_tpu.specification.specs import SpecError

    tpu = pod.tpu
    line = anchor(pod.type)
    where = f"pod {pod.type!r} task {task.name!r}"
    env = pod_task_mesh_env(pod, task)
    try:
        workload = builder(env, tpu, pod, task)
    except SpecError as e:
        return [Finding(rel, line, "shard-mesh", f"{where}: {e}")]
    except Exception as e:
        # a malformed env value (VOCAB: "not-a-number") or a broken
        # profile must fail THIS pod with a suppressible, anchored
        # finding — not abort the whole analysis CLI with a traceback
        return [Finding(
            rel, line, "shard-mesh",
            f"{where}: workload profile {builder.__name__} failed: "
            f"{type(e).__name__}: {e}",
        )]
    findings: List[Finding] = []

    declared = declared_chips(pod)
    if workload.mesh.total != declared:
        findings.append(Finding(
            rel, line, "shard-mesh",
            mesh_span_message(where, declared, workload.mesh.total,
                              f"{workload.script}'s mesh"),
        ))

    leaf_reports, problems = _check_workload(workload, vocab)
    for rule, _key, message in problems:
        findings.append(Finding(rel, line, rule, f"{where}: {message}"))

    threshold = giant_mb * 1024 * 1024
    for report in leaf_reports:
        leaf = report.leaf
        if leaf.section == "params" and leaf.bytes >= threshold \
                and report.replication > 1:
            findings.append(Finding(
                rel, line, "shard-replicated-giant",
                f"{where}: {leaf.path} "
                f"({leaf.bytes / 2 ** 20:.0f} MB) is replicated "
                f"{report.replication}x across the mesh — add an "
                "fsdp/tp entry to its PartitionSpec or raise "
                "--giant-mb if intentional",
            ))

    footprint = _footprint(workload, leaf_reports)
    per_chip_mb = footprint["per_chip_mb"]
    hbm_budget = hbm_mb or GENERATION_HBM_MB.get(tpu.generation, 0)
    if hbm_budget and per_chip_mb > hbm_budget:
        findings.append(Finding(
            rel, line, "shard-hbm-overcommit",
            f"{where}: per-chip footprint {per_chip_mb:.0f} MB exceeds "
            f"{tpu.generation} HBM ({hbm_budget} MB); shard more axes "
            "or shrink the model",
        ))
    chips_per_host_used = min(tpu.chips_per_host, workload.mesh.total)
    per_host_mb = per_chip_mb * max(chips_per_host_used, 1)
    declared_mem = task.resources.memory_mb
    if declared_mem and per_host_mb > declared_mem:
        findings.append(Finding(
            rel, line, "shard-hbm-overcommit",
            f"{where}: per-host footprint {per_host_mb:.0f} MB exceeds "
            f"the declared memory: {declared_mem} MB — raise the "
            "task's memory or shard the state further",
        ))
    footprint["per_host_mb"] = round(per_host_mb, 2)
    footprint["hbm_budget_mb"] = hbm_budget
    footprint["declared_memory_mb"] = declared_mem

    reports_out.append(ShardReport(
        key=f"{rel}:{pod.type}",
        script=workload.script,
        mesh={k: v for k, v in workload.mesh.axes().items() if v > 1},
        chips=workload.mesh.total,
        footprint=footprint,
        cost=_cost_model(workload, leaf_reports, tpu.generation),
    ))
    return findings


def analyze_all(
    root: str, hbm_mb: int = 0, giant_mb: float = 256.0
) -> ShardResult:
    frameworks_dir = os.path.join(root, "frameworks")
    result = ShardResult()
    if not os.path.isdir(frameworks_dir):
        return result
    vocab = _axis_vocabulary(root)
    for name in sorted(os.listdir(frameworks_dir)):
        framework_dir = os.path.join(frameworks_dir, name)
        if not os.path.isdir(framework_dir):
            continue
        sub = analyze_framework(
            framework_dir, root, vocab, hbm_mb, giant_mb
        )
        result.findings += sub.findings
        result.suppressed += sub.suppressed
        result.files_checked += sub.files_checked
        result.reports += sub.reports
    result.findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return result


SHARD_RULES = (
    ("shard-mesh",
     "topology cannot lay a host-aligned mesh / reserved vs laid chip "
     "mismatch / idle mesh axis"),
    ("shard-divisibility",
     "a mesh axis product does not divide the dim it shards"),
    ("shard-unknown-axis",
     "a PartitionSpec axis outside the mesh-axis vocabulary"),
    ("shard-replicated-giant",
     "a giant param replicated across mesh axes (above --giant-mb)"),
    ("shard-hbm-overcommit",
     "per-chip footprint exceeds generation HBM or declared memory"),
)


def shard_rule_catalog() -> str:
    lines = ["shardcheck rules (static sharding / HBM / layout):", ""]
    for rule_id, description in SHARD_RULES:
        lines.append(f"  {rule_id}")
        lines.append(f"      {description}")
    return "\n".join(lines)
