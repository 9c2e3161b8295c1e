"""The serving mixture's dispatch plan (ops/grouped_matmul.py
``dispatch_plan``, models/moe.py ``moe_serve_ffn``) against the spelling
it replaced, kept here as the test's own plain one: a stable ``argsort``
of the assignments, a second to invert it, a ``bincount``, and
megablox's ``gmm`` (interpreted) or ``lax.ragged_dot`` over group sizes
set at their place among the whole stack's.  The plan counts where that
sorted, so the products see the same rows in the same tiles and the
result is equal BIT FOR BIT; both are held to the float32 references'
mixtures (models/reference/lfm2_moe.py, models/reference/afmoe.py)."""

import numpy as np
import pytest

TOLERANCE = 3e-5            # tests/test_lfm2_serving.py, test_afmoe_serving.py

KERNELS = ["interpret", "ragged_dot"]


def _sorted_spelling(config, routing, experts, layer, x, live, kernel):
    """``moe_serve_ffn`` as it stood before the plan."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dcos_commons_tpu.models.moe import route
    from dcos_commons_tpu.models.quantize import dequantize_weight as dq
    from dcos_commons_tpu.ops.grouped_matmul import ROW_TILE, _tiling

    def grouped_matmul(rows, stack, group_sizes, first):
        m, n_groups = rows.shape[0], group_sizes.shape[0]
        if kernel == "ragged_dot":
            held = lax.dynamic_slice_in_dim(stack, first, n_groups, axis=0)
            return lax.ragged_dot(
                rows, held.astype(rows.dtype), group_sizes,
                preferred_element_type=jnp.float32,
            ).astype(rows.dtype)
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        tile = min(m, ROW_TILE)
        padded = -(-m // tile) * tile
        lhs = jnp.pad(rows, ((0, padded - m), (0, 0)))
        sizes = lax.dynamic_update_slice_in_dim(
            jnp.zeros(stack.shape[0], jnp.int32), group_sizes, first, axis=0
        )
        out = gmm(
            lhs, stack, sizes, preferred_element_type=rows.dtype,
            tiling=(tile,) + _tiling(stack.shape[1], stack.shape[2]),
            interpret=True,
        )[:m]
        owned = jnp.arange(m, dtype=jnp.int32) < group_sizes.sum()
        return jnp.where(owned[:, None], out, 0)

    t, d = x.shape
    e, k, dt = config.n_experts, config.top_k, config.dtype
    gate_vals, expert_idx, _scores = route(config, routing, x)
    flat_expert = expert_idx.reshape(-1)
    if live is not None:
        flat_expert = jnp.where(jnp.repeat(live, k), flat_expert, e)
    order = jnp.argsort(flat_expert, stable=True)
    back = jnp.argsort(order)
    group_sizes = jnp.bincount(flat_expert, length=e + 1)[:e].astype(jnp.int32)
    counts = jnp.stack(
        [group_sizes.sum(), (group_sizes > 0).sum()]
    ).astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)

    def product(rows, name):
        w = experts[name]
        if isinstance(w, dict):
            w = dq(jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, layer, axis=0, keepdims=False
            ), w), dt)
            return grouped_matmul(rows, w, group_sizes, 0)
        return grouped_matmul(
            rows, w.reshape((-1,) + w.shape[-2:]), group_sizes, layer * e
        )

    rows = x.astype(dt)[order // k]
    gate = jax.nn.silu(product(rows, "w_gate"))
    out = product(gate * product(rows, "w_up"), "w_down")
    weight = gate_vals if live is None else jnp.where(
        live[:, None], gate_vals, 0.0
    )
    y = jnp.sum(
        out[back].reshape(t, k, d).astype(jnp.float32) * weight[:, :, None],
        axis=1,
    )
    if config.n_shared:
        h = x.astype(dt)
        hidden = jax.nn.silu(h @ dq(routing["shared_gate"], dt)) * (
            h @ dq(routing["shared_up"], dt)
        )
        y = y + (hidden @ dq(routing["shared_down"], dt)).astype(jnp.float32)
    return y.astype(x.dtype), counts


def _reference(config, routing, experts, layer, x):
    """The float32 references' mixture of the family the config is of
    (a shared expert: ``afmoe``) over ``x``, whose rows have root mean
    square one, with the norms' weights one and their eps 0 (so the norm
    in front of the mixture hands ``x`` on as it is) and the residual
    taken off again: (``y``, the routing margins).  The ``afmoe``
    mixture ends in such a norm of ``y``, which stays."""
    import jax.numpy as jnp

    from dcos_commons_tpu.models.reference import afmoe, lfm2_moe

    stack = {name: w[layer][None] for name, w in experts.items()}
    stack.update({name: w[None] for name, w in routing.items()})
    stack["mlp_norm"] = jnp.ones((1, x.shape[1]))
    items = {
        "num_experts": config.n_experts, "num_experts_per_tok": config.top_k,
    }
    if config.n_shared:
        items.update(
            rms_norm_eps=0.0, route_norm=True, route_scale=config.scaling,
            sliding_window=8,
        )
        parts = afmoe._part_fns(tuple(sorted(items.items())))
        stack["mlp_post_norm"] = jnp.ones((1, x.shape[1]))
    else:
        items.update(
            norm_eps=0.0, use_expert_bias=True, norm_topk_prob=True,
            conv_L_cache=3,
        )
        parts = lfm2_moe._part_fns(tuple(sorted(items.items())))
    y, margin = parts["moe"](stack, 0, x)
    return np.asarray(y - x), margin


def _case(name):
    """(config, this layer's routing leaves, the stacked experts, the
    layer, ``x``, ``live``) of one named case."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.moe import MoEConfig, init_moe_params
    from dcos_commons_tpu.models.quantize import quantize_weight

    t, k, e, n_layers, layer, d, f = 12, 2, 8, 1, 0, 32, 24
    live, dtype = None, jnp.float32
    lfm2 = dict(score="sigmoid", expert_bias=True)
    afmoe_like = dict(
        score="sigmoid", expert_bias=True, scaling=2.826, norm_eps=1e-20,
        n_shared=1,
    )
    family = lfm2
    if name == "all_live":
        pass
    elif name == "none_live":
        live = np.zeros(t, bool)
    elif name == "one_live_row":
        live = np.arange(t) == 7
    elif name == "one_expert_takes_all":
        k = 1
    elif name == "a_group_straddles_two_row_tiles":
        t, k, e = 64, 4, 8
    elif name == "ties_keep_expert_idx_order":
        t, k, e = 40, 3, 4
        live = np.arange(t) % 3 != 1
    elif name == "shared_expert":
        family, t, k, e = afmoe_like, 24, 8, 16
        live = np.arange(t) < 9
    elif name == "layer_two_of_three":
        n_layers, layer = 3, 2
        live = np.arange(t) % 2 == 0
    elif name == "int8_experts":
        n_layers, layer = 2, 1
        live = np.arange(t) != 3
    elif name == "a_chunk_counted_by_blocks":
        # over COUNT_BLOCK and INVERT_BY_COUNTING: blocks, and the sort
        t, k, e = 150, 4, 8
        live = np.arange(t) < 131
    elif name == "lfm2_decode_step":
        # the cells' (E, k) at their pools' slots, a few rows in use
        t, k, e, n_layers, layer = 64, 4, 64, 3, 1
        live = np.isin(np.arange(t), [2, 17, 40])
    elif name == "trinity_decode_step":
        family, t, k, e, n_layers, layer = afmoe_like, 24, 8, 128, 3, 1
        live = np.isin(np.arange(t), [0, 5, 23])
    elif name == "mixtral_decode_step":
        family, t, k, e, n_layers, layer = {}, 64, 2, 8, 3, 0
        live = np.arange(t) % 13 == 4
    elif name == "bfloat16_middle_layer":
        dtype, n_layers, layer = jnp.bfloat16, 3, 1
        live = np.arange(t) != 0
    elif name == "bfloat16_int8_experts":
        dtype, n_layers, layer = jnp.bfloat16, 3, 2
    else:
        raise KeyError(name)
    config = MoEConfig(
        d_model=d, d_ff=f, n_experts=e, top_k=k, dtype=dtype, **family
    )
    layers = jax.vmap(lambda key: init_moe_params(config, key))(
        jax.random.split(jax.random.key(3), n_layers)
    )
    experts = {name_: layers[name_] for name_ in ("w_gate", "w_up", "w_down")}
    routing = {
        name_: w[layer] for name_, w in layers.items() if name_ not in experts
    }
    if config.expert_bias:
        routing["expert_bias"] = routing["expert_bias"] * 30.0
    if name == "one_expert_takes_all":
        routing["expert_bias"] = jnp.zeros(e).at[5].set(4.0)
    if name.endswith("int8_experts"):
        experts = {name_: quantize_weight(w) for name_, w in experts.items()}
    x = jax.random.normal(jax.random.key(4), (t, d))
    # rows of root mean square one: what the references' norm hands on
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    return config, routing, experts, layer, x, (
        None if live is None else jnp.asarray(live)
    )


CASES = [
    "all_live", "none_live", "one_live_row", "one_expert_takes_all",
    "a_group_straddles_two_row_tiles", "ties_keep_expert_idx_order",
    "shared_expert", "layer_two_of_three", "int8_experts",
    "a_chunk_counted_by_blocks", "lfm2_decode_step", "trinity_decode_step",
    "mixtral_decode_step", "bfloat16_middle_layer", "bfloat16_int8_experts",
]


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("name", CASES)
def test_the_plan_gives_what_the_sort_gave_bit_for_bit(
    name, kernel, monkeypatch
):
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.moe import moe_serve_ffn, route
    from dcos_commons_tpu.models.quantize import dequantize_weight as dq
    from dcos_commons_tpu.ops import grouped_matmul as gm

    config, routing, experts, layer, x, live = _case(name)
    monkeypatch.setattr(
        gm, "grouped_matmul_kernel",
        lambda: "interpret" if kernel == "interpret" else None,
    )
    got, counts = jax.jit(
        lambda x: moe_serve_ffn(config, routing, experts, layer, x, live)
    )(x)
    want, want_counts = jax.jit(lambda x: _sorted_spelling(
        config, routing, experts, layer, x, live, kernel
    ))(x)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert counts.tolist() == want_counts.tolist()
    assert counts.dtype == jnp.int32

    _weights, chosen, _ = route(config, routing, x)
    alive = np.ones(x.shape[0], bool) if live is None else np.asarray(live)
    touched = set(np.asarray(chosen)[alive].reshape(-1).tolist())
    assert counts.tolist() == [int(alive.sum()) * config.top_k, len(touched)]
    if not config.n_shared:      # which every row goes through
        assert not np.asarray(got)[~alive].any()
    if name == "none_live":
        assert counts.tolist() == [0, 0] and not np.asarray(got).any()
    if name == "one_expert_takes_all":
        assert touched == {5}
    if name == "a_group_straddles_two_row_tiles":
        ends = np.cumsum(np.bincount(np.asarray(chosen).reshape(-1)))
        assert x.shape[0] * config.top_k == 2 * gm.ROW_TILE
        assert gm.ROW_TILE not in ends.tolist()

    # and the float32 reference's mixture of the family (softmax
    # routing has none; in bfloat16 the sorted spelling above is the one)
    if config.score != "sigmoid" or config.dtype != jnp.float32:
        return
    plain = {
        name_: dq(w, jnp.float32) for name_, w in experts.items()
    }
    ref, margin = _reference(config, routing, plain, layer, x)
    if config.n_shared:
        # the afmoe reference ends in an RMS norm of weight one
        y = np.asarray(moe_serve_ffn(
            config, routing, experts, layer, x, None
        )[0])
        y = y / np.sqrt(np.mean(y * y, -1, keepdims=True))
        assert np.abs(y - ref)[alive].max(initial=0.0) < TOLERANCE
    else:
        gap = np.abs(np.asarray(got) - ref)[alive]
        assert gap.max(initial=0.0) < TOLERANCE
    assert float(jnp.min(margin)) > 1e-6


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("t,k,e,dead", [
    (64, 4, 64, 61),        # lfm2-24b.chat's decode step: one block
    (24, 8, 128, 22),       # trinity-mini.longdoc's: 192 of 256 rows
    (64, 2, 8, 0),          # mixtral8x7b.chat's
    (5, 2, 4, 2),           # a tile of ten rows
    (512, 8, 128, 100),     # a chunk: sixteen blocks, the sort
    (512, 4, 64, 509),      # lfm2-24b.chat's, three tokens of it live
    (512, 2, 8, 512),       # mixtral8x7b.chat's, nothing live
    (300, 3, 8, 7),         # blocks that do not divide the assignments
    (130, 1, 4, 0),
    (7, 3, 1, 1),           # one expert
])
def test_the_plan_is_the_stable_sort_and_megabloxs_tile_metadata(
    t, k, e, dead, kernel, monkeypatch
):
    """``back`` is where a stable sort by expert puts each assignment
    (ties in ``expert_idx``'s own order), ``src`` its inverse, and the
    visits the kernel is handed are those megablox derived from the
    group sizes."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(
        gm, "grouped_matmul_kernel",
        lambda: "interpret" if kernel == "interpret" else None,
    )
    rng = np.random.default_rng(t * k + e)
    idx = rng.integers(0, e, (t, k)).astype(np.int32)
    live = np.ones(t, bool)
    live[rng.permutation(t)[:dead]] = False
    plan = jax.jit(lambda i, l: gm.dispatch_plan(i, l, e))(
        jnp.asarray(idx), jnp.asarray(live) if dead else None
    )
    keys = np.where(live[:, None], idx, e).reshape(-1)
    order = np.argsort(keys, kind="stable")
    a = t * k
    assert np.array_equal(np.asarray(plan.back)[order], np.arange(a))
    assert np.array_equal(np.asarray(plan.src)[:a], order // k)
    sizes = np.bincount(keys, minlength=e + 1)[:e]
    assert np.array_equal(np.asarray(plan.group_sizes), sizes)
    assert plan.counts.tolist() == [int(sizes.sum()), int((sizes > 0).sum())]
    if kernel != "interpret":
        assert plan.tiles is None and plan.src.shape == (a,)
        return
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    tile = min(a, gm.ROW_TILE)
    m = -(-a // tile) * tile
    assert plan.src.shape == (m,)
    (offsets, group_ids, m_tile_ids), visits = make_group_metadata(
        group_sizes=jnp.asarray(sizes, jnp.int32), m=m, tm=tile,
        start_group=jnp.int32(0), num_nonzero_groups=e,
        visit_empty_groups=False,
    )
    n = int(visits)
    tiles = plan.tiles
    assert int(tiles.num_tiles) == n
    assert np.array_equal(tiles.group_offsets, offsets)
    assert np.array_equal(tiles.group_ids[:n], group_ids[:n])
    assert np.array_equal(tiles.m_tile_ids[:n], m_tile_ids[:n])
    # past the last visit nothing is read, and nothing points outside
    assert tiles.group_ids.shape == (m // tile + e - 1,)
    assert 0 <= int(tiles.group_ids.min()) and int(tiles.group_ids.max()) < e
    assert 0 <= int(tiles.m_tile_ids.min())
    assert int(tiles.m_tile_ids.max()) < m // tile
