"""The grouped-query decode kernel (ops/paged_decode.py) against the
gather path it replaces on a TPU: interpreted here, at toy size.

The kernel reads, for each row, the pages ``tables[s, :pos // P + 1]``
in place and keeps an online softmax in float32; the gather path copies
every row's whole table into a dense buffer and runs a masked softmax
over all of it (models/decode.py ``paged_decode_step``).  Same
arithmetic, so the same numbers to rounding.
"""

import dataclasses

import numpy as np
import pytest

P_TOK, TABLE, PAGES = 4, 132, 400       # a row holds at most 528 positions
HEAD_DIM = 16
# the pages a step of the walk takes at these sizes (``step_pages``:
# pages this small stop at the most a step may take)
BLOCK = 64
# query heads a KV head, and the (query, KV) heads that make them: 8
# over 4 KV heads is ``trinity-mini``'s grouping, 1 is EVA's
SHAPES = {4: (8, 2), 1: (8, 8), 8: (32, 4)}


def _last_of(pages):
    """The last position of a row of that many pages."""
    return pages * P_TOK - 1


# rows of one pool, by what they exercise: (positions, idle rows).  A
# case that names idle rows tells the kernel so (``live``) and gets
# zeros for them; without the word every row is read, an empty slot's
# trash page too.
ROWS = {
    "unequal_lengths": ([5, 9, 2, 13, 22], ()),
    "an_idle_row": ([7, 0, 3], (1,)),
    "a_pages_last_entry": ([P_TOK - 1, 2 * P_TOK - 1, 10], ()),
    "a_pages_first_entry": ([P_TOK, 3 * P_TOK, 1], ()),
    "the_tables_last_entry": ([TABLE * P_TOK - 1, 6], ()),
    "one_page_and_whole_blocks": (
        [_last_of(1), _last_of(BLOCK), _last_of(2 * BLOCK)], ()
    ),
    "a_block_and_one_page": (
        [_last_of(BLOCK + 1), BLOCK * P_TOK, _last_of(2 * BLOCK + 1)], ()
    ),
    "not_a_multiple_of_the_block": (
        [_last_of(BLOCK + 3) - 2, _last_of(BLOCK - 1), 2 * BLOCK * P_TOK + 5],
        (),
    ),
    "idle_before_between_and_behind": (
        [0, 0, 11, 0, _last_of(BLOCK + 2), 3, 0, 0], (0, 1, 3, 6, 7)
    ),
    "every_slot_idle": ([0, 0, 0], (0, 1, 2)),
}


def _tables(positions, idle, table=TABLE):
    """Each row's pages its own, in no order; an idle row's all zero
    (the trash page), entries past the row's last page zero too."""
    rng = np.random.RandomState(len(positions))
    free = list(rng.permutation(np.arange(1, PAGES)))
    tables = np.zeros((len(positions), table), np.int32)
    for s, pos in enumerate(positions):
        if s not in idle:
            for j in range(pos // P_TOK + 1):
                tables[s, j] = free.pop()
    return tables


def _gather_attention(q, arena_k, arena_v, tables, pos):
    """``paged_decode_step``'s gather path, one layer."""
    import jax
    import jax.numpy as jnp

    b, h, hd = q.shape
    kv = arena_k.shape[2]
    length = tables.shape[1] * P_TOK
    k_all = arena_k[tables].reshape(b, length, kv, hd)
    v_all = arena_v[tables].reshape(b, length, kv, hd)
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, kv, h // kv, hd)
    scores = jnp.einsum("bkrd,blkd->bkrl", qg, k_all.astype(jnp.float32))
    valid = jnp.arange(length)[None, None, None, :] <= pos[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=-1)
    return jnp.einsum(
        "bkrl,blkd->bkrd", probs, v_all.astype(jnp.float32)
    ).astype(q.dtype).reshape(b, h, hd)


def _operands(rows, reps, dtype):
    """(q, arena_k, arena_v) of a pool of ``rows`` slots."""
    import jax
    import jax.numpy as jnp

    heads, kv = SHAPES[reps]
    keys = jax.random.split(jax.random.key(7), 3)
    shape = (PAGES, P_TOK, kv, HEAD_DIM)
    return (
        jax.random.normal(
            keys[2], (rows, heads, HEAD_DIM), jnp.float32
        ).astype(dtype),
        jax.random.normal(keys[0], shape, jnp.float32).astype(dtype),
        jax.random.normal(keys[1], shape, jnp.float32).astype(dtype),
    )


def _tolerance(dtype, want):
    """float32: the sums run in another order; bfloat16: both round one
    float32 result, so they differ by at most one step of the format."""
    return 2e-5 if dtype == "float32" else 2.0 ** -7 * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reps", sorted(SHAPES))
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_the_kernel_reads_what_the_gather_path_reads(rows, reps, dtype):
    import jax.numpy as jnp

    from dcos_commons_tpu.ops.paged_decode import paged_decode_attention

    positions, idle = ROWS[rows]
    q, arena_k, arena_v = _operands(len(positions), reps, dtype)
    tables = jnp.asarray(_tables(positions, idle))
    pos = jnp.asarray(positions, jnp.int32)
    tells = "idle" in rows
    got = paged_decode_attention(
        q, arena_k, arena_v, tables, pos,
        tables[:, 0] > 0 if tells else None, scale=HEAD_DIM ** -0.5,
        interpret=True,
    )
    want = _gather_attention(q, arena_k, arena_v, tables, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if tells:
        # a slot that decodes nothing is not read: zeros, not the trash
        # page's entry
        assert not got[list(idle)].any()
        live = [s for s in range(len(positions)) if s not in idle]
        got, want = got[live], want[live]
    if len(got):
        assert float(np.abs(got - want).max()) <= _tolerance(dtype, want)


def test_the_step_is_a_block_of_pages_within_its_vmem_budget():
    """``BLOCK`` above is what the toy sizes give; a step of the cells'
    pages holds two blocks each of K and V inside the budget."""
    from dcos_commons_tpu.ops import paged_decode

    for _heads, kv in SHAPES.values():
        assert paged_decode.step_pages(P_TOK, kv, HEAD_DIM, 4) == BLOCK
    # page, KV heads, lanes, itemsize: evabyte, mixtral, trinity-mini
    for sizes, pages in [
        ((16, 32, 128, 2), 8), ((16, 8, 128, 2), 32), ((16, 4, 128, 2), 64),
    ]:
        assert paged_decode.step_pages(*sizes) == pages
        assert 4 * pages * np.prod(sizes) <= paged_decode.STEP_VMEM_BYTES
    # a page too large for the budget still walks, one page a step
    assert paged_decode.step_pages(64, 32, 256, 4) == 1


# -- two regions, a lower bound: the walk itself -------------------------

# a row of the walk: (pages of the first region, entries of it that
# count, pages behind it, entries of those that count, the first
# region's first entry that counts); None: an idle slot
WALKS = {
    # EVA's ring, then its summaries: the ring ends inside the first
    # block, at a page's edge and inside a page
    "the_first_region_ends_inside_a_block": [
        (3, 3 * P_TOK, 7, 7 * P_TOK - 1, 0),
        (5, 5 * P_TOK - 3, BLOCK, BLOCK * P_TOK, 0),
        (1, 1, 1, 2, 0),
    ],
    "the_first_region_ends_with_a_block": [
        (BLOCK, BLOCK * P_TOK, 2, 5, 0),
        (BLOCK, BLOCK * P_TOK - 1, BLOCK, BLOCK * P_TOK - 2, 0),
    ],
    "no_second_region": [(BLOCK + 2, (BLOCK + 2) * P_TOK - 2, 0, 0, 0)],
    # a window: the lower bound in the first block's second page, in
    # its first page, and in the second block
    "a_lower_bound_in_the_first_blocks_second_page": [
        (BLOCK + 1, (BLOCK + 1) * P_TOK - 1, 0, 0, P_TOK + 2),
        (4, 4 * P_TOK, 0, 0, P_TOK),
        (2, 2 * P_TOK - 1, 0, 0, 2 * P_TOK - 2),
    ],
    "a_lower_bound_elsewhere": [
        (3, 10, 0, 0, 1),
        (2 * BLOCK, 2 * BLOCK * P_TOK, 0, 0, BLOCK * P_TOK + 1),
        (1, P_TOK, 0, 0, P_TOK - 1),
    ],
    "idle_slots_among_two_region_rows": [
        None, (2, 7, 3, 9, 0), None, None, (BLOCK, 30, 1, 1, 0), None,
    ],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reps", sorted(SHAPES))
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_the_walk_keeps_both_regions_bounds_entry_for_entry(
    walk, reps, dtype
):
    """``page_walk_attention`` against a softmax, row by row, over the
    entries its operands say count: the first region's from
    ``lower_first`` up to ``bound_first``, then the second's up to
    ``bound_rest``; a list with a lower bound is a ring read from
    ``ring_first`` on; idle slots give zeros."""
    import jax.numpy as jnp

    from dcos_commons_tpu.ops.paged_decode import page_walk_attention

    rows = WALKS[walk]
    lower = "lower_bound" in walk
    q, arena_k, arena_v = _operands(len(rows), reps, dtype)
    heads, kv = SHAPES[reps]
    free = list(np.random.RandomState(3).permutation(np.arange(1, PAGES)))
    ids = np.zeros((len(rows), TABLE), np.int32)
    per_row = np.zeros((6, len(rows)), np.int32)
    listed = {}
    for s, row in enumerate(rows):
        if row is not None:
            n_first, bound_first, n_rest, bound_rest, lower_first = row
            # a window's list is a ring: the row's pages begin near the
            # table's end and go on from its start
            turn = (TABLE - 2 + 5 * s) % TABLE if lower else 0
            listed[s] = [free.pop() for _ in range(n_first + n_rest)]
            for j, at in enumerate(listed[s]):
                ids[s, (turn + j) % TABLE] = at
            per_row[:, s] = (
                n_first, n_first + n_rest, bound_first, bound_rest,
                lower_first, turn,
            )
    n_first, n_pages, bound_first, bound_rest, lower_first, turn = (
        jnp.asarray(a) for a in per_row
    )
    got = page_walk_attention(
        q, arena_k, arena_v, jnp.asarray(ids), n_first, n_pages,
        bound_first, bound_rest, scale=HEAD_DIM ** -0.5, name="walk",
        interpret=True, lower_first=lower_first if lower else None,
        ring_first=turn if lower else None,
        live=jnp.asarray([row is not None for row in rows]),
    )
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    k, v, qf = (np.asarray(a, np.float32) for a in (arena_k, arena_v, q))
    for s, row in enumerate(rows):
        if row is None:
            assert not got[s].any()
            continue
        n_first, bound_first, n_rest, bound_rest, lower_first = row
        first, rest = listed[s][:n_first], listed[s][n_first:]
        keys = np.concatenate([
            k[first].reshape(-1, kv, HEAD_DIM)[lower_first:bound_first],
            k[rest].reshape(-1, kv, HEAD_DIM)[:bound_rest],
        ])
        values = np.concatenate([
            v[first].reshape(-1, kv, HEAD_DIM)[lower_first:bound_first],
            v[rest].reshape(-1, kv, HEAD_DIM)[:bound_rest],
        ])
        for head in range(heads):
            g = head // reps
            score = keys[:, g] @ qf[s, head] * HEAD_DIM ** -0.5
            weight = np.exp(score - score.max())
            want = (weight / weight.sum()) @ values[:, g]
            assert float(np.abs(got[s, head] - want).max()) <= _tolerance(
                dtype, want
            ), (s, head)


# -- inside the decode step ---------------------------------------------


@pytest.fixture(scope="module", params=[4, 1], ids=["reps4", "reps1"])
def model(request):
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig, init_params

    config = TransformerConfig(
        vocab=64, d_model=32, n_layers=3, n_heads=8,
        n_kv_heads=8 // request.param, d_ff=96, max_seq=64,
        dtype=jnp.float32, remat=False,
    )
    return config, init_params(config, jax.random.key(3))


STEP_TABLE = 6                          # a row of the step: 24 positions


def _step_case(config, dtype):
    """An arena full of earlier keys and values, rows of unequal
    length, one idle, one on a page's first entry, one at the table's
    last."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models.decode import init_paged_kv_cache

    zero = init_paged_kv_cache(config, PAGES, P_TOK, "native")
    keys = jax.random.split(jax.random.key(11), len(zero))
    cache = {
        name: jax.random.normal(key, arr.shape, jnp.float32).astype(dtype)
        for key, (name, arr) in zip(keys, sorted(zero.items()))
    }
    positions, idle = [5, 0, P_TOK, STEP_TABLE * P_TOK - 1, 11], (1,)
    token = jnp.asarray([7, 0, 40, 3, 21], jnp.int32)
    return cache, (
        token, jnp.asarray(positions, jnp.int32),
        jnp.asarray(_tables(positions, idle, STEP_TABLE)),
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_decode_step_with_the_kernel_equals_the_gather_path(
    model, dtype, monkeypatch
):
    """The live rows' logits and the returned arena to rounding through
    three layers; the first layer's arena bit for bit (its ``kv_write``
    scatter runs before any attention has differed).  The idle slot is
    not read by the kernel."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import decode

    config, params = model
    config = dataclasses.replace(config, dtype=jnp.dtype(dtype))
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    cache, args = _step_case(config, dtype)
    step = lambda: jax.jit(  # noqa: E731 — traced anew under each rule
        lambda cache, *args: decode.paged_decode_step(
            config, params, cache, *args
        )
    )(cache, *args)
    want_logits, want_cache, _ = step()
    monkeypatch.setattr(
        decode, "decode_attention_kernel", lambda config, cache: "interpret"
    )
    logits, new_cache, _ = step()
    live = np.asarray(args[1]) > 0
    got, want = np.asarray(logits)[live], np.asarray(want_logits)[live]
    if dtype == "float32":
        assert float(np.abs(got - want).max()) < 2e-5
        for name in cache:
            new, old = np.asarray(new_cache[name]), np.asarray(want_cache[name])
            np.testing.assert_array_equal(new[0], old[0])
            # but for each layer's trash page, where the idle slot
            # writes what its zeros of an attention came to
            assert float(np.abs(new - old)[:, 1:].max()) < 2e-5
    else:
        # three layers of bfloat16 roundings that fall differently
        assert float(np.abs(got - want).max()) < 0.05 * np.abs(want).max()


def test_the_rule_keeps_the_gather_path_where_the_kernel_cannot_run(
    model, monkeypatch
):
    """On a TPU with a native arena and no mesh: the kernel.  Off a
    TPU, over a quantized arena, or under an ambient mesh of several
    devices: the gather path."""
    import jax

    from dcos_commons_tpu.models import decode
    from dcos_commons_tpu.models.decode import init_paged_kv_cache
    from dcos_commons_tpu.parallel.mesh import MeshSpec, make_mesh

    config, _params = model
    native = init_paged_kv_cache(config, PAGES, P_TOK, "native")
    int8 = init_paged_kv_cache(config, PAGES, P_TOK, "int8")
    assert decode.decode_attention_kernel(config, native) is None   # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode.decode_attention_kernel(config, native) == "compiled"
    assert decode.decode_attention_kernel(config, int8) is None
    mesh = make_mesh(MeshSpec(tp=2), jax.devices()[:2])
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert decode.decode_attention_kernel(config, native) is None


def test_the_step_of_the_walk_is_stated_where_the_kernel_runs(
    model, monkeypatch
):
    """``/stats`` ``model.decode_attention_step``: by the kernel's name
    in a trace, the pages a step takes and the form of its products,
    both read off the arena's shapes; nothing on the gather path."""
    import jax

    from dcos_commons_tpu.models import decode
    from dcos_commons_tpu.models.decode import init_paged_kv_cache
    from dcos_commons_tpu.ops import paged_decode

    config, _params = model
    native = init_paged_kv_cache(config, PAGES, P_TOK, "native")
    assert decode.decode_attention_step(config, native) == {}      # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    page, kv, lanes = native["k"].shape[-3:]
    assert decode.decode_attention_step(config, native) == {
        "paged_decode_attention": {
            "pages": paged_decode.step_pages(page, kv, lanes, 4),
            "scores": "mxu_masked_heads",
        },
    }
    int8 = init_paged_kv_cache(config, PAGES, P_TOK, "int8")
    assert decode.decode_attention_step(config, int8) == {}


def test_a_pool_laid_over_a_tp_mesh_decodes_through_the_gather_path(
    model, monkeypatch
):
    """``serve_gang_worker.py`` lays the arena's KV heads over its tp
    mesh (``cache_sharding``).  A bare ``pallas_call`` under that
    multi-device jit would raise on a TPU; the pool makes the mesh
    ambient while its decode step is traced, so the rule sees it and
    the step takes the gather path."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dcos_commons_tpu.models import decode
    from dcos_commons_tpu.parallel.mesh import MeshSpec, make_mesh
    from dcos_commons_tpu.serve.pool import PagedPoolModel

    config, params = model
    mesh = make_mesh(MeshSpec(tp=2), jax.devices()[:2])
    rule, chosen = decode.decode_attention_kernel, []

    def recording(config, cache):
        chosen.append(rule(config, cache))
        return chosen[-1]

    monkeypatch.setattr(decode, "decode_attention_kernel", recording)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with mesh:
        pool = PagedPoolModel(
            config, params, 3, STEP_TABLE * P_TOK, P_TOK, PAGES, 4,
            cache_sharding=NamedSharding(
                mesh, P(None, None, None, "tp", None)
            ),
        )
        pool.warm(ahead=False)  # as the gang worker warms it
    assert chosen == [None]
