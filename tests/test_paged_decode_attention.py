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

P_TOK, TABLE, PAGES = 4, 6, 40          # a row holds at most 24 positions
HEADS, HEAD_DIM = 8, 16

# rows of one pool, by what they exercise: (positions, idle rows)
ROWS = {
    "unequal_lengths": ([5, 9, 2, 13, 22], ()),
    "an_idle_row": ([7, 0, 3], (1,)),
    "a_pages_last_entry": ([P_TOK - 1, 2 * P_TOK - 1, 10], ()),
    "a_pages_first_entry": ([P_TOK, 3 * P_TOK, 1], ()),
    "the_tables_last_entry": ([TABLE * P_TOK - 1, 6], ()),
}


def _tables(positions, idle):
    """Each row's pages its own, in no order; an idle row's all zero
    (the trash page), entries past the row's last page zero too."""
    rng = np.random.RandomState(len(positions))
    free = list(rng.permutation(np.arange(1, PAGES)))
    tables = np.zeros((len(positions), TABLE), np.int32)
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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reps", [4, 1])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_the_kernel_reads_what_the_gather_path_reads(rows, reps, dtype):
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.ops.paged_decode import paged_decode_attention

    positions, idle = ROWS[rows]
    kv = HEADS // reps
    keys = jax.random.split(jax.random.key(7), 3)
    shape = (PAGES, P_TOK, kv, HEAD_DIM)
    arena_k = jax.random.normal(keys[0], shape, jnp.float32).astype(dtype)
    arena_v = jax.random.normal(keys[1], shape, jnp.float32).astype(dtype)
    q = jax.random.normal(
        keys[2], (len(positions), HEADS, HEAD_DIM), jnp.float32
    ).astype(dtype)
    tables = jnp.asarray(_tables(positions, idle))
    pos = jnp.asarray(positions, jnp.int32)
    got = paged_decode_attention(
        q, arena_k, arena_v, tables, pos, scale=HEAD_DIM ** -0.5,
        interpret=True,
    )
    want = _gather_attention(q, arena_k, arena_v, tables, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # float32: the sums run in another order; bfloat16: both round one
    # float32 result, so they differ by at most one step of the format
    tolerance = 2e-5 if dtype == "float32" else 2.0 ** -7 * np.abs(want).max()
    assert float(np.abs(got - want).max()) <= tolerance


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
    positions, idle = [5, 0, P_TOK, TABLE * P_TOK - 1, 11], (1,)
    token = jnp.asarray([7, 0, 40, 3, 21], jnp.int32)
    return cache, (
        token, jnp.asarray(positions, jnp.int32),
        jnp.asarray(_tables(positions, idle)),
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_decode_step_with_the_kernel_equals_the_gather_path(
    model, dtype, monkeypatch
):
    """Logits and the returned arena to rounding through three layers;
    the first layer's arena bit for bit (its ``kv_write`` scatter runs
    before any attention has differed)."""
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
            assert float(np.abs(new - old).max()) < 2e-5
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
            config, params, 3, TABLE * P_TOK, P_TOK, PAGES, 4,
            cache_sharding=NamedSharding(
                mesh, P(None, None, None, "tp", None)
            ),
        )
        pool.warm(ahead=False)  # as the gang worker warms it
    assert chosen == [None]
