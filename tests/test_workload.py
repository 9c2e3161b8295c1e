"""Workload library tests on the virtual 8-device CPU mesh.

Kernel correctness against jnp oracles (pallas interpret mode), ring
attention against dense attention, and the full sharded train step
compiling + running over a dp/fsdp/tp/sp mesh — the multi-chip path
the driver's dryrun exercises.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P
from dcos_commons_tpu.parallel.compat import shard_map

from dcos_commons_tpu.models import (
    MlpConfig,
    TransformerConfig,
    init_params,
    loss_fn,
    make_train_step,
    forward,
    mlp_init,
    mlp_train_step,
)
from dcos_commons_tpu.ops.attention import flash_attention
from dcos_commons_tpu.ops.rmsnorm import rms_norm
from dcos_commons_tpu.parallel.mesh import MeshSpec, make_mesh
from dcos_commons_tpu.parallel.ring import reference_attention, ring_attention
from dcos_commons_tpu.utils import (
    param_count,
    restore_checkpoint,
    save_checkpoint,
    synthetic_mnist,
    synthetic_tokens,
)


def test_eight_devices_available():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"


# -- kernels ----------------------------------------------------------


def test_flash_attention_matches_reference():
    key = jax.random.key(0)
    q, k, v = (
        jax.random.normal(k_, (2, 4, 256, 64), jnp.float32)
        for k_ in jax.random.split(key, 3)
    )
    oracle = reference_attention(q, k, v, causal=True)
    kernel = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(kernel), np.asarray(oracle), atol=2e-5, rtol=2e-5
    )
    # non-causal too
    oracle_nc = reference_attention(q, k, v, causal=False)
    kernel_nc = flash_attention(q, k, v, causal=False, interpret=True)
    np.testing.assert_allclose(
        np.asarray(kernel_nc), np.asarray(oracle_nc), atol=2e-5, rtol=2e-5
    )


def test_flash_attention_backward_matches_reference_vjp():
    """The FA2 two-kernel backward (dq + dk/dv over the saved
    logsumexp) must match the dense reference VJP."""
    key = jax.random.key(2)
    q, k, v = (
        jax.random.normal(k_, (2, 3, 256, 64), jnp.float32)
        for k_ in jax.random.split(key, 3)
    )
    for causal in (True, False):
        def loss_kernel(q, k, v):
            return (
                flash_attention(
                    q, k, v, causal=causal, interpret=True,
                    force_pallas=True,
                ) ** 2
            ).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=causal) ** 2).sum()

        grads_kernel = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, got, want in zip("qkv", grads_kernel, grads_ref):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=5e-5, rtol=5e-5,
                err_msg=f"d{name} causal={causal}",
            )


def test_flash_attention_ragged_falls_back():
    key = jax.random.key(1)
    q, k, v = (
        jax.random.normal(k_, (1, 2, 100, 32), jnp.float32)
        for k_ in jax.random.split(key, 3)
    )
    out = flash_attention(q, k, v, causal=True)  # 100 % 128 != 0
    oracle = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)


def test_rms_norm_matches_reference():
    x = jax.random.normal(jax.random.key(2), (512, 128), jnp.float32)
    w = jax.random.normal(jax.random.key(3), (128,), jnp.float32)
    kernel = rms_norm(x, w, interpret=True, block_rows=256)
    x32 = x.astype(jnp.float32)
    oracle = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6
    ) * w
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(oracle),
                               atol=1e-5, rtol=1e-5)


# -- ring attention ---------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh(MeshSpec(sp=8))
    key = jax.random.key(4)
    # global sequence 256 = 8 chunks of 32
    q, k, v = (
        jax.random.normal(k_, (2, 4, 256, 32), jnp.float32)
        for k_ in jax.random.split(key, 3)
    )
    oracle = reference_attention(q, k, v, causal=causal)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal,
                          axis_size=8),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)


# -- transformer ------------------------------------------------------


SMALL = TransformerConfig(
    vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq=64, dtype=jnp.float32, remat=False,
)


def test_transformer_forward_shapes():
    params = init_params(SMALL, jax.random.key(0))
    tokens, targets = synthetic_tokens(jax.random.key(1), 2, 32, SMALL.vocab)
    logits = forward(SMALL, params, tokens)
    assert logits.shape == (2, 32, SMALL.vocab)
    assert logits.dtype == jnp.float32
    assert param_count(params) > 0


def test_transformer_causality():
    """Changing a future token must not change past logits."""
    params = init_params(SMALL, jax.random.key(0))
    tokens, _ = synthetic_tokens(jax.random.key(1), 1, 32, SMALL.vocab)
    logits1 = forward(SMALL, params, tokens)
    perturbed = tokens.at[0, -1].set((tokens[0, -1] + 1) % SMALL.vocab)
    logits2 = forward(SMALL, params, perturbed)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :-1]), np.asarray(logits2[0, :-1]),
        atol=1e-5, rtol=1e-5,
    )
    assert not np.allclose(np.asarray(logits1[0, -1]), np.asarray(logits2[0, -1]))


def test_transformer_training_reduces_loss():
    params = init_params(SMALL, jax.random.key(0))
    optimizer = optax.adam(1e-2)
    opt_state = optimizer.init(params)
    step = make_train_step(SMALL, optimizer)
    tokens, targets = synthetic_tokens(jax.random.key(1), 4, 32, SMALL.vocab)
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


def test_transformer_sharded_train_step():
    """The multi-chip path: dp=2 x fsdp=2 x tp=2 mesh, full train step."""
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    config = TransformerConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq=32, dtype=jnp.float32, remat=True,
    )
    optimizer = optax.adam(1e-3)
    with mesh:
        params = init_params(config, jax.random.key(0))
        opt_state = optimizer.init(params)
        step = make_train_step(config, optimizer, mesh=mesh, donate=False)
        tokens, targets = synthetic_tokens(jax.random.key(1), 8, 32, config.vocab)
        params2, opt_state2, loss = step(params, opt_state, tokens, targets)
        assert jnp.isfinite(loss)
        # sharded result must equal the single-device result
        step_local = make_train_step(config, optimizer, donate=False)
        _, _, loss_local = step_local(params, opt_state, tokens, targets)
        np.testing.assert_allclose(float(loss), float(loss_local),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "spec", [MeshSpec(dp=4, tp=2), MeshSpec(dp=8), MeshSpec(dp=2, fsdp=2, tp=2)]
)
def test_sharded_train_step_runs_kernels_per_shard(spec, monkeypatch):
    """Under a mesh the Pallas kernels (interpreted here) run through
    shard_map on each device's batch/head shard; the step must still
    equal the single-device step — including the norm weight's
    gradient, which shard_map has to sum over the batch shards."""
    from dcos_commons_tpu.models import transformer as tmod

    monkeypatch.setattr(
        tmod, "flash_attention",
        functools.partial(flash_attention, interpret=True),
    )
    monkeypatch.setattr(
        tmod, "rms_norm",
        functools.partial(rms_norm, interpret=True, block_rows=64),
    )
    config = TransformerConfig(
        vocab=64, d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=128, max_seq=128, dtype=jnp.float32,
    )
    params = init_params(config, jax.random.key(0))
    optimizer = optax.sgd(0.1)
    tokens, targets = synthetic_tokens(jax.random.key(1), 8, 128, 64)

    def one_step(mesh):
        step = make_train_step(config, optimizer, mesh=mesh, donate=False)
        state = optimizer.init(params)
        if mesh is None:
            return step(params, state, tokens, targets)
        with mesh:
            lowered = step.lower(params, state, tokens, targets)
            # the kernels sit in shard_map bodies, not in the GSPMD part
            assert "sdy.manual_computation" in lowered.as_text()
            return step(params, state, tokens, targets)

    want_params, _, want_loss = one_step(None)
    got_params, _, got_loss = one_step(make_mesh(spec))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for got, want in zip(
        jax.tree.leaves(got_params), jax.tree.leaves(want_params)
    ):
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_transformer_ring_attention_end_to_end():
    """sp=4: forward with ring attention == unsharded forward."""
    mesh = make_mesh(MeshSpec(sp=4, tp=2))
    config = TransformerConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq=64, dtype=jnp.float32, remat=False,
    )
    ring_config = TransformerConfig(
        **{**config.__dict__, "use_ring_attention": True}
    )
    params = init_params(config, jax.random.key(0))
    tokens, targets = synthetic_tokens(jax.random.key(1), 2, 64, config.vocab)
    oracle = loss_fn(config, params, tokens, targets)

    def body(params, tokens, targets):
        # per-chunk mean -> global mean (equal-sized chunks)
        local = loss_fn(ring_config, params, tokens, targets)
        return jax.lax.pmean(local, "sp")

    with mesh:
        ring_loss = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(None, "sp"), P(None, "sp")),
            out_specs=P(),
            check_vma=False,
        )
        loss = jax.jit(ring_loss)(params, tokens, targets)
    np.testing.assert_allclose(float(loss), float(oracle), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    """All-to-all sequence parallelism: sp=4 Ulysses == dense oracle
    (the second long-context recipe next to the ring)."""
    from dcos_commons_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh(MeshSpec(sp=4))
    key = jax.random.key(7)
    # 8 heads over sp=4 -> 2 heads/device; global sequence 256
    q, k, v = (
        jax.random.normal(k_, (2, 8, 256, 32), jnp.float32)
        for k_ in jax.random.split(key, 3)
    )
    oracle = reference_attention(q, k, v, causal=causal)
    uly = shard_map(
        functools.partial(
            ulysses_attention, axis_name="sp", causal=causal,
            block_q=64, block_k=64, axis_size=4,
        ),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
        check_vma=False,
    )
    out = uly(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from dcos_commons_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh(MeshSpec(sp=4))
    q = jnp.zeros((1, 6, 64, 8), jnp.float32)  # 6 heads % 4 != 0
    with pytest.raises(Exception, match="divisible"):
        shard_map(
            functools.partial(ulysses_attention, axis_name="sp",
                              axis_size=4),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )(q, q, q)


def test_transformer_ulysses_attention_end_to_end():
    """sp=4: forward with Ulysses attention == unsharded forward, and
    ring == ulysses on the same params (both recipes interchangeable
    behind TransformerConfig.sp_axis)."""
    mesh = make_mesh(MeshSpec(sp=4, tp=2))
    config = TransformerConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq=64, dtype=jnp.float32, remat=False,
    )
    uly_config = TransformerConfig(
        **{**config.__dict__, "use_ulysses_attention": True}
    )
    params = init_params(config, jax.random.key(0))
    tokens, targets = synthetic_tokens(jax.random.key(1), 2, 64, config.vocab)
    oracle = loss_fn(config, params, tokens, targets)

    def body(params, tokens, targets):
        local = loss_fn(uly_config, params, tokens, targets)
        return jax.lax.pmean(local, "sp")

    with mesh:
        uly_loss = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(None, "sp"), P(None, "sp")),
            out_specs=P(),
            check_vma=False,
        )
        loss = jax.jit(uly_loss)(params, tokens, targets)
    np.testing.assert_allclose(float(loss), float(oracle), atol=1e-4, rtol=1e-4)


def test_config_rejects_both_sp_recipes():
    with pytest.raises(ValueError, match="ONE sequence-parallel"):
        TransformerConfig(use_ring_attention=True,
                          use_ulysses_attention=True)


# -- MoE flagship variant --------------------------------------------


MOE_CFG = TransformerConfig(
    vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
    d_ff=128, max_seq=64, dtype=jnp.float32, remat=False,
    n_experts=4, moe_top_k=2,
)


def test_moe_transformer_trains():
    """n_experts > 0: every layer's FFN is a routed expert mixture;
    the train step moves the loss and grads reach router + experts."""
    import optax

    params = init_params(MOE_CFG, jax.random.key(0))
    assert params["layers"]["router"].shape == (2, 64, 4)
    assert params["layers"]["w_gate"].shape == (2, 4, 64, 128)
    tokens, targets = synthetic_tokens(jax.random.key(1), 4, 64, 128)
    optimizer = optax.adam(1e-2)
    step = make_train_step(MOE_CFG, optimizer, donate=False)
    opt_state = optimizer.init(params)
    p, o, loss0 = step(params, opt_state, tokens, targets)
    for _ in range(20):
        p, o, loss = step(p, o, tokens, targets)
    assert jnp.isfinite(loss) and float(loss) < float(loss0)
    router_delta = jnp.abs(
        p["layers"]["router"] - params["layers"]["router"]
    ).max()
    assert float(router_delta) > 0  # the router actually learns


def test_moe_transformer_sharded_train_step():
    """The MoE flagship under a dp x ep mesh: expert params shard over
    ep and the jitted (GSPMD) step runs — the jit-native counterpart
    of the dryrun's explicit shard_map all_to_all path."""
    import optax

    mesh = make_mesh(MeshSpec(dp=2, ep=4))
    optimizer = optax.adam(1e-3)
    with mesh:
        params = init_params(MOE_CFG, jax.random.key(0))
        opt_state = optimizer.init(params)
        step = make_train_step(MOE_CFG, optimizer, mesh=mesh, donate=False)
        tokens, targets = synthetic_tokens(jax.random.key(1), 4, 64, 128)
        p, o, loss = step(params, opt_state, tokens, targets)
        loss.block_until_ready()
    assert bool(jnp.isfinite(loss))
    # expert weights really live sharded over ep
    sharding = p["layers"]["w_gate"].sharding
    assert "ep" in (sharding.spec[1] or ())


def test_moe_generate_matches_forward_chain():
    """KV-cache decode works for the MoE variant too: decode routes
    DROP-FREE, so greedy generate equals argmax-chained full forwards
    whenever the forward side is also in its drop-free regime (the
    capacity factor here guarantees that; with training-style capacity
    pressure, dropped tokens make forwards differ from ANY drop-free
    server by construction).  Checked across several seeds — routing
    equivalence must not be seed luck."""
    from dcos_commons_tpu.models import generate

    cfg = TransformerConfig(
        **{**MOE_CFG.__dict__, "moe_capacity_factor": 8.0}
    )
    for seed in range(5):
        params = init_params(cfg, jax.random.key(seed))
        prompt, _ = synthetic_tokens(
            jax.random.key(100 + seed), 2, 6, cfg.vocab
        )
        out = generate(cfg, params, prompt, max_new_tokens=4)
        seq = prompt
        for i in range(4):
            nxt = jnp.argmax(
                forward(cfg, params, seq)[:, -1], axis=-1
            ).astype(jnp.int32)
            np.testing.assert_array_equal(
                np.asarray(out[:, i]), np.asarray(nxt),
                err_msg=f"moe decode divergence seed {seed} step {i}",
            )
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


def test_moe_generate_with_int8_kv_cache():
    """The int8 KV cache composes with the MoE variant (the quantized
    path is FFN-agnostic): generation runs and closely tracks the
    exact cache."""
    from dcos_commons_tpu.models import generate

    cfg = TransformerConfig(
        **{**MOE_CFG.__dict__, "moe_capacity_factor": 8.0}
    )
    params = init_params(cfg, jax.random.key(0))
    prompt, _ = synthetic_tokens(jax.random.key(7), 2, 6, cfg.vocab)
    exact = generate(cfg, params, prompt, max_new_tokens=8)
    quant = generate(
        cfg, params, prompt, max_new_tokens=8, kv_dtype="int8"
    )
    assert quant.shape == exact.shape
    agree = float(jnp.mean((exact == quant).astype(jnp.float32)))
    assert agree >= 0.75, f"only {agree:.0%} of greedy tokens agree"


def test_moe_rejected_in_pipeline_path():
    from dcos_commons_tpu.models import pipeline_forward

    params = init_params(MOE_CFG, jax.random.key(0))
    tokens, _ = synthetic_tokens(jax.random.key(3), 2, 64, 128)
    with pytest.raises(NotImplementedError, match="not pipelined"):
        pipeline_forward(MOE_CFG, params, tokens, n_micro=2)


# -- mlp + checkpointing ---------------------------------------------


def test_mlp_trains():
    config = MlpConfig(dtype=jnp.float32)
    params = mlp_init(config, jax.random.key(0))
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step = mlp_train_step(optimizer)
    x, y = synthetic_mnist(jax.random.key(1), 64)
    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_checkpoint_roundtrip(tmp_path):
    config = MlpConfig(dtype=jnp.float32)
    params = mlp_init(config, jax.random.key(0))
    save_checkpoint(str(tmp_path), 7, params)
    like = mlp_init(config, jax.random.key(1))
    restored, step = restore_checkpoint(str(tmp_path), like)
    assert step == 7
    np.testing.assert_array_equal(
        np.asarray(restored["w1"]), np.asarray(params["w1"])
    )
    # empty dir: returns like, None
    _, none_step = restore_checkpoint(str(tmp_path / "empty"), like)
    assert none_step is None


def test_checkpoint_retention(tmp_path):
    """keep=K prunes to the newest K AFTER the new save is durable;
    keep=0 keeps everything; the latest step always restores."""
    tree = {"w": jnp.ones((2, 2), jnp.float32)}
    # a stray operator file in the directory must neither crash the
    # pruner nor be pruned (review r5)
    (tmp_path / "step_best.npz").write_bytes(b"not a checkpoint")
    for step in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), step, tree, keep=2)
    names = sorted(p.name for p in tmp_path.glob("step_*.npz"))
    assert names == [
        "step_0000000003.npz", "step_0000000004.npz", "step_best.npz",
    ]
    restored, step = restore_checkpoint(str(tmp_path), tree)
    assert step == 4
    # keep=0 (default): nothing pruned
    save_checkpoint(str(tmp_path), 5, tree)
    assert len(list(tmp_path.glob("step_0*.npz"))) == 3
    # an explicitly requested absent step errors, never silent-fresh
    import pytest

    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path), tree, step=99)
    # a hand-named (unpadded) checkpoint restores and prunes by its
    # LISTED name
    import shutil

    shutil.copy(
        tmp_path / "step_0000000005.npz", tmp_path / "step_7.npz"
    )
    restored, step = restore_checkpoint(str(tmp_path), tree)
    assert step == 7
    save_checkpoint(str(tmp_path), 8, tree, keep=1)
    names = sorted(p.name for p in tmp_path.glob("step_*.npz"))
    assert names == ["step_0000000008.npz", "step_best.npz"]
    # ROLLBACK + retrain: saving a step OLDER than existing files
    # keeps the checkpoint just written AND prunes the abandoned
    # future (review r5 x2) — the default latest-step resume must
    # find the retrain, not the state the rollback undid
    save_checkpoint(str(tmp_path), 2, tree, keep=1)
    names = sorted(p.name for p in tmp_path.glob("step_0*.npz"))
    assert names == ["step_0000000002.npz"]
    restored, step = restore_checkpoint(str(tmp_path), tree)
    assert step == 2
    # the operator's non-step snapshot survives every prune
    assert (tmp_path / "step_best.npz").exists()


def test_checkpoint_bf16_roundtrip(tmp_path):
    """bf16 leaves must survive the npz round-trip (review regression)."""
    tree = {"w": jnp.ones((4, 4), jnp.bfloat16) * 1.5,
            "count": jnp.zeros((), jnp.int32)}
    save_checkpoint(str(tmp_path), 3, tree)
    like = {"w": jnp.zeros((4, 4), jnp.bfloat16),
            "count": jnp.zeros((), jnp.int32)}
    restored, step = restore_checkpoint(str(tmp_path), like)
    assert step == 3
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["w"].astype(jnp.float32)),
        np.full((4, 4), 1.5, np.float32),
    )
    # stored as the 16 bits it is, not widened: the file costs what
    # the state costs (the widened flagship checkpoint was refused by
    # a chip host's file-size limit)
    (name,) = [n for n in os.listdir(tmp_path) if n.endswith(".npz")]
    stored = np.load(tmp_path / name)
    assert {stored[k].dtype for k in stored.files if k != "__meta__"} == {
        np.dtype("uint16"), np.dtype("int32"),
    }
    # a float32-widened leaf (what older files hold) still restores
    widened = {k: stored[k] for k in stored.files}
    for key in [k for k in widened if widened[k].dtype == np.uint16]:
        widened[key] = np.full((4, 4), 1.5, np.float32)
    np.savez(tmp_path / "step_0000000004.npz", **widened)
    restored, step = restore_checkpoint(str(tmp_path), like)
    assert step == 4 and restored["w"].dtype == jnp.bfloat16
    assert float(restored["w"][0, 0]) == 1.5


def test_checkpoint_refused_write_leaves_no_tmp(tmp_path, monkeypatch):
    """A write the disk refuses raises and leaves nothing behind: a
    stranded multi-GB .tmp would take the space the next save needs."""
    def refuse(*_args, **_kwargs):
        raise OSError(27, "File too large")

    monkeypatch.setattr(np, "savez", refuse)
    with pytest.raises(OSError):
        save_checkpoint(str(tmp_path), 1, {"w": np.ones(4)})
    assert os.listdir(tmp_path) == []
