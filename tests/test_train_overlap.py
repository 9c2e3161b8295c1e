"""Step-time fast path (ISSUE 7): InflightWindow accounting under
async dispatch, loss equivalence of the overlapped loop, grad-accum
numerics, async + incarnation-fenced checkpointing, and the worker's
env knobs end to end.

The accounting tests drive the window with a FAKE device (a ready_fn
that sleeps until each step's scheduled completion) so the billing
contract is pinned independently of any backend's dispatch semantics:
this container's CPU backend executes inline, a TPU's dispatch is
async — wall_s must mean the same thing on both.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from dcos_commons_tpu.models import (  # noqa: E402
    TransformerConfig,
    init_params,
    make_train_step,
)
from dcos_commons_tpu.trace.steplog import (  # noqa: E402
    InflightWindow,
    StepLog,
    read_steplog,
)
from dcos_commons_tpu.utils import (  # noqa: E402
    AsyncCheckpointer,
    StaleWriterError,
    claim_incarnation,
    restore_checkpoint,
    save_checkpoint,
)


class _Recorder:
    """StepLog stand-in capturing records in memory."""

    def __init__(self):
        self.records = []

    def record(self, step, **fields):
        self.records.append(dict(step=step, **fields))


class _FakeDevice:
    """A device whose step N completes at a scheduled wall time:
    ready(result) blocks until that step's completion, like
    block_until_ready on a genuinely async backend."""

    def __init__(self):
        self.done_at = {}

    def dispatch(self, step, duration_s):
        # steps execute in order: step N completes duration after
        # the LATER of its dispatch and step N-1's completion
        prev = max(self.done_at.values()) if self.done_at else time.time()
        self.done_at[step] = max(prev, time.time()) + duration_s
        return step

    def ready(self, step):
        delay = self.done_at[step] - time.time()
        if delay > 0:
            time.sleep(delay)
        return step


# -- window accounting -------------------------------------------------


def test_window_bills_wall_to_incurring_step():
    """Async dispatch k=2: the host runs ahead, yet each step's
    wall_s converges to the device time THAT step added, and
    blocked_s stays with the step whose barrier it was."""
    device = _FakeDevice()
    rec = _Recorder()
    window = InflightWindow(rec, 2, ready_fn=device.ready)
    device_s = 0.05
    t_start = time.time()
    for i in range(6):
        t0 = time.time()
        result = device.dispatch(i, device_s)
        window.push(i, result, t0, blocked_s=0.001 * i, worker=7)
    window.drain()
    total = time.time() - t_start

    assert [r["step"] for r in rec.records] == list(range(6))
    assert all(r["worker"] == 7 for r in rec.records)
    # blocked_s billed to the step that measured it, untouched
    assert [r["blocked_s"] for r in rec.records] == [
        pytest.approx(0.001 * i) for i in range(6)
    ]
    # conservation: the records account for the whole run (pipeline
    # fill included), no step double-billed
    assert sum(r["wall_s"] for r in rec.records) == pytest.approx(
        total, abs=0.03
    )
    # steady state: each drained step bills ~one device step, NOT the
    # dispatch-to-ready span (which covers k+1 steps under overlap)
    for r in rec.records[1:]:
        assert r["wall_s"] == pytest.approx(device_s, abs=0.03)


def test_window_zero_matches_synchronous_loop():
    """k=0 is the pre-overlap loop: drain at every push, wall_s spans
    dispatch start to ready."""
    device = _FakeDevice()
    rec = _Recorder()
    window = InflightWindow(rec, 0, ready_fn=device.ready)
    for i in range(3):
        t0 = time.time()
        result = device.dispatch(i, 0.03)
        drained = window.push(i, result, t0)
        # synchronous: this step drained before push returned
        assert [s for s, _ in drained] == [i]
    assert window.drain() == []
    for r in rec.records:
        assert r["wall_s"] == pytest.approx(0.03, abs=0.02)


def test_window_caps_in_flight_depth():
    """The window never holds more than k undrained steps: dispatch
    runs at most k ahead of the oldest unfinished result."""
    device = _FakeDevice()
    rec = _Recorder()
    window = InflightWindow(rec, 3, ready_fn=device.ready)
    for i in range(10):
        window.push(i, device.dispatch(i, 0.001), time.time())
        assert len(window._pending) <= 3
    window.drain()
    assert window.drained == 10
    assert [r["step"] for r in rec.records] == list(range(10))


def test_window_idle_gap_billed_to_nobody():
    """A host-side pause between steps (a blocking save in the legacy
    path, a stall in the data loader) is NOT device time: the next
    step's wall_s starts at its own dispatch, not at the previous
    ready."""
    device = _FakeDevice()
    rec = _Recorder()
    window = InflightWindow(rec, 0, ready_fn=device.ready)
    window.push(0, device.dispatch(0, 0.02), time.time())
    time.sleep(0.08)  # the host stall
    window.push(1, device.dispatch(1, 0.02), time.time())
    window.drain()
    assert rec.records[1]["wall_s"] == pytest.approx(0.02, abs=0.02)


# -- loop equivalence --------------------------------------------------


def _tiny_config():
    return TransformerConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=176, max_seq=32, dtype=jnp.float32, remat=False,
    )


def _loop(step_fn, config, window_size, steps=6, batch=4):
    corpus = np.random.RandomState(0).randint(
        0, config.vocab, size=(steps, batch, config.max_seq + 1),
        dtype=np.int32,
    )
    params = init_params(config, jax.random.key(0))
    optimizer = optax.adamw(3e-4)
    opt_state = optimizer.init(params)
    rec = _Recorder()
    window = InflightWindow(rec, window_size)
    losses = {}
    for i in range(steps):
        t0 = time.time()
        tokens = jnp.asarray(corpus[i, :, :-1])
        targets = jnp.asarray(corpus[i, :, 1:])
        params, opt_state, loss = step_fn(
            params, opt_state, tokens, targets
        )
        for s, ready in window.push(i, loss, t0):
            losses[s] = float(ready)
    for s, ready in window.drain():
        losses[s] = float(ready)
    return losses, params


def test_overlapped_donated_loop_is_loss_equivalent():
    """The fast path (donated buffers + bounded in-flight window)
    must reproduce the synchronous undonated loop's losses EXACTLY
    under a deterministic config — buffer aliasing and host blocking
    order must never change the math (the PR 6 token-equality
    discipline applied to training)."""
    config = _tiny_config()
    optimizer = optax.adamw(3e-4)
    legacy = make_train_step(config, optimizer, donate=False)
    fast = make_train_step(config, optimizer, donate=True)
    legacy_losses, legacy_params = _loop(legacy, config, 0)
    fast_losses, fast_params = _loop(fast, config, 2)
    assert legacy_losses == fast_losses
    for a, b in zip(
        jax.tree.leaves(legacy_params), jax.tree.leaves(fast_params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_accum_matches_full_batch():
    """Equal-size microbatch accumulation is the full-batch gradient
    up to float reassociation: losses and updated params agree to
    numerical tolerance, over several steps."""
    config = _tiny_config()
    optimizer = optax.adamw(3e-4)
    full = make_train_step(config, optimizer, donate=False)
    accum = make_train_step(
        config, optimizer, donate=False, grad_accum=4
    )
    params = init_params(config, jax.random.key(0))
    state_f = (params, optimizer.init(params))
    state_a = (params, optimizer.init(params))
    tokens = jax.random.randint(
        jax.random.key(1), (8, config.max_seq), 0, config.vocab
    )
    targets = jax.random.randint(
        jax.random.key(2), (8, config.max_seq), 0, config.vocab
    )
    for _ in range(3):
        pf, sf, lf = full(*state_f, tokens, targets)
        pa, sa, la = accum(*state_a, tokens, targets)
        state_f, state_a = (pf, sf), (pa, sa)
        assert float(lf) == pytest.approx(float(la), abs=1e-5)
    for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pa)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_grad_accum_rejects_indivisible_batch():
    config = _tiny_config()
    step = make_train_step(
        config, optax.adamw(3e-4), donate=False, grad_accum=3
    )
    params = init_params(config, jax.random.key(0))
    opt_state = optax.adamw(3e-4).init(params)
    tokens = jnp.zeros((4, config.max_seq), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        step(params, opt_state, tokens, tokens)


# -- async + fenced checkpointing -------------------------------------


def test_async_checkpointer_snapshot_isolated_from_donation(tmp_path):
    """save() must capture the state AT SAVE TIME even though the
    loop keeps training (and donating those buffers) while the writer
    drains: the snapshot is a device-side copy, not a reference."""
    config = _tiny_config()
    optimizer = optax.adamw(3e-4)
    step_fn = make_train_step(config, optimizer, donate=True)
    params = init_params(config, jax.random.key(0))
    opt_state = optimizer.init(params)
    tokens = jax.random.randint(
        jax.random.key(1), (4, config.max_seq), 0, config.vocab
    )
    checkpointer = AsyncCheckpointer(str(tmp_path), keep=0)
    saved_at = {}
    for i in range(4):
        params, opt_state, loss = step_fn(
            params, opt_state, tokens, tokens
        )
        if i in (1, 3):
            checkpointer.save(
                i + 1, {"params": params, "opt_state": opt_state}
            )
            saved_at[i + 1] = jax.tree.map(
                lambda a: np.asarray(a).copy(), params
            )
    assert checkpointer.close() == []
    like = {
        "params": init_params(config, jax.random.key(9)),
        "opt_state": optimizer.init(params),
    }
    for step in (2, 4):
        restored, got = restore_checkpoint(
            str(tmp_path), like, step=step
        )
        assert got == step
        for want, have in zip(
            jax.tree.leaves(saved_at[step]),
            jax.tree.leaves(restored["params"]),
        ):
            np.testing.assert_array_equal(want, np.asarray(have))


def test_async_checkpointer_holds_one_device_snapshot(tmp_path, monkeypatch):
    """A snapshot is a full copy of the train state in HBM; at
    chip-filling sizes two do not fit (found on a v5e: the 872M
    flagship died RESOURCE_EXHAUSTED at its second save).  However
    fast save() is called and however slow the disk, at most one
    snapshot is device-resident: the writer moves it to host before
    the write, and the next save() waits for THAT."""
    from dcos_commons_tpu.utils import checkpoint as ckpt

    lock = threading.Lock()
    resident = {"now": 0, "max": 0}

    def snapshot_tree(tree):
        with lock:
            resident["now"] += 1
            resident["max"] = max(resident["max"], resident["now"])
        return tree

    def host_array(leaf):
        with lock:
            resident["now"] -= 1
        return leaf

    def slow_disk(directory, step, tree, **kwargs):
        time.sleep(0.1)
        return f"step_{step}"

    monkeypatch.setattr(ckpt, "_snapshot_tree", snapshot_tree)
    monkeypatch.setattr(ckpt, "_host_array", host_array)
    monkeypatch.setattr(ckpt, "save_checkpoint", slow_disk)
    checkpointer = ckpt.AsyncCheckpointer(str(tmp_path), incarnation=1)
    for step in range(5):
        checkpointer.save(step, {"w": np.ones(4)})  # one leaf per tree
    assert checkpointer.close() == []
    assert checkpointer.saved == [f"step_{i}" for i in range(5)]
    assert resident == {"now": 0, "max": 1}


def test_zombie_writer_cannot_destroy_newer_frontier(tmp_path):
    """The ADVICE round-5 regression: recovery relaunches a trainer
    (new incarnation) while the superseded one still has a save in
    flight.  The zombie's save must refuse — and the live writer's
    newer checkpoint must survive untouched."""
    d = str(tmp_path)
    tree = {"w": jnp.ones((2, 2), jnp.float32)}
    zombie_inc = claim_incarnation(d)
    live_inc = claim_incarnation(d)
    assert live_inc > zombie_inc
    live_path = save_checkpoint(d, 120, tree, keep=3, incarnation=live_inc)
    # the zombie flushes one last save BELOW the live frontier: the
    # old "caller owns the frontier" rule would have pruned step 120
    # as an 'abandoned future'
    with pytest.raises(StaleWriterError):
        save_checkpoint(d, 100, tree, keep=3, incarnation=zombie_inc)
    assert os.path.exists(live_path)
    restored, step = restore_checkpoint(d, tree)
    assert step == 120

    # same fence through the async writer: the failure is recorded,
    # the checkpointer latches fenced, and later saves drop silently
    checkpointer = AsyncCheckpointer(d, keep=3, incarnation=zombie_inc)
    checkpointer.save(101, tree)
    errors = checkpointer.wait()
    assert errors and "superseded" in errors[0]
    assert checkpointer.fenced is True
    checkpointer.save(102, tree)  # dropped, not raised
    assert checkpointer.close() == errors
    assert os.path.exists(live_path)
    _, step = restore_checkpoint(d, tree)
    assert step == 120


def test_fenced_prune_scopes_to_own_incarnation(tmp_path):
    """Retention and rollback pruning act on the writer's own past
    (its incarnation and older — legacy unfenced files included),
    never a newer incarnation's files."""
    d = str(tmp_path)
    tree = {"w": jnp.ones((2, 2), jnp.float32)}
    save_checkpoint(d, 5, tree)  # legacy, incarnation 0
    inc = claim_incarnation(d)
    save_checkpoint(d, 7, tree, keep=2, incarnation=inc)
    save_checkpoint(d, 9, tree, keep=2, incarnation=inc)
    names = sorted(
        n for n in os.listdir(d) if n.startswith("step_")
    )
    # keep=2 retained its own two newest; the legacy step 5 is this
    # writer's prunable past
    assert names == [
        "step_0000000007.inc_%010d.npz" % inc,
        "step_0000000009.inc_%010d.npz" % inc,
    ]
    # rollback WITHIN the incarnation still prunes its own abandoned
    # future (the pre-fencing semantics, now scoped)
    save_checkpoint(d, 3, tree, keep=1, incarnation=inc)
    _, step = restore_checkpoint(d, tree)
    assert step == 3


def test_claim_incarnation_is_race_free(tmp_path):
    """Concurrent claimers (a recovery relaunch racing the zombie's
    restart) can never share a token."""
    d = str(tmp_path)
    claimed = []
    lock = threading.Lock()

    def claim():
        inc = claim_incarnation(d)
        with lock:
            claimed.append(inc)

    threads = [threading.Thread(target=claim) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(claimed)) == 8


def test_restore_prefers_newest_incarnation_at_same_step(tmp_path):
    """Two writers stamped the same step (zombie save landed before
    fencing existed / before the newer writer's first save): the
    newest incarnation's file wins the restore."""
    d = str(tmp_path)
    old = {"w": jnp.ones((2, 2), jnp.float32)}
    new = {"w": jnp.full((2, 2), 7.0, jnp.float32)}
    save_checkpoint(d, 10, old, incarnation=1)
    save_checkpoint(d, 10, new, incarnation=2)
    restored, step = restore_checkpoint(d, old)
    assert step == 10
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.full((2, 2), 7.0, np.float32)
    )


# -- XLA overlap flags -------------------------------------------------


def test_collective_overlap_flags_tpu_only_and_operator_wins():
    """The latency-hiding flag set lands only for TPU tasks, in
    libtpu's own LIBTPU_INIT_ARGS (jaxlib aborts on an xla_tpu_* name
    in XLA_FLAGS, which is therefore never touched), never clobbers an
    operator's explicit spelling, and honors the TRAIN_XLA_OVERLAP
    opt-out."""
    from dcos_commons_tpu.parallel.overlap import (
        OVERLAP_FLAGS,
        enable_collective_overlap,
    )

    # not a TPU task: untouched
    env = {"JAX_PLATFORMS": "cpu", "TPU_GENERATION": "v5e"}
    assert enable_collective_overlap(env) == []
    assert "LIBTPU_INIT_ARGS" not in env and "XLA_FLAGS" not in env
    env = {}
    assert enable_collective_overlap(env) == []

    # TPU task: the full set lands, idempotently, and XLA_FLAGS keeps
    # exactly what the operator put there
    mine = "--xla_force_host_platform_device_count=8"
    env = {"TPU_GENERATION": "v5e", "XLA_FLAGS": mine}
    assert enable_collective_overlap(env) == list(OVERLAP_FLAGS)
    assert enable_collective_overlap(env) == []
    for flag in OVERLAP_FLAGS:
        assert flag in env["LIBTPU_INIT_ARGS"]
    assert env["XLA_FLAGS"] == mine

    # the operator's polarity survives (their spelling stays, ours is
    # not added for that flag)
    theirs = "--xla_tpu_enable_async_collective_fusion=false"
    env = {"TPU_GENERATION": "v5e", "LIBTPU_INIT_ARGS": theirs}
    added = enable_collective_overlap(env)
    assert OVERLAP_FLAGS[0] not in added
    assert env["LIBTPU_INIT_ARGS"].count(
        "--xla_tpu_enable_async_collective_fusion="
    ) >= 1
    assert theirs in env["LIBTPU_INIT_ARGS"]
    assert "XLA_FLAGS" not in env

    # name matching is token-wise: spelling only the LONGER
    # fuse_all_gather flag must not suppress the shorter fusion flag
    # (review r7: substring containment did exactly that)
    sub = "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=false"
    env = {"TPU_GENERATION": "v5e", "LIBTPU_INIT_ARGS": sub}
    added = enable_collective_overlap(env)
    assert OVERLAP_FLAGS[0] in added
    assert OVERLAP_FLAGS[1] not in added
    assert sub in env["LIBTPU_INIT_ARGS"]

    # the opt-out knob
    env = {"TPU_GENERATION": "v5e", "TRAIN_XLA_OVERLAP": "0"}
    assert enable_collective_overlap(env) == []


# -- the worker end to end --------------------------------------------


def _run_worker(sandbox, env_overrides):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "SANDBOX": sandbox,
        "CHECKPOINT_DIR": os.path.join(sandbox, "ckpt"),
        "VOCAB": "64", "D_MODEL": "32", "N_LAYERS": "1",
        "N_HEADS": "2", "N_KV_HEADS": "2", "D_FF": "96",
        "SEQ_LEN": "16",
        "KEEPALIVE_S": "0",
        "JAX_COMPILATION_CACHE_DIR": os.path.join(sandbox, "xla-cache"),
        **env_overrides,
    }
    return subprocess.run(
        [sys.executable,
         os.path.join(REPO, "frameworks/jax/train_worker.py")],
        env=env, capture_output=True, text=True, timeout=240,
    )


def test_worker_overlap_and_knobs_end_to_end(tmp_path):
    """The real worker with the fast-path defaults (window 2, async
    fenced checkpointing), then a RESUME with every knob opted out
    (TRAIN_INFLIGHT_STEPS=0, TRAIN_ASYNC_CKPT=0, mirroring
    STEPLOG_BARRIER_PROBE): both bill every step exactly once in the
    steplog, the resume continues at the checkpoint stamp, and the
    second incarnation's file takes over the directory."""
    sandbox = str(tmp_path)
    out = _run_worker(sandbox, {"TRAIN_STEPS": "5"})
    assert out.returncode == 0, out.stderr[-2000:]
    records = read_steplog(os.path.join(sandbox, "steplog.jsonl"))
    assert [r["step"] for r in records] == list(range(5))
    for r in records:
        assert r["wall_s"] >= 0 and r["blocked_s"] == 0.0
        assert r["tokens"] > 0
    ckpt = os.path.join(sandbox, "ckpt")
    fenced = [n for n in os.listdir(ckpt) if ".inc_" in n]
    assert fenced, os.listdir(ckpt)

    # resume with the synchronous opt-outs: same loop semantics, new
    # writer incarnation
    out = _run_worker(sandbox, {
        "TRAIN_STEPS": "7",
        "TRAIN_INFLIGHT_STEPS": "0",
        "TRAIN_ASYNC_CKPT": "0",
        "TRAIN_DONATE": "0",
    })
    assert out.returncode == 0, out.stderr[-2000:]
    records = read_steplog(os.path.join(sandbox, "steplog.jsonl"))
    # appended: steps 5..6 exactly once after the first run's 0..4
    assert [r["step"] for r in records] == list(range(5)) + [5, 6]
    names = sorted(n for n in os.listdir(ckpt) if n.startswith("step_"))
    incs = {n.split(".inc_")[1].split(".npz")[0] for n in names
            if ".inc_" in n}
    assert len(incs) == 2, names  # the resume claimed a new token
    assert any("step_0000000007" in n for n in names)
