"""Multi-host pjit training worker (BASELINE.json config 4).

One of these runs per host of the gang pod.  It consumes the
scheduler's env contract (COORDINATOR_ADDRESS, TPU_WORKER_ID, ...),
rendezvouses via jax.distributed, builds a dp-over-hosts x tp-within-
host mesh, and trains the flagship transformer with orbax-style
checkpointing so PERMANENT gang recovery resumes from the last step.
"""

import json
import os
import sys
import time

# the package is found from this file (frameworks/jax/ sits two levels
# under the checkout): tasks run with their sandbox as cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))


def main() -> int:
    from dcos_commons_tpu.parallel.distributed import initialize_from_env
    from dcos_commons_tpu.parallel.overlap import enable_collective_overlap

    # libtpu's latency-hiding scheduler flags must land in
    # LIBTPU_INIT_ARGS before the first jax backend init: without them
    # several libtpu builds serialize the grad reduce-scatters the
    # microbatched step was restructured to overlap (TPU-only;
    # TRAIN_XLA_OVERLAP=0 opts out)
    enable_collective_overlap()
    contract = initialize_from_env()
    if contract["num_slices"] > 1:
        # the slice identity an operator needs when reading one
        # sandbox's log against a whole-gang timeline
        print(
            f"multi-slice gang: slice {contract['slice_index']}/"
            f"{contract['num_slices']} "
            f"({contract['hosts_per_slice']} host(s)/slice), "
            f"slice anchor {contract['slice_coordinator'] or 'n/a'}",
            flush=True,
        )

    import jax
    import jax.numpy as jnp
    import optax

    from dcos_commons_tpu.models import (
        config_from_env,
        init_params,
        make_train_step,
        train_state_shardings,
    )
    from dcos_commons_tpu.ops.introspect import mosaic_calls
    from dcos_commons_tpu.parallel.mesh import mesh_from_env
    from dcos_commons_tpu.trace.steplog import InflightWindow, StepLog
    from dcos_commons_tpu.utils import (
        AsyncCheckpointer,
        claim_devices,
        claim_incarnation,
        enable_compilation_cache,
        restore_checkpoint,
        save_checkpoint,
        synthetic_tokens,
    )

    # what this worker runs on, before anything is built on it: a tpu:
    # pod that fell back to the CPU stops here
    devices = claim_devices()
    print(f"devices: {json.dumps(devices)}", flush=True)
    # a recovered/replaced gang worker re-jits the identical train
    # step; the persistent cache turns that into a disk read
    cache_dir = enable_compilation_cache()

    steps = int(os.environ.get("TRAIN_STEPS", "100"))
    ckpt_dir = os.environ.get("CHECKPOINT_DIR", "checkpoints")
    # per-step telemetry into $SANDBOX/steplog.jsonl: the scheduler's
    # /v1/debug/trace merges every host's lane into one timeline, so
    # gang skew (who waited on whom) is read off the blocked_s column.
    # The barrier probe is a gang-wide sync BEFORE each step's first
    # collective; its wall time on the fast hosts IS the skew the slow
    # host imposed.  STEPLOG_BARRIER_PROBE=0 drops the probe (and the
    # skew column) when even a barrier per step is too much.
    steplog = StepLog()
    probe_gang = os.environ.get("STEPLOG_BARRIER_PROBE", "1") not in (
        "0", "false"
    )
    mesh = mesh_from_env(os.environ)
    if os.environ.get("TPU_TOPOLOGY"):
        # elastic-DP resume guard (ISSUE 13): when the devices actually
        # present disagree with the DECLARED topology (a resized
        # relaunch), proceeding is only safe if the change is a pure
        # batch-axis (dp/dcn) re-layout — params and optimizer state
        # replicate over those axes, so the fenced checkpoint restores
        # bit-identically onto the new mesh.  A model-axis change
        # (tp/fsdp/...) would silently train a different parallelism:
        # refuse loudly.  TRAIN_ELASTIC_DP=1 opts in; the scheduler's
        # own elastic re-slice rewrites TPU_TOPOLOGY consistently and
        # never needs the flag.
        from dcos_commons_tpu.parallel.mesh import (
            derive,
            elastic_reshard_ok,
        )

        declared = derive(os.environ)
        actual = derive(os.environ, n_devices=mesh.devices.size)
        if actual != declared:
            elastic = os.environ.get("TRAIN_ELASTIC_DP", "0") not in (
                "0", "false"
            )
            if not elastic or not elastic_reshard_ok(declared, actual):
                raise RuntimeError(
                    f"mesh mismatch: declared topology derives {declared} "
                    f"but {mesh.devices.size} device(s) derive {actual}; "
                    "only a dp/dcn change is elastically resumable "
                    "(set TRAIN_ELASTIC_DP=1 to allow it)"
                )
            print(
                f"elastic-dp resume: {declared.total} -> {actual.total} "
                f"chips (dp {declared.dp}->{actual.dp}, dcn "
                f"{declared.dcn}->{actual.dcn}); checkpoint reshards as "
                "a pure re-layout", flush=True,
            )
    # the env->config contract lives in models/transformer.py so
    # analysis/shardcheck verifies the EXACT model this pod trains
    config = config_from_env(os.environ, dtype=jnp.bfloat16)
    optimizer = optax.adamw(3e-4)
    with mesh:
        params = init_params(config, jax.random.key(0))
        opt_state = optimizer.init(params)
        # checkpoint carries params AND optimizer moments; its stamp is
        # the next step to run, so resume never double-applies a step
        state = {"params": params, "opt_state": opt_state}
        state, start = restore_checkpoint(ckpt_dir, state)
        params, opt_state = state["params"], state["opt_state"]
        start = start or 0
        if contract["worker_count"] > 1:
            # the checkpoint stamp came off LOCAL disk: if one host's
            # sandbox holds step 80 and another's holds step 100, the
            # training loops disagree on the trip count and the gang
            # deadlocks in the shorter host's last allreduce
            # (spmdcheck: spmd-per-host-trip-count).  Agree up front
            # and fail the deploy loudly on divergence — recovery
            # relaunches the gang, which beats a silent hang.
            from jax.experimental import multihost_utils

            starts = multihost_utils.process_allgather(jnp.int32(start))
            if int(starts.min()) != int(starts.max()):
                raise RuntimeError(
                    "checkpoint step diverges across the gang: "
                    f"{sorted(int(s) for s in starts)}; wipe the stale "
                    "sandboxes or restore a shared CHECKPOINT_DIR"
                )
            start = int(starts[0])
        # lay the state out as the step pins it BEFORE the first call:
        # default-placed, step 0 would run on one input type and step
        # 1 on step 0's mesh-sharded outputs — two traces, two XLA
        # compiles of the same step on every cold start
        params, opt_state = jax.device_put(
            (params, opt_state),
            train_state_shardings(config, optimizer, mesh),
        )
        # the step-time fast path (ISSUE 7): donated buffers (the
        # params/opt-state update happens in place instead of paying a
        # full HBM copy per step), optional microbatched gradient
        # accumulation (per-microbatch collectives overlap the next
        # microbatch's compute), and a bounded async-dispatch window
        # below.  Each has an env opt-out because a debugging session
        # wants the boring synchronous loop back.
        donate = os.environ.get("TRAIN_DONATE", "1") not in ("0", "false")
        grad_accum = max(1, int(os.environ.get("TRAIN_GRAD_ACCUM", "1")))
        # in-flight window: dispatch step N, block on step N-k's loss.
        # 0 = synchronous (block every step, the pre-overlap loop)
        inflight = max(0, int(os.environ.get("TRAIN_INFLIGHT_STEPS", "2")))
        step_fn = make_train_step(
            config, optimizer, mesh=mesh, donate=donate,
            grad_accum=grad_accum,
        )
        batch = max(2, 2 * mesh.devices.size)
        # microbatches must split evenly AND each batch must still
        # shard over the mesh's data axes (in_shardings pins tokens to
        # batch_spec): round up to a multiple of lcm(grad_accum,
        # batch-axis product) — padding to grad_accum alone could
        # break dp/fsdp divisibility and kill the first dispatch
        # (review r7)
        import math

        from dcos_commons_tpu.parallel.mesh import BATCH_AXES

        batch_shard = 1
        for axis in BATCH_AXES:
            batch_shard *= mesh.shape.get(axis, 1)
        multiple = math.lcm(grad_accum, batch_shard)
        if batch % multiple:
            batch += multiple - batch % multiple
        data_dir = os.environ.get("DATA_DIR", "")
        batches = None
        if data_dir:
            # real corpus: memory-mapped token shards round-robin over
            # the gang (disjoint per worker), device-prefetched; the
            # stream is a pure function of (seed, step) so checkpoint
            # resume continues EXACTLY where the dead incarnation left
            from jax.sharding import NamedSharding

            from dcos_commons_tpu.data import DevicePrefetcher, TokenDataset
            from dcos_commons_tpu.parallel.mesh import batch_spec

            dataset = TokenDataset(
                data_dir, config.max_seq,
                worker_id=contract["worker_id"],
                worker_count=contract["worker_count"],
            )
            # batches must land SHARDED like the train step expects
            # (each process's distinct batch is its dp slice of the
            # global batch) — a plain device_put would fight the jit's
            # in_shardings on any multi-device mesh.  Each process
            # therefore yields its SHARE of the global batch: feeding
            # `batch` rows per process would silently train at
            # batch x worker_count (JAX infers global = local x procs)
            local_rows = max(1, batch // contract["worker_count"])
            batches = DevicePrefetcher(
                dataset.batches(local_rows, start_step=start), depth=2,
                sharding=NamedSharding(mesh, batch_spec()),
            )
            print(
                f"data: {dataset.n_sequences} sequences for worker "
                f"{contract['worker_id']}", flush=True,
            )
        else:
            tokens, targets = synthetic_tokens(
                jax.random.key(1), batch, config.max_seq, config.vocab
            )
        gang = contract["worker_count"] > 1
        if gang and probe_gang:
            from jax.experimental import multihost_utils
        # non-blocking checkpointing: save() costs the loop one async
        # device-side copy; the gather + npz write + fenced prune run
        # on a background thread.  The writer incarnation (claimed by
        # process 0 only — it is the only writer) fences a zombie
        # trainer out of a relaunched gang's CHECKPOINT_DIR.
        keep = int(os.environ.get("CHECKPOINT_KEEP", "3"))
        async_ckpt = os.environ.get("TRAIN_ASYNC_CKPT", "1") not in (
            "0", "false"
        )
        if gang:
            # process 0 claims (single writer) and BROADCASTS the
            # token so the whole gang agrees on the incarnation —
            # spmdcheck: every host must issue the same collective
            # sequence, so the claim result is made gang-uniform
            # before anything downstream can branch on it
            from jax.experimental import multihost_utils

            local = (
                claim_incarnation(ckpt_dir)
                if jax.process_index() == 0 else 0
            )
            incarnation = int(multihost_utils.broadcast_one_to_all(
                jnp.int32(local)
            ))
        else:
            incarnation = claim_incarnation(ckpt_dir)
        checkpointer = (
            AsyncCheckpointer(ckpt_dir, keep=keep, incarnation=incarnation)
            if async_ckpt else None
        )
        # the bounded in-flight window bills wall_s/blocked_s to the
        # step that incurred them even though the host runs k steps
        # ahead of the devices (trace/steplog.py InflightWindow)
        window = InflightWindow(steplog, inflight)

        def note_drained(drained):
            for s, ready_loss in drained:
                if s % 20 == 0 or s == steps - 1:
                    # the loss is already on host: float() here cannot
                    # stall the pipeline the way printing the
                    # just-dispatched step's loss would
                    print(
                        f"step {s} loss={float(ready_loss):.4f}",
                        flush=True,
                    )

        # build the step BEFORE the loop and say what was built: the
        # Mosaic kernels in it with the operand shapes ONE device runs
        # them over (ops/introspect.py — a step that dispatched to the
        # jnp reference, or runs a kernel over the global batch, shows
        # here and nowhere else), and what building cost — an XLA
        # compile, or a read of the persistent cache — apart from the
        # first step's own time.  The loop's first call then finds the
        # executable in that cache.
        batch_aval = jax.ShapeDtypeStruct((batch, config.max_seq), jnp.int32)
        build_t0 = time.time()
        lowered = step_fn.lower(params, opt_state, batch_aval, batch_aval)
        kernels = mosaic_calls(lowered.as_text())
        compile_t0 = time.time()
        compiled = lowered.compile()
        built = time.time()
        print("train step: " + json.dumps({
            "mesh": {a: n for a, n in mesh.shape.items() if n > 1},
            "batch": batch,
            "seq": config.max_seq,
            "vocab": config.vocab,
            "d_model": config.d_model,
            "n_layers": config.n_layers,
            "n_heads": config.n_heads,
            "n_kv_heads": config.n_kv_heads,
            "d_ff": config.d_ff,
            "dtype": jnp.dtype(config.dtype).name,
            "lower_s": round(compile_t0 - build_t0, 2),
            "compile_s": round(built - compile_t0, 2),
            "compile_cache": cache_dir,
            "mosaic_calls": kernels,
            "all_gathers": (compiled.as_text() or "").count(" all-gather("),
        }), flush=True)
        del lowered, compiled

        t0 = time.time()
        for i in range(start, steps):
            step_t0 = time.time()
            blocked_s = 0.0
            if gang and probe_gang:
                # pre-allreduce barrier probe: meet the gang before
                # this step's first collective; time spent here is
                # time BLOCKED on slower hosts, not compute.  Under
                # overlap the probe still runs at DISPATCH order, so
                # its wait is the skew the slow host imposed at this
                # step's admission, billed to this step.
                b0 = time.time()
                multihost_utils.sync_global_devices(f"steplog-{i}")
                blocked_s = time.time() - b0
            if batches is not None:
                tokens, targets = next(batches)
            params, opt_state, loss = step_fn(params, opt_state, tokens, targets)
            if i % 20 == 0 or i == steps - 1:
                state = {"params": params, "opt_state": opt_state}
                if checkpointer is not None:
                    # snapshot NOW: the async device copy is enqueued
                    # before the next dispatch donates these buffers
                    checkpointer.save(i + 1, state)
                else:
                    save_checkpoint(
                        ckpt_dir, i + 1, state, keep=keep,
                        incarnation=incarnation,
                    )
            # push the dispatched step into the window; it blocks on
            # step i-k's loss (not step i's) and stamps the steplog
            # with the wall/blocked time each DRAINED step incurred
            note_drained(window.push(
                i, loss, step_t0, blocked_s=blocked_s,
                tokens=tokens.shape[0] * tokens.shape[1],
                worker=contract["worker_id"],
                platform=devices["platform"],
            ))
        note_drained(window.drain())
        # every local device should be holding its share of the state
        # (the CPU backend reports no memory stats)
        print("device memory: " + json.dumps([
            {"id": d.id,
             "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use")}
            for d in jax.local_devices()
        ]), flush=True)
        ckpt_errors = checkpointer.close() if checkpointer is not None else []
        steplog.close()
        if batches is not None:
            batches.close()
        if ckpt_errors:
            # a run whose checkpoints did not land is not a finished
            # run: recovery would resume from an older step than the
            # log claims
            raise RuntimeError(
                f"checkpoint writer failed: {ckpt_errors[:3]}"
            )
        dt = time.time() - t0
        tps = batch * config.max_seq * (steps - start) / max(dt, 1e-9)
        print(
            f"worker {contract['worker_id']}/{contract['worker_count']}: "
            f"{steps - start} steps, {tps:,.0f} tokens/s", flush=True,
        )
    # goal RUNNING: stay alive serving the mesh until the scheduler
    # kills or reconfigures the pod
    keepalive = os.environ.get("KEEPALIVE_S")
    if keepalive:
        time.sleep(float(keepalive))
    else:
        while True:
            time.sleep(60)


if __name__ == "__main__":
    sys.exit(main() or 0)
