"""Sharded serving gang worker: continuous batching over a multi-host
jax.distributed gang, fronted by rank 0's HTTP server.

The serving half of the flagship at GANG scale: the model's parameters
are tensor-parallel-sharded across every chip of the gang (a model too
big for one host serves from the whole slice), and the paged KV
arena (dcos_commons_tpu/serve/) is laid over the same mesh.  SPMD
serving needs every process in every collective, but requests arrive
only at the VIP'd rank — so rank 0 drives the gang with PER-TICK
broadcast ops and every rank executes the identical payload:

    NOOP    keep the gang meeting in a collective while idle
    ADMIT   prefill ONE CHUNK of one request's prompt, through its
            page table, into the pool
    DECODE  advance EVERY pool row one step (per-row pos/temp/seed,
            through per-row page tables)

Requests therefore join and leave MID-FLIGHT: a request arriving
while others decode is admitted at the next tick (TTFT = one tick +
its own prefill, not a whole preceding generation), and a row hitting
its EOS/max-token retires its slot immediately while the rest keep
stepping.  The driver/follower shape is unchanged from the
dispatch-per-group protocol this replaces (spmdcheck-clean: followers
just execute the broadcast payload), only the op vocabulary grew.

The broadcast payload: ADMIT carries a PREFILL_CHUNK_TOKENS-wide
prompt chunk, its traced start position/true length, and the
request's page table; DECODE carries every row's page table alongside
its (token, position, temp, seed) state.  Page allocation, budgeting and the prefix cache
are rank 0's HOST-side bookkeeping (serve/paging.py): followers only
ever see physical page ids in the broadcast tables, so every rank
still executes the identical tick and the collective schedules never
diverge.

Failover comes from GANG recovery, not from this file: kill any host
and the scheduler replaces the whole gang (tests/test_gang_serve.py
semantics); the replacement re-rendezvouses, rebuilds the identical
tp-sharded params, and greedy replies are token-identical
(tests/test_gang_serve_sharded.py proves it end to end).

Reference: the reference never serves models — its analogue is any
multi-task service behind a VIP (sdk/scheduler
offer/evaluate/PodInfoBuilder VIP labels); the gang/SPMD shape is the
TPU-first addition.
"""

import json
import math
import os
import sys

import numpy as np
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

# the package is found from this file (frameworks/jax/ sits two levels
# under the checkout): tasks run with their sandbox as cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))

from dcos_commons_tpu.serve import (  # noqa: E402
    SERVESTATS_NAME,
    PagedEngine,
    QueueTimeoutError,
    paged_config_from_env,
)
from dcos_commons_tpu.trace.steplog import StepLog  # noqa: E402

# how often idle ranks meet in a noop collective: the gang must stay
# in lockstep even with no traffic, or a request would wait on ranks
# parked in a stale program
IDLE_TICK_S = 0.05

# per-tick broadcast ops (the old one-shot OP_GENERATE grew into the
# ADMIT/DECODE pair so requests join and leave mid-flight)
OP_NOOP = 0
OP_ADMIT = 1
OP_DECODE = 2

# steplog sampling: continuous batching ticks once per TOKEN, not per
# request — record the first few ticks then every Nth so the skew
# signal survives without an unbounded file
_STEPLOG_EVERY = 64


# -- the broadcast protocol -------------------------------------------
# every tick broadcasts the same tuple of arrays regardless of op: the
# broadcast cost is flat and the follower loop is shape-stable


def _zero_paged_payload(slots, pages_per_row, chunk_tokens):
    return (
        # head by op: ADMIT = [op, slot, start, true_len, seed,
        # temp_micro]; DECODE = [op, n_active, 0, 0, 0, 0]; NOOP = 0s
        np.zeros(6, np.int64),
        np.zeros((slots, 4), np.int64),   # rows [tok, pos, temp_u, seed]
        np.zeros((slots, pages_per_row), np.int64),  # page tables
        np.zeros((1, chunk_tokens), np.int32),       # ADMIT chunk
    )


def _broadcast_paged_tick(multihost_utils, payload, slots,
                          pages_per_row, chunk_tokens):
    """One gang-wide broadcast of the paged payload: rank 0 passes
    (head, rows, tables, chunk), followers pass None and receive rank
    0's.  Flat cost per tick: the byte shape never depends on op."""
    if payload is None:
        payload = _zero_paged_payload(slots, pages_per_row, chunk_tokens)
    head, rows, tables, chunk = multihost_utils.broadcast_one_to_all(
        payload
    )
    return (
        np.asarray(head), np.asarray(rows), np.asarray(tables),
        np.asarray(chunk),
    )


def _execute_paged_tick(pool, head, rows, tables, chunk):
    """Execute the broadcast paged op on EVERY rank (rank 0 included)
    — page ids arrive as data, so the traced operands are
    byte-identical across the gang."""
    op = int(head[0])
    if op == OP_ADMIT:
        slot = int(head[1])
        return pool.prefill_chunk(
            chunk, slot=slot, table=tables[slot].astype(np.int32),
            start=int(head[2]), true_len=int(head[3]),
            temp=int(head[5]) / 1e6, seed=int(head[4]),
        )
    if op == OP_DECODE:
        return pool.decode(
            rows[:, 0].astype(np.int32),
            rows[:, 1].astype(np.int32),
            (rows[:, 2] / 1e6).astype(np.float32),
            rows[:, 3].astype(np.int32),
            tables.astype(np.int32),
        )
    return None


def main() -> int:
    from dcos_commons_tpu.parallel.distributed import initialize_from_env

    contract = initialize_from_env()

    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dcos_commons_tpu.models import config_from_env, init_params
    from dcos_commons_tpu.models.transformer import param_shardings
    from dcos_commons_tpu.parallel.mesh import MeshSpec, make_mesh
    from dcos_commons_tpu.serve.pool import PagedPoolModel
    from dcos_commons_tpu.utils import (
        claim_devices,
        enable_compilation_cache,
        restore_checkpoint,
    )

    # a tpu: pod that fell back to the CPU stops here
    devices = claim_devices()
    print(f"devices: {json.dumps(devices)}", flush=True)
    enable_compilation_cache()
    rank = contract["worker_id"]
    # a RELAUNCH reuses the sandbox: a stale ready file from the
    # previous incarnation must not pass readiness while we are cold
    try:
        os.remove("ready")
    except OSError:
        pass
    config = config_from_env(
        os.environ,
        dtype=jnp.bfloat16 if devices["platform"] == "tpu" else jnp.float32,
        remat=False,
    )
    if not config.one_kind:
        # per-part stacks and a row's conv state have no layout over
        # the gang's tp mesh (models/transformer.py init_params,
        # serve/pool.py): a mixed layer pattern is one chip's
        raise SystemExit(
            "serve_gang_worker: the layer pattern "
            f"{sorted(set(config.layer_kinds))} is not served by a gang; "
            "deploy it through serve_worker.py on one chip"
        )
    max_len = int(os.environ.get("MAX_LEN", "256"))
    # unset SERVE_BATCH means a bare/dev launch; fall back to one
    # request rather than the deploy default 8 (see options.json
    # serving.batch description)
    # sdklint: disable=config-default-drift — dev fallback
    batch = int(os.environ.get("SERVE_BATCH", "1"))
    # "" and 0 both mean "use SERVE_BATCH" (the options.json default)
    slots = int(os.environ.get("SERVE_SLOTS") or 0) or batch
    new_tokens = int(os.environ.get("MAX_NEW_TOKENS", "32"))
    prompt_len = max_len - new_tokens

    # the WHOLE gang is one tp axis: the model lives sharded across
    # every chip (ICI within hosts, DCN across under a dcn axis would
    # slot in here for multi-slice; the test gang is one slice)
    n_devices = len(jax.devices())
    mesh = make_mesh(MeshSpec(tp=n_devices))
    with mesh:
        params = init_params(config, jax.random.key(0))
        ckpt_dir = os.environ.get("CHECKPOINT_DIR", "")
        if ckpt_dir:
            state, step = restore_checkpoint(ckpt_dir, {"params": params})
            if step is not None:
                params = state["params"]
                print(f"restored checkpoint step {step}", flush=True)
        params = jax.tree.map(
            jax.device_put, params, param_shardings(config, mesh)
        )
        if os.environ.get("WEIGHT_DTYPE", "native") == "int8":
            # quantize AFTER placement: GSPMD derives the int8/scale
            # shardings from the already-sharded weights, so the
            # {"q","scale"} leaves need no new sharding rules
            from dcos_commons_tpu.models import quantize_params_int8

            params = jax.jit(quantize_params_int8)(params)
            if rank == 0:
                print("weights quantized to int8 (per-channel)", flush=True)
        replicated = NamedSharding(mesh, P())

        def to_global(arr):
            """Identical host-local array on every rank -> one global
            replicated jax array the sharded pool accepts."""
            return multihost_utils.host_local_array_to_global_array(
                arr, mesh, P()
            )

        kv_dtype = os.environ.get("KV_DTYPE", "native")
        # the pool's KV heads ride the tp axis like the attention
        # weights when they divide it; otherwise the cache replicates
        # (tiny-head test configs on wide meshes).  The arena keeps
        # kv heads on dim 3 — (layers, pages, page_tokens, kv, hd)
        kv_spec = (
            P(None, None, None, "tp", None)
            if config.n_kv_heads % n_devices == 0 else P()
        )
        paged = paged_config_from_env(os.environ)
        pool = PagedPoolModel(
            config, params, slots, max_len, paged.page_tokens,
            paged.pages, paged.chunk_tokens, kv_dtype=kv_dtype,
            cache_sharding=NamedSharding(mesh, kv_spec),
            put=to_global,
            constrain_out=lambda x: (
                jax.lax.with_sharding_constraint(x, replicated)
            ),
        )

        # warm the compiled pool as a GANG before readiness: the first
        # request must not pay the compiles, and a rank that cannot
        # compile must fail deploy, not the first client.  Every rank
        # reaches this call at the same program point (pre-loop).  The
        # gang's ticks broadcast host arrays and are resolved as they
        # return: the synchronous calls are the ones to warm
        pool.warm(ahead=False)
        pages_per_row = paged.pages_per_row
        chunk_tokens = paged.chunk_tokens

        # per-tick step telemetry ($SANDBOX/steplog.jsonl): sampled
        # decode ticks on every rank — wall seconds, active rows, and
        # for followers the time spent parked in the broadcast waiting
        # for rank 0 (the serving gang's skew/idle signal).  Surfaced
        # by the scheduler's /v1/debug/trace as one lane per host.
        import time as _time

        steplog = StepLog()
        tick_count = [0]

        def _log_tick(wall_s, blocked_s, active):
            n = tick_count[0]
            tick_count[0] += 1
            if n >= 4 and n % _STEPLOG_EVERY:
                return
            steplog.record(
                n,
                wall_s=round(wall_s, 6),
                blocked_s=round(blocked_s, 6),
                rows=active,
                tokens=active,
                worker=rank,
            )

        # Intentional driver/follower split: BOTH sides of this branch
        # run the identical collective sequence (one
        # _broadcast_paged_tick per tick; _execute_paged_tick runs
        # the same op payload on every rank), so the schedules never
        # diverge; the branch only decides who PRODUCES the payload
        # that every rank consumes.
        # sdklint: disable=spmd-host-branch — driver loops meet in the broadcast
        if rank != 0:
            # follower loop: meet rank 0 in every broadcast tick and
            # execute whatever it scheduled
            with open("ready", "w") as f:
                f.write("warm\n")
            print(f"rank {rank}: following gang broadcasts", flush=True)
            while True:
                b0 = _time.time()
                head, rows, tables, chunk = _broadcast_paged_tick(
                    multihost_utils, None, slots, pages_per_row,
                    chunk_tokens,
                )
                blocked_s = _time.time() - b0
                t0 = _time.time()
                _execute_paged_tick(pool, head, rows, tables, chunk)
                if int(head[0]) == OP_DECODE:
                    _log_tick(
                        _time.time() - t0, blocked_s, int(head[1])
                    )

        # ---- rank 0: HTTP front end + the engine --------------------
        # engine callbacks broadcast the op, then execute it exactly
        # like a follower would (one code path = no divergence);
        # on_idle keeps the followers meeting in noop collectives.
        def paged_prefill_fn(padded, slot, table, start, true_len,
                             temp, seed):
            # round() like decode does: truncation would give a
            # request's FIRST token a different temperature than its
            # later tokens (0.07*1e6 truncates to 69999)
            head = np.asarray(
                [OP_ADMIT, slot, start, true_len, seed,
                 round(temp * 1e6)],
                np.int64,
            )
            _, zero_rows, zero_tables, _ = _zero_paged_payload(
                slots, pages_per_row, chunk_tokens
            )
            zero_tables[slot] = table
            out = _broadcast_paged_tick(
                multihost_utils,
                (head, zero_rows, zero_tables,
                 padded.astype(np.int32)),
                slots, pages_per_row, chunk_tokens,
            )
            return _execute_paged_tick(pool, *out)

        def paged_decode_fn(tok, pos, temps, seeds, tables, n_active):
            head = np.asarray(
                [OP_DECODE, n_active, 0, 0, 0, 0], np.int64
            )
            rows = np.stack([
                tok.astype(np.int64),
                pos.astype(np.int64),
                np.round(
                    temps.astype(np.float64) * 1e6
                ).astype(np.int64),
                seeds.astype(np.int64),
            ], axis=1)
            zero_chunk = np.zeros((1, chunk_tokens), np.int32)
            bcast = _broadcast_paged_tick(
                multihost_utils,
                (head, rows, tables.astype(np.int64), zero_chunk),
                slots, pages_per_row, chunk_tokens,
            )
            t0 = _time.time()
            out = _execute_paged_tick(pool, *bcast)
            # rank 0 paces the gang; it never waits in the broadcast
            _log_tick(_time.time() - t0, 0.0, n_active)
            return out

        def paged_idle_tick():
            _broadcast_paged_tick(
                multihost_utils, None, slots, pages_per_row,
                chunk_tokens,
            )

        queue_timeout_s = float(
            os.environ.get("SERVE_QUEUE_TIMEOUT_S", "600")
        )
        stats_path = os.path.join(
            os.environ.get("SANDBOX", "."), SERVESTATS_NAME
        )

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                if self.path.split("?")[0] != "/stats":
                    self.send_error(404)
                    return
                payload = json.dumps(engine.stats()).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_POST(self):
                if self.path != "/generate":
                    self.send_error(404)
                    return
                length = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(length))
                    rows = body["tokens"]
                    if len(rows) > batch:
                        raise ValueError(
                            f"{len(rows)} prompts > server batch {batch}"
                        )
                    # rows may have MIXED lengths: each rides its own
                    # pool slot with its own true_len
                    for row in rows:
                        if not 1 <= len(row) <= prompt_len:
                            raise ValueError(
                                f"prompt length must be in "
                                f"[1, {prompt_len}]"
                            )
                    if not rows:
                        raise ValueError("tokens must be non-empty")
                    temp = float(body.get("temperature", 0.0))
                    if not math.isfinite(temp) or not 0.0 <= temp <= 1e4:
                        # bounded: the broadcast head carries the value
                        # as micro-units in an int64 — and a six-digit
                        # temperature is an input error anyway
                        raise ValueError(
                            f"temperature must be in [0, 10000], got {temp}"
                        )
                    n = min(
                        int(body.get("max_new_tokens", new_tokens)),
                        new_tokens,
                    )
                    if n < 1:
                        raise ValueError("max_new_tokens must be >= 1")
                    eos = body.get("eos")
                    if eos is not None:
                        eos = int(eos)
                        if not 0 <= eos < config.vocab:
                            raise ValueError(
                                f"eos must be in [0, {config.vocab})"
                            )
                    result = engine.submit(
                        [[int(t) % config.vocab for t in row]
                         for row in rows],
                        n, temperature=temp, eos_id=eos,
                    )
                    payload = json.dumps({"tokens": result}).encode()
                    self.send_response(200)
                except QueueTimeoutError as e:
                    # saturation, NOT caller error: no KV slot freed
                    # in time — clients/load generators back off
                    payload = json.dumps({"error": str(e)}).encode()
                    self.send_response(503)
                except Exception as e:  # noqa: BLE001
                    payload = json.dumps({"error": str(e)}).encode()
                    self.send_response(400)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        # bind BEFORE building the engine: the actually-bound port
        # rides the engine's first stats flush (the /v1/endpoints
        # `advertise: true` contract); on a shared machine a taken
        # assigned port falls back to an ephemeral bind + advertise
        port = int(os.environ.get("PORT_HTTP", "0"))
        try:
            server = ThreadingHTTPServer(("0.0.0.0", port), Handler)
        except OSError:
            server = ThreadingHTTPServer(("0.0.0.0", 0), Handler)
            print(
                f"rank 0: port {port} in use; bound "
                f"{server.server_address[1]} instead (advertised via "
                "servestats)",
                flush=True,
            )
        bound_port = int(server.server_address[1])
        engine = PagedEngine(
            paged_prefill_fn, paged_decode_fn, slots, max_len,
            prompt_len,
            page_tokens=paged.page_tokens, pages=paged.pages,
            chunk_tokens=paged.chunk_tokens,
            prefix_cache=paged.prefix_cache, layout=pool.layout,
            queue_timeout_s=queue_timeout_s,
            on_idle=paged_idle_tick, idle_every_s=IDLE_TICK_S,
            stats_path=stats_path,
            log=lambda msg: print(msg, flush=True),
            extra_stats={
                "http_port": bound_port,
                "prefill_chunk_source": paged.chunk_source,
            },
            annotate=jax.profiler.TraceAnnotation,
        )
        with open("ready", "w") as f:
            f.write("warm\n")
        shape = (
            f"{paged.pages}-page arena (pages of {paged.page_tokens}, "
            f"{slots} rows, {paged.chunk_note})"
        )
        print(
            f"rank 0: serving sharded generate over a {shape} "
            f"(prompts<={prompt_len}->{new_tokens}) tp={n_devices} "
            f"on {server.server_address[1]}",
            flush=True,
        )
        server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
