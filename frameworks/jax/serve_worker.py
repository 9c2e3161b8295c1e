"""Inference serving task: the flagship behind an HTTP endpoint.

The scheduler deploys this like any other task (svc_serve.yml): it
builds the model, warms the pool's two programs (one prefill chunk
and one pool decode step), then serves POST
/generate on the scheduler-assigned port — discoverable via
/v1/endpoints and the VIP.  Readiness: the task's readiness check
passes once the warmup file exists, so the deploy plan completes only
when the server can actually answer.

Request:  {"tokens": [[...]], "max_new_tokens": N, "temperature": T,
           "eos": E?}
Response: {"tokens": [[...]]} — the continuations only (cut at E when
          the row produced it).
Errors:   400 = caller error (bad prompt/params); 503 = server
          saturation (the request timed out waiting for a KV slot) —
          load generators must be able to tell these apart.

Concurrency: CONTINUOUS BATCHING over a persistent PAGED KV arena
(dcos_commons_tpu/serve/, ISSUE 11): KV memory is a fixed budget of
KV_PAGE_TOKENS-sized pages with per-request page tables — a short
reply holds exactly the pages its tokens need instead of stranding a
MAX_LEN row, admission is page-budgeted (a request enters only when
its worst-case page need fits, and the 503 body says whether memory
or compute saturated), prompts prefill PREFILL_CHUNK_TOKENS at a time
interleaved with decode ticks (a long prompt no longer blocks the
tick it rides), and fully-prefilled prompt pages are shared read-only
across requests with the same prefix (prefix caching — the system-
prompt multiplier).  Mixed prompt lengths, requested lengths AND
temperatures share one pool dispatch, and greedy outputs are
token-identical to whole-batch generate.  MODEL_CONFIG names a
configuration file (the key names of a published config.json) that sizes the model
in place of the eight size names and says what they cannot:
rope_theta, the norm's epsilon and unit offset, an untied head, and
attention_class "eva", which gives every row a ring of exact window
pages beside its chunk-summary pages in the same arena
(models/decode.py, serve/paging.py RowLayout).  GET /stats exposes the
serving gauges (queue depth, KV occupancy, kv_pages_free,
prefix_cache_hit_rate, prefill_chunk_backlog, tokens/s) and the
engine loop's cumulative counters under ``loop``; the same snapshot
lands in the sandbox for the scheduler's /v1/debug/serving.

The engine's timeline: GET /trace (text; ?fmt=chrome for Perfetto)
renders the worker's span ring — one ``request`` span a POST with the
engine's queue/prefill/decode spans under it, one ``engine.tick`` a
tick — when SERVE_TRACE_CAPACITY > 0 (default 0: off).  POST /profile
{"seconds": n <= 30} captures a jax.profiler trace of the live
process into $SANDBOX/profile (the last capture only), with the
engine's ``engine.*`` and the pool's ``pool.*`` host spans above the
device's lines; 409 while another profiler session is open.

The start-up's timeline: GET /stats ``startup`` (and the sandbox
snapshot) holds this process's own account of launch -> ready, seven
phases that touch (``launch imports backend_up weights build warm
ready``, seconds and wall end stamps, their sum ``start_to_ready_s``),
``warm`` by program (``_prefill`` / ``_decode`` / other: ``source``,
``"stored"`` where the program was loaded from the store beside the
compile cache in ``load_s`` seconds, dcos_commons_tpu/utils/
stored_program.py, else ``"compiled"``: the host's ``trace_s`` and
``lower_s``, the backend's ``compile_s``, of it ``cache_read_s``, and
``store_s``, the entry's write), ``programs`` (the two programs counted
by ``stored`` / ``compiled``) and what compiled or loaded after
``ready`` (``compiles_after_ready``; an ``engine.compile`` span each in
GET /trace).  Each phase is a record of the sandbox's steplog under the
trace id the launch carried (``LAUNCH_TRACE``), so the scheduler's
/v1/debug/trace shows it between ``launch:`` and
``status:TASK_RUNNING`` (dcos_commons_tpu/trace/startup.py).
"""

import json
import math
import os
import shutil
import sys
import time
import urllib.parse

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
from dcos_commons_tpu.serve.migration import (  # noqa: E402
    HttpEngineClient,
    MigrationError,
    PrefillHandoff,
    SessionMigratedError,
    SessionSnapshot,
    drain_sessions,
)
from dcos_commons_tpu.trace import (  # noqa: E402
    StartupClock,
    TraceRecorder,
    chrome_json,
    to_text,
)

PROFILE_MAX_S = 30.0


def trace_reply(tracer: TraceRecorder, query: str = ""):
    """(body, content type) of GET /trace: the span ring as the text
    timeline, or as Chrome trace events for ``fmt=chrome``; a recorder
    of capacity 0 says so instead of rendering an empty timeline."""
    if not tracer.enabled:
        return (
            b"# trace recorder off: set SERVE_TRACE_CAPACITY > 0 in "
            b"this task's env\n", "text/plain",
        )
    if urllib.parse.parse_qs(query).get("fmt", [""])[0] == "chrome":
        return (
            chrome_json(tracer, service="serve").encode(),
            "application/json",
        )
    return to_text(tracer, service="serve").encode(), "text/plain"


def main() -> int:
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import config_from_env, init_params
    from dcos_commons_tpu.models.decode import (
        decode_attention_kernel,
        decode_attention_step,
        layer_plan,
    )
    from dcos_commons_tpu.serve.pool import PagedPoolModel
    from dcos_commons_tpu.utils import (
        claim_devices,
        enable_compilation_cache,
        restore_checkpoint,
    )

    # the imports end here, and the first phase that is this code's
    # begins; the listener goes on before the first trace
    startup = StartupClock()
    jax.monitoring.register_event_duration_secs_listener(startup.on_duration)
    # what this server runs on, before anything is built on it: a tpu:
    # pod that fell back to the CPU stops here instead of serving
    devices = claim_devices()
    startup.mark("backend_up")
    print(f"devices: {json.dumps(devices)}", flush=True)
    enable_compilation_cache()
    config = config_from_env(
        os.environ,
        # the dtype follows the platform JAX actually gave us
        dtype=jnp.bfloat16 if devices["platform"] == "tpu" else jnp.float32,
        remat=False,
    )
    max_len = int(os.environ.get("MAX_LEN", "256"))
    # unset SERVE_BATCH means a bare/dev launch; fall back to one
    # request rather than the deploy default 8 (see options.json
    # serving.batch description)
    # sdklint: disable=config-default-drift — dev fallback
    batch = int(os.environ.get("SERVE_BATCH", "1"))
    # the pool's decode rows default to the request cap; SERVE_SLOTS
    # decouples them (more concurrent residents than any one request may carry);
    # "" and 0 both mean "use SERVE_BATCH" (the options.json default)
    slots = int(os.environ.get("SERVE_SLOTS") or 0) or batch
    new_tokens = int(os.environ.get("MAX_NEW_TOKENS", "32"))

    params = init_params(config, jax.random.key(0))
    ckpt_dir = os.environ.get("CHECKPOINT_DIR", "")
    if ckpt_dir:
        # serve the TRAINED weights when a checkpoint tree exists
        # (the train pod's orbax-style output); params-only restore
        state, step = restore_checkpoint(ckpt_dir, {"params": params})
        if step is not None:
            params = state["params"]
            print(f"restored checkpoint step {step}", flush=True)

    # WEIGHT_DTYPE=int8 stores the layer matmul weights quantized
    # (models/quantize.py): decode streams half the weight bytes per
    # step — the dominant HBM term at small serving batches
    if os.environ.get("WEIGHT_DTYPE", "native") == "int8":
        from dcos_commons_tpu.models import quantize_params_int8

        params = jax.device_put(quantize_params_int8(params))
        print("weights quantized to int8 (per-channel)", flush=True)
    # the parameters are on the device, whatever dispatched them last
    jax.block_until_ready(params)
    startup.mark("weights")

    # TWO compiles cover every request: the paged arena's
    # prefill-chunk + decode-step (page tables, start positions, true
    # lengths, temps, seeds all traced) — novel requests never
    # recompile.  KV_DTYPE=int8 halves the cache bytes per
    # decode step: the lever for many resident requests on a full
    # chip (models/decode.py)
    prompt_len = max_len - new_tokens
    kv_dtype = os.environ.get("KV_DTYPE", "native")
    queue_timeout_s = float(os.environ.get("SERVE_QUEUE_TIMEOUT_S", "600"))
    sandbox = os.environ.get("SANDBOX", ".")
    stats_path = os.path.join(sandbox, SERVESTATS_NAME)
    paged = paged_config_from_env(os.environ)
    # the request/engine span ring, OFF unless this task's env asks
    # for it: the hot loop then meets only no-op spans
    tracer = TraceRecorder(
        capacity=int(os.environ.get("SERVE_TRACE_CAPACITY") or 0),
        service="serve",
    )

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            url = urllib.parse.urlsplit(self.path)
            if url.path == "/stats":
                self.send_response(200)
                self._finish(json.dumps(engine.stats()).encode())
            elif url.path == "/trace":
                self.send_response(200)
                self._finish(*trace_reply(tracer, url.query))
            else:
                self.send_error(404)

        def do_POST(self):
            if self.path == "/migrate":
                self._do_migrate()
                return
            if self.path == "/profile":
                self._do_profile()
                return
            if self.path != "/generate":
                self.send_error(404)
                return
            length = int(self.headers.get("Content-Length", 0))
            try:
                body = json.loads(self.rfile.read(length))
                if "collect" in body:
                    # the migration follow-up (router/core.py): the
                    # session moved HERE mid-generation and the
                    # router collects the finished reply by dest rid
                    result = [engine.collect(int(body["collect"]))]
                    payload = json.dumps({"tokens": result}).encode()
                    self.send_response(200)
                    self._finish(payload)
                    return
                rows = body["tokens"]
                if len(rows) > batch:
                    raise ValueError(
                        f"{len(rows)} prompts > server batch {batch}; "
                        "split the request"
                    )
                # rows may have MIXED lengths (per-row true_len); an
                # over-length prompt is refused, never silently
                # continued as a DIFFERENT (truncated) prompt
                if not rows:
                    raise ValueError("tokens must be non-empty")
                for row in rows:
                    if len(row) < 1:
                        raise ValueError("prompts must be non-empty")
                    if len(row) > prompt_len:
                        raise ValueError(
                            f"prompt length {len(row)} exceeds the "
                            f"server's context {prompt_len}"
                        )
                temp = float(body.get("temperature", 0.0))
                if not math.isfinite(temp) or temp < 0.0:
                    # json.loads accepts NaN/Infinity: a NaN must not
                    # reach the chip, where it poisons sampling
                    raise ValueError(
                        f"temperature must be finite and >= 0, got {temp}"
                    )
                n = int(body.get("max_new_tokens", new_tokens))
                if n < 1:
                    raise ValueError(
                        f"max_new_tokens must be >= 1, got {n}"
                    )
                n = min(n, new_tokens)
                eos = body.get("eos")
                if eos is not None:
                    eos = int(eos)
                    if not 0 <= eos < config.vocab:
                        raise ValueError(
                            f"eos must be in [0, {config.vocab}), got {eos}"
                        )
                clean_rows = [
                    [int(t) % config.vocab for t in row] for row in rows
                ]
                with tracer.span(
                    "request", track="req", rows=len(clean_rows)
                ) as request:
                    result = engine.submit(
                        clean_rows, n, temperature=temp, eos_id=eos,
                        trace_parent=request,
                    )
                payload = json.dumps({"tokens": result}).encode()
                self.send_response(200)
            except SessionMigratedError as e:
                # a redirect, not a failure: the session finished on
                # another pod — 409 names it and the router follows
                # with a collect request (router/frontdoor.py)
                payload = json.dumps({
                    "error": str(e),
                    "rid": e.rid,
                    "migrated_to": e.moved_to,
                    "dest_rid": e.dest_rid,
                }).encode()
                self.send_response(409)
            except QueueTimeoutError as e:
                # saturation, NOT caller error: the request never got
                # a KV slot in time — clients/load generators back off
                payload = json.dumps({"error": str(e)}).encode()
                self.send_response(503)
            except Exception as e:  # noqa: BLE001 — surface to client
                payload = json.dumps({"error": str(e)}).encode()
                self.send_response(400)
            self._finish(payload)

        def _finish(self, payload: bytes,
                    content_type: str = "application/json") -> None:
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def _do_profile(self) -> None:
            """The program's own profiler entry: hold a jax.profiler
            session open for ``seconds`` over whatever the engine is
            serving, and name the directory it wrote.  One session a
            process: 409 while another (this verb's, or one a harness
            opened in this process) is under way."""
            length = int(self.headers.get("Content-Length", 0))
            out_dir = os.path.join(sandbox, "profile")
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
                seconds = float(body.get("seconds", 5.0))
                if not 0.0 < seconds <= PROFILE_MAX_S:
                    raise ValueError(
                        f"seconds must be in (0, {PROFILE_MAX_S:g}], "
                        f"got {seconds}"
                    )
            except Exception as e:  # noqa: BLE001 — surface to client
                self.send_response(400)
                self._finish(json.dumps({"error": str(e)}).encode())
                return
            try:
                jax.profiler.start_trace(out_dir)
                try:
                    # the session is this call's: drop the capture
                    # before it (stop_trace writes a new timestamped
                    # run, and nothing else prunes the sandbox)
                    shutil.rmtree(out_dir, ignore_errors=True)
                    time.sleep(seconds)
                finally:
                    jax.profiler.stop_trace()
            except RuntimeError as e:
                # jax's own one-session rule, on either edge
                self.send_response(409)
                self._finish(json.dumps({"error": str(e)}).encode())
                return
            self.send_response(200)
            self._finish(json.dumps(
                {"dir": os.path.abspath(out_dir), "seconds": seconds}
            ).encode())

        def _do_migrate(self) -> None:
            """The DCN lane's HTTP leg: this pod as a migration
            DESTINATION (serve/migration.py HttpEngineClient drives
            it verb by verb).  409 = the engine refused (budget,
            geometry, unknown rid) — the source aborts cleanly and
            resumes; 400 = malformed request."""
            length = int(self.headers.get("Content-Length", 0))
            try:
                body = json.loads(self.rfile.read(length))
                verb = body.get("verb")
                if verb == "splice":
                    snap = SessionSnapshot.from_wire(body["snapshot"])
                    dest_rid = engine.splice(snap)
                    payload = json.dumps(
                        {"dest_rid": dest_rid}
                    ).encode()
                elif verb == "activate":
                    engine.activate(int(body["rid"]))
                    payload = json.dumps({"ok": True}).encode()
                elif verb == "abort":
                    engine.abort_splice(int(body["rid"]))
                    payload = json.dumps({"ok": True}).encode()
                elif verb == "drain":
                    # source-side one-shot: move every live session
                    # to the named peers (drain-with-migration — the
                    # front door's /drain?to= and the scale-in plan
                    # both drive this instead of waiting generations
                    # out).  Sessions that cannot move are reported
                    # ok=false and finish here under the legacy drain.
                    dests = {
                        str(peer): HttpEngineClient(str(peer),
                                                    str(addr))
                        for peer, addr in dict(
                            body.get("dests") or {}
                        ).items()
                    }
                    report = drain_sessions(
                        engine, dests,
                        log=lambda msg: print(msg, flush=True),
                    )
                    payload = json.dumps({"report": report}).encode()
                else:
                    raise ValueError(f"unknown migrate verb {verb!r}")
                self.send_response(200)
            except MigrationError as e:
                payload = json.dumps({"error": str(e)}).encode()
                self.send_response(409)
            except Exception as e:  # noqa: BLE001 — surface to client
                payload = json.dumps({"error": str(e)}).encode()
                self.send_response(400)
            self._finish(payload)

    # a RELAUNCH reuses the sandbox: a stale ready file from the
    # previous incarnation must not pass readiness while we are cold
    try:
        os.remove("ready")
    except OSError:
        pass
    # bind BEFORE building the engine: the port actually bound is
    # annotated into the engine's very first stats snapshot, which is
    # what /v1/endpoints advertises for `advertise: true` ports — and
    # a hard bind failure still fails readiness, not the first client
    port = int(os.environ.get("PORT_HTTP", "0"))
    try:
        server = ThreadingHTTPServer(("0.0.0.0", port), Handler)
    except OSError:
        # the scheduler-assigned port is taken on this machine (a
        # simulated fleet runs many "hosts" on one box): bind an
        # ephemeral port and ADVERTISE it instead of crash-looping
        server = ThreadingHTTPServer(("0.0.0.0", 0), Handler)
        print(
            f"port {port} in use; bound {server.server_address[1]} "
            "instead (advertised via servestats)",
            flush=True,
        )
    bound_port = int(server.server_address[1])

    # SERVE_ROLE (ISSUE 16) declares this pod's place in a
    # disaggregated topology; a prefill pod with SERVE_DECODE_PODS
    # peers hands finished prompts to the decode pool over the
    # /migrate lane, and degrades to unified when it cannot.
    role = (os.environ.get("SERVE_ROLE") or "").strip() or "unified"
    handoff = None
    if role == "prefill":
        decode_pods = {}
        for item in os.environ.get("SERVE_DECODE_PODS",
                                   "").split(","):
            if "=" not in item:
                continue
            peer, addr = item.split("=", 1)
            peer, addr = peer.strip(), addr.strip()
            if peer and addr:
                decode_pods[peer] = HttpEngineClient(peer, addr)
        if decode_pods:
            handoff = PrefillHandoff(
                lambda: decode_pods,
                log=lambda msg: print(msg, flush=True),
            )
    pool = PagedPoolModel(
        config, params, slots, max_len, paged.page_tokens,
        paged.pages, paged.chunk_tokens, kv_dtype=kv_dtype,
        # a pod that hands its prompts over decodes only when the
        # hand-off fails: its chunks carry no decode step
        riders=handoff is None,
    )
    engine = PagedEngine(
        pool.prefill_chunk, pool.decode, slots, max_len,
        prompt_len,
        page_tokens=paged.page_tokens, pages=paged.pages,
        chunk_tokens=paged.chunk_tokens,
        prefix_cache=paged.prefix_cache, layout=pool.layout,
        queue_timeout_s=queue_timeout_s, stats_path=stats_path,
        role=role, read_page=pool.export_page,
        write_page=pool.import_page, handoff=handoff,
        # this device half carries a token on the device, so the
        # loop runs one call ahead of it
        resolve_decode_fn=pool.resolve_decode,
        # and where its chunk program carries the decode step, a tick
        # with a chunk is one device program
        chunk_riders=pool.chunk_riders,
        device_counters=pool.loop_counters,
        log=lambda msg: print(msg, flush=True),
        extra_stats={"http_port": bound_port},
        annotate=jax.profiler.TraceAnnotation, tracer=tracer,
    )
    startup.mark("build")
    warm_t0 = time.monotonic()
    with startup.warm():
        pool.warm()
    shape = (
        f"paged KV: {paged.pages} pages x {paged.page_tokens} "
        f"tokens, {slots} rows of {pool.pages_per_row} table "
        f"entries, {paged.chunk_note}, "
        f"prefix cache {'on' if paged.prefix_cache else 'off'}"
    )
    # which path a decode step's attention takes: the page walk that
    # reads live pages in place, or the gather of every row's whole
    # table
    decode_attention = (
        "kernel" if decode_attention_kernel(config, pool.cache)
        else "gather"
    )
    # /stats and the sandbox snapshot state what answers the requests
    # and what warming it cost (XLA compile, or persistent-cache read)
    engine.annotate_stats(
        platform=devices["platform"],
        device_kind=devices["device_kind"],
        device_count=devices["device_count"],
        model={
            "vocab": config.vocab, "d_model": config.d_model,
            "n_layers": config.n_layers, "n_heads": config.n_heads,
            "n_kv_heads": config.n_kv_heads, "d_ff": config.d_ff,
            "head_dim": config.head_dim,
            "dtype": jnp.dtype(config.dtype).name,
            "attention": config.attention,
            "decode_attention": decode_attention,
            # what a step of the kernel's page walk takes, by the
            # kernel's name in a trace ({} on the gather path)
            "decode_attention_step": decode_attention_step(
                config, pool.cache
            ),
            "window_size": config.window_size,
            "chunk_size": config.chunk_size,
            # the layer pattern, and how a mixture's tokens choose
            "layer_types": [op for op, _ffn in config.layer_kinds],
            # how both programs walk it: [lead, period, reps, tail]
            "layer_plan": list(layer_plan(config.layer_kinds)),
            "n_dense_layers": config.n_dense_layers,
            "n_experts": config.n_experts,
            "moe_top_k": config.moe_top_k,
            "moe_d_ff": config.moe_d_ff or config.d_ff,
            "moe_routing": config.moe_score,
            "conv_l_cache": config.conv_l_cache,
            "n_shared_experts": config.n_shared_experts,
            # window layers among full ones: the window, a row's ring
            # in their arena, and the history pool's pages as asked
            "sliding_window": config.sliding_window,
            "kv_ring_pages": pool.layout.ring_pages,
            "kv_pages": paged.pages,
            "attention_gate": config.attention_gate,
            "sandwich_norm": config.sandwich_norm,
            "model_config": os.environ.get("MODEL_CONFIG", ""),
            # what the chunk's width was chosen from, where the code
            # chose it (serve/paging.py chosen_chunk_tokens)
            **paged.chunk_stats,
        },
        prefill_chunk_source=paged.chunk_source,
        warm_s=round(time.monotonic() - warm_t0, 2),
        # launch -> ready by phase, warm by program; the same dict all
        # along, so its last phase and the after-ready counts show
        startup=startup.stats,
    )
    with open("ready", "w") as f:
        f.write("warm\n")
    startup.ready(tracer)
    print(
        f"warm: continuous batching ({shape}) "
        f"(prompts<={prompt_len}, <={new_tokens} new) on "
        f"{server.server_address[1]}",
        flush=True,
    )
    server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
