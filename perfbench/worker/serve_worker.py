"""The benchmark's worker entry: `svc_serve.yml` runs
``python $JAX_FRAMEWORK_DIR/serve_worker.py`` and the benchmark points
``JAX_FRAMEWORK_DIR`` here.  This file changes nothing of the program:
it hands it the benchmark's seeded weights in place of its own
``init_params`` (so the reference can be checked against weights the
program did not make), puts profiler spans around the program's two
device calls, opens a small control port of its own (device memory,
compile events, set-up times, profiler start and stop), and then calls
the program's ``serve_worker.main()`` as it is.

What it needs of the program is listed by name in PROGRAM_NAMES (and
in PERF.md, section 3).  A program that lacks one stops here with a
message that says which, not later with an AttributeError.
"""

import functools
import importlib.util
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

STARTED = time.time()
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, CHECKOUT)

PROGRAM_NAMES = (
    "frameworks/jax/serve_worker.py: main()",
    "dcos_commons_tpu.models.init_params(config, key), looked up by "
    "main() when it runs, its tree as the configuration's family states "
    "it (families/<family>/weight_specs.py), and config.dtype",
    "dcos_commons_tpu.serve.pool.PagedPoolModel.prefill_chunk and .decode",
)

CONTROL_FILE = "perfbench_control.json"
# Where /trace/stop writes the profiler session's bytes, as they come,
# under the directory /trace/start named: the path jax.profiler's own
# stop would have put them at.  That stop also exports a trace viewer's
# JSON, which no reader of the benchmark opens and which was three
# quarters of a stop's seconds (PERF.md section 6, PR 34).
PROFILE_FILE = os.path.join("plugins", "profile", "perfbench",
                            "worker.xplane.pb")
# every compile or cache read the process makes, by the host's clock
COMPILE_EVENTS = []
# seconds from this process's start to each step of its set-up
SETUP_TIMES = {}


class ProgramChanged(SystemExit):
    def __init__(self, what: str):
        super().__init__(
            f"perfbench worker entry: {what}.  The benchmark reaches the "
            "program through: " + "; ".join(PROGRAM_NAMES) + ".  A PR that "
            "moves one of these needs a benchmark PR beside it."
        )


def _seeded_weights():
    """Replace the program's weight initialisation by the benchmark's,
    after checking, leaf by leaf, that the benchmark's tree is the one
    the program's own ``init_params`` would have built."""
    import dcos_commons_tpu.models as models
    from perfbench.harness.manifest import family_of
    from perfbench.harness.weights import make_weights, tree_differences

    if not callable(getattr(models, "init_params", None)):
        raise ProgramChanged("dcos_commons_tpu.models has no init_params")
    program_init = models.init_params
    with open(os.environ["PERFBENCH_CONFIG_FILE"]) as f:
        model = json.load(f)
    family = family_of(os.environ["PERFBENCH_CONFIG_FILE"])
    specs = family.weight_specs(model)
    seed = int(os.environ["PERFBENCH_SEED"])

    def init_params(config, key):
        import jax

        started = time.monotonic()
        SETUP_TIMES["backend_up"] = time.time() - STARTED
        # shapes and dtypes only: nothing is built on the device
        theirs = jax.eval_shape(functools.partial(program_init, config), key)
        differences = tree_differences(specs, config.dtype, theirs)
        if differences:
            raise ProgramChanged(
                "the program's parameter tree is not the one "
                f"{family.directory}/weight_specs.py states: "
                + "; ".join(differences[:8])
            )
        tree = make_weights(specs, seed, config.dtype)
        jax.block_until_ready(tree)
        SETUP_TIMES["weights_built"] = time.time() - STARTED
        print(
            f"perfbench weights: seed {seed}, "
            f"{time.monotonic() - started:.2f}s", flush=True,
        )
        return tree

    models.init_params = init_params


def _annotate_device_calls():
    """Host spans, on the profiler's clock, around the engine's two
    public calls into the device half, and around every blocking
    ``jax.device_get`` made inside one of them (named after the call
    it is in).  Nothing private to the program is touched: if the
    program fetches another way the fetch spans are simply absent and
    the trace reduction charges the whole call."""
    import jax
    from dcos_commons_tpu.serve import pool

    cls = getattr(pool, "PagedPoolModel", None)
    for name in ("prefill_chunk", "decode"):
        if not callable(getattr(cls, name, None)):
            raise ProgramChanged(
                f"dcos_commons_tpu.serve.pool.PagedPoolModel.{name} is gone"
            )
    inside = threading.local()

    def spanned(name, fn):
        def call(*args, **kwargs):
            inside.call = name
            try:
                with jax.profiler.TraceAnnotation(name):
                    return fn(*args, **kwargs)
            finally:
                inside.call = None
        return call

    device_get = jax.device_get

    def spanned_device_get(*args, **kwargs):
        name = getattr(inside, "call", None)
        if name is None:
            return device_get(*args, **kwargs)
        with jax.profiler.TraceAnnotation(name + ":fetch"):
            return device_get(*args, **kwargs)

    jax.device_get = spanned_device_get
    cls.prefill_chunk = spanned("prefill_chunk", cls.prefill_chunk)
    cls.decode = spanned("decode", cls.decode)


def _control_server():
    import jax

    def on_duration(event, duration, **_kwargs):
        if event.endswith("backend_compile_duration") or \
                "cache_retrieval" in event:
            COMPILE_EVENTS.append(
                {"t": time.time(), "event": event, "s": duration}
            )

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    profile = {}

    class Control(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def _reply(self, payload):
            data = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            peaks = []
            for device in jax.local_devices():
                stats = device.memory_stats() or {}
                peaks.append(int(stats.get("peak_bytes_in_use", 0)))
            self._reply({
                "memory_peak_bytes": max(peaks),
                "compile_events": list(COMPILE_EVENTS),
                "started": STARTED, "setup_times": dict(SETUP_TIMES),
                "t": time.time(),
            })

        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            reply = {}
            if self.path == "/trace/start":
                from jax._src.lib import _profiler

                # the backend before the session, as jax.profiler's own
                # start has it, or the TPU's tracer records nothing
                jax.devices()
                profile["path"] = os.path.join(body["dir"], PROFILE_FILE)
                profile["session"] = _profiler.ProfilerSession()
            elif self.path == "/trace/stop":
                data = profile.pop("session").stop()
                os.makedirs(os.path.dirname(profile["path"]), exist_ok=True)
                with open(profile["path"], "wb") as f:
                    f.write(data)
                reply["profile_bytes"] = len(data)
            else:
                self.send_error(404)
                return
            self._reply(dict(reply, ok=True, t=time.time()))

    server = ThreadingHTTPServer(("127.0.0.1", 0), Control)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    tmp = CONTROL_FILE + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"port": server.server_address[1]}, f)
    os.replace(tmp, CONTROL_FILE)
    return server


def main() -> int:
    _seeded_weights()
    _annotate_device_calls()
    _control_server()
    SETUP_TIMES["entry_loaded"] = time.time() - STARTED
    spec = importlib.util.spec_from_file_location(
        "program_serve_worker",
        os.path.join(CHECKOUT, "frameworks", "jax", "serve_worker.py"),
    )
    program = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(program)
    if not callable(getattr(program, "main", None)):
        raise ProgramChanged("frameworks/jax/serve_worker.py has no main()")
    return program.main()


if __name__ == "__main__":
    raise SystemExit(main())
