"""frameworks/jax serving pod end to end: deploy -> warm -> generate.

A REAL serve_worker process deploys through the control plane; the
readiness check ("test -f ready") gates the deploy plan on the model
being warm, the VIP surfaces the backend, and POST /generate answers
with deterministic greedy continuations.  Train AND serve run through
one scheduler — the reference's model has no data plane at all
(SURVEY: "the workloads are whatever the service YAML launches").
"""

import json
import os
import time
import urllib.error
import urllib.request

from dcos_commons_tpu.agent import LocalProcessAgent
from dcos_commons_tpu.offer.inventory import TpuHost
from dcos_commons_tpu.scheduler import SchedulerBuilder, SchedulerConfig
from dcos_commons_tpu.specification import from_yaml_file
from dcos_commons_tpu.storage import MemPersister

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_ENV = {
    "FRAMEWORK_NAME": "tiny-serve",
    "JAX_FRAMEWORK_DIR": os.path.join(REPO, "frameworks", "jax"),
    "VOCAB": "64",
    "D_MODEL": "32",
    "N_LAYERS": "2",
    "SEQ_LEN": "64",
    "MAX_LEN": "48",
    "MAX_NEW_TOKENS": "8",
    # exactness assertions below need batch 1 (the overflow 400) and
    # the exact cache; int8/batched serving has its own coverage
    "SERVE_BATCH": "1",
    "KV_DTYPE": "native",
}


def test_inference_pod_serves_generate(tmp_path):
    spec = from_yaml_file(
        os.path.join(REPO, "frameworks", "jax", "svc_serve.yml"), TINY_ENV
    )
    builder = SchedulerBuilder(
        spec,
        SchedulerConfig(
            sandbox_root=str(tmp_path / "sbx"), backoff_enabled=False
        ),
        MemPersister(),
    )
    from dcos_commons_tpu.offer.inventory import SliceInventory

    builder.set_inventory(SliceInventory([TpuHost(
        host_id="h0", hostname="127.0.0.1", generation="v5e",
        grid=(0, 0), chip_block=(1, 1), cpus=8.0, memory_mb=16384,
        # a high range other dev-box services are unlikely to hold
        # (port 10000 is taken on the CI host)
        ports=((23100, 23200),),
    )]))
    agent = LocalProcessAgent(str(tmp_path / "sbx"))
    builder.set_agent(agent)
    scheduler = builder.build()
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            scheduler.run_cycle()
            if scheduler.deploy_manager.get_plan().is_complete:
                break
            time.sleep(0.2)
        # readiness ("test -f ready") gates this: COMPLETE means WARM
        assert scheduler.deploy_manager.get_plan().is_complete, (
            open(tmp_path / "sbx" / "server-0-api" / "stderr").read()[-500:]
            if (tmp_path / "sbx" / "server-0-api" / "stderr").exists()
            else "no stderr"
        )
        info = scheduler.state_store.fetch_task("server-0-api")
        port = int(info.env["PORT_HTTP"])

        def post(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        out = post({"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8})
        assert len(out["tokens"]) == 1
        assert len(out["tokens"][0]) == 8
        assert all(0 <= t < 64 for t in out["tokens"][0])
        # the SERVED continuation equals direct generate() on the
        # EXACT prompt — the right-pad + true_len path changes nothing
        import jax
        import jax.numpy as jnp

        from dcos_commons_tpu.models import (
            TransformerConfig,
            generate,
            init_params,
        )

        cfg = TransformerConfig(
            vocab=64, d_model=32, n_layers=2, n_heads=8, n_kv_heads=8,
            d_ff=1408, max_seq=64, dtype=jnp.float32, remat=False,
        )
        oracle = generate(
            cfg, init_params(cfg, jax.random.key(0)),
            jnp.asarray([[1, 2, 3, 4]], jnp.int32), max_new_tokens=8,
        )
        assert out["tokens"][0] == [int(t) for t in oracle[0]]
        # greedy is deterministic: same prompt, same continuation
        again = post({"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8})
        assert again["tokens"] == out["tokens"]
        # a different prompt (almost surely) diverges
        other = post({"tokens": [[9, 8, 7, 6, 5]], "max_new_tokens": 8})
        assert len(other["tokens"][0]) == 8
        # malformed requests get clean 400s, never silent truncation:
        # batch overflow, over-length prompt, empty prompt
        for bad in (
            {"tokens": [[1], [2]]},                 # > server batch
            {"tokens": [list(range(41))]},          # > context (40)
            {"tokens": [[]]},                       # empty prompt
            # json.dumps emits bare NaN and the server's json.loads
            # accepts it: a NaN group key would stall the batcher
            {"tokens": [[1, 2]], "temperature": float("nan")},
            {"tokens": [[1, 2]], "temperature": float("inf")},
            {"tokens": [[1, 2]], "temperature": -1.0},
        ):
            try:
                post(bad)
                raise AssertionError(f"should have failed: {bad}")
            except urllib.error.HTTPError as e:
                assert e.code == 400, bad
        # the engine's own timeline (ISSUE 24): the loop's counters
        # ride /stats; the span ring is off unless SERVE_TRACE_CAPACITY asks
        def get(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30
            ) as resp:
                return resp.read().decode()

        stats = json.loads(get("/stats"))
        loop = stats["loop"]
        assert loop["requests_timed"] == 3 and loop["decode_calls"] >= 7
        # the YAML states no chunk width: the code chose it from the
        # model's sizes (a dense model's 256, held to MAX_LEN 48) and
        # says so, with the two counts it chose from
        assert stats["prefill_chunk_tokens"] == 48
        assert stats["prefill_chunk_source"] == "model"
        assert stats["model"]["chunk_read_weights"] \
            == stats["model"]["chunk_token_weights"] > 0
        assert loop["phase_s"]["decode_call"] > 0
        assert "recorder off" in get("/trace")
        assert "recorder off" in get("/trace?fmt=chrome")

        # the program's own profiler entry: a capture of the live
        # process lands in the sandbox; a second session is refused
        # while the first is open, and so is a silly length
        def profile(seconds):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/profile",
                data=json.dumps({"seconds": seconds}).encode(),
                method="POST",
            )
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        import glob
        import threading

        first = {}
        opener = threading.Thread(
            target=lambda: first.update(reply=profile(1.5))
        )
        opener.start()
        time.sleep(0.5)
        assert profile(0.2)[0] == 409
        post({"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8})
        opener.join(timeout=60)
        code, reply = first["reply"]
        assert code == 200, reply
        assert reply["dir"] == str(
            tmp_path / "sbx" / "server-0-api" / "profile"
        )
        runs = os.path.join(reply["dir"], "plugins", "profile", "*")
        assert glob.glob(os.path.join(runs, "*.xplane.pb"))
        assert profile(31)[0] == 400
        # the sandbox keeps the last capture only
        assert profile(0.2)[0] == 200
        assert len(glob.glob(runs)) == 1
        # VIP discovery lists the live backend
        from dcos_commons_tpu.http.api import SchedulerApi

        code, body = SchedulerApi(scheduler).get_endpoint("vip:inference")
        assert code == 200
        assert any(str(port) in addr for addr in body["address"])
    finally:
        agent.shutdown()


def test_continuous_batching_merges_concurrent_clients(tmp_path):
    """SERVE_BATCH > 1: concurrent single-prompt clients — of MIXED
    prompt lengths — share the pool (each rides its own row,
    admitted mid-flight; per-row true_len/temperature/seed) with each
    client's own correct greedy continuation — concurrency must not
    change any answer.

    Runs the FULL serving quantization stack (int8 weights + int8 KV,
    models/quantize.py): every assertion here is served-vs-served
    self-consistency, so the quantized pod must hold them all."""
    import threading

    env = {
        **TINY_ENV, "SERVE_BATCH": "4",
        "WEIGHT_DTYPE": "int8", "KV_DTYPE": "int8",
    }
    spec = from_yaml_file(
        os.path.join(REPO, "frameworks", "jax", "svc_serve.yml"), env
    )
    builder = SchedulerBuilder(
        spec,
        SchedulerConfig(
            sandbox_root=str(tmp_path / "sbx"), backoff_enabled=False
        ),
        MemPersister(),
    )
    from dcos_commons_tpu.offer.inventory import SliceInventory

    builder.set_inventory(SliceInventory([TpuHost(
        host_id="h0", hostname="127.0.0.1", generation="v5e",
        grid=(0, 0), chip_block=(1, 1), cpus=8.0, memory_mb=16384,
        ports=((23100, 23200),),
    )]))
    agent = LocalProcessAgent(str(tmp_path / "sbx"))
    builder.set_agent(agent)
    scheduler = builder.build()
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            scheduler.run_cycle()
            if scheduler.deploy_manager.get_plan().is_complete:
                break
            time.sleep(0.2)
        assert scheduler.deploy_manager.get_plan().is_complete
        info = scheduler.state_store.fetch_task("server-0-api")
        port = int(info.env["PORT_HTTP"])

        def post(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        # sequential oracle answers, one per distinct prompt —
        # DELIBERATELY mixed lengths: heterogeneous clients must merge
        prompts = [[1, 2, 3], [4, 5], [7, 8, 9, 6, 2], [3]]
        expected = [
            post({"tokens": [p], "max_new_tokens": 6})["tokens"][0]
            for p in prompts
        ]
        # now the same four prompts CONCURRENTLY: same answers
        results = [None] * len(prompts)
        errors = []

        def client(i):
            try:
                results[i] = post(
                    {"tokens": [prompts[i]], "max_new_tokens": 6}
                )["tokens"][0]
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert results == expected
        # ONE multi-row MIXED-length request pins the per-row lens
        # path deterministically (concurrent merging above depends on
        # thread timing)
        mixed = post({
            "tokens": [prompts[0], prompts[1]], "max_new_tokens": 6,
        })
        assert mixed["tokens"] == [expected[0], expected[1]]
        # the worker's log shows concurrent rows sharing the pool
        stdout_path = tmp_path / "sbx" / "server-0-api" / "stdout"
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if "continuous-batch:" in stdout_path.read_text():
                break
            time.sleep(0.2)
        assert "continuous-batch:" in stdout_path.read_text(), (
            "concurrent clients never shared a pool decode step"
        )
        # the serving gauges are live: /stats on the worker reports
        # the pool shape and the tokens the run produced
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/stats", method="GET"
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            stats = json.loads(resp.read())
        assert stats["slots"] == 4
        assert stats["requests_completed"] >= 7
        assert stats["tokens_out"] >= 7 * 6
        assert 0.0 <= stats["kv_occupancy"] <= 1.0
        # and the SCHEDULER sees them: the worker mirrors the gauges
        # to its sandbox, the agent surfaces the file, and
        # /v1/debug/serving merges per task
        from dcos_commons_tpu.http.api import SchedulerApi

        def scheduler_sees():
            code, body = SchedulerApi(scheduler).debug_serving()
            assert code == 200
            return body["serving"].get("server-0-api")

        deadline = time.monotonic() + 15
        merged = scheduler_sees()
        while (not merged or merged.get("requests_completed", 0) < 7) \
                and time.monotonic() < deadline:
            time.sleep(0.5)  # the worker rewrites servestats ~1/s
            merged = scheduler_sees()
        assert merged and merged["slots"] == 4
        assert merged["requests_completed"] >= 7
    finally:
        agent.shutdown()
