"""A throw-away benchmark root for the tests: the real BENCHMARK.json
and the real files under ``perfbench/``, plus a toy configuration, a
toy mix, a cell of the two, one more per-layer metric with a reader of
its own and one (``decode_tick_ms.toy``) that an existing reader
serves under a new suffix, all ADDED as new files and new entries.  No file that is
there is edited, which is what a later PR is held to."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))

TOY_MODEL = {
    "model_type": "mixtral", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "num_local_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
}
TOY_SIZING = {
    "MAX_LEN": 96, "MAX_NEW_TOKENS": 16, "SERVE_SLOTS": 4,
    "SERVE_BATCH": 1, "KV_PAGES": 64,
}
TOY_OPEN = {
    "why": "toy open loop for the tests", "loop": "open",
    "prompt_tokens": {"kind": "lognormal", "median": 24, "sigma": 0.6,
                      "min": 4, "max": 72},
    "output_tokens": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
    "pairing_seed": 7, "ramp_s": 1, "tail_s": 5, "drain_limit_s": 60,
    "request_timeout_s": 120, "clients": 16, "trace_after_s": 0.5,
    "trace_s": 1, "check_draw": 2, "check_tokens": 4,
    "sizing_env": TOY_SIZING,
}
TOY_LIMITS = {"max_gap": 1e-3, "mean_gap": 1e-4, "mismatch_share": 0.02}
TOY_READER = '''"""A per-layer metric added by a file alone."""


def read(run):
    return float(len(run["judged"]))
'''


def build(root: str) -> str:
    """Make the throw-away root under ``root``; returns it."""
    shutil.copytree(
        os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    bench = os.path.join(root, "perfbench")

    def put(relative, payload):
        path = os.path.join(bench, relative)
        assert not os.path.exists(path), f"{relative} would be edited"
        with open(path, "w") as f:
            if isinstance(payload, str):
                f.write(payload)
            else:
                json.dump(payload, f)

    put("configs/toy-moe.json", TOY_MODEL)
    put("traffic/toy-open.json", TOY_OPEN)
    put("cells/toy.open.json",
        {"rate_rps": 4.0, "correct_limits": TOY_LIMITS})
    put("layer_metrics/toy_judged_requests.py", TOY_READER)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "toy-moe", "source": "tests", "reduced": [],
        "file": "perfbench/configs/toy-moe.json", "why": "toy",
    })
    manifest["workloads"].append(
        {"name": "toy.open", "config": "toy-moe", "traffic": "toy-open",
         "chips": 1, "why": "toy"}
    )
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append("toy.open")
    manifest["per_layer"] += [
        {"name": "toy_judged_requests", "unit": "requests",
         "better": "higher", "source": "program_counter",
         "layer": "load generator", "moves": "setup_s",
         "workloads": ["toy.open"]},
        # a quantity split by a new suffix: read by decode_tick_ms.py
        {"name": "decode_tick_ms.toy", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "device step",
         "moves": "setup_s", "workloads": ["toy.open"]},
    ]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
