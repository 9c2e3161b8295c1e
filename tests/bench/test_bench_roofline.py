"""The first family's needs (``perfbench/families/gqa_decoder/
needs.py``) and the roofline share against numbers worked by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from perfbench.harness import readers, roofline  # noqa: E402
from toyroot import family  # noqa: E402

needs_of = family().needs


# a dense configuration at Mistral-7B-v0.3's published widths, 16 layers
# deep (no such file is in the benchmark yet; the functions read sizes)
MISTRAL = {
    "hidden_size": 4096, "intermediate_size": 14336,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
    "num_hidden_layers": 16, "vocab_size": 32768,
}


def config(name):
    if name == "mistral-7b-v0.3":
        return MISTRAL
    with open(os.path.join(REPO, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# by hand, per layer: q and o 4096x4096 each, k and v 4096x1024 each
ATTN = 2 * 4096 * 4096 + 2 * 4096 * 1024          # 41,943,040
FFN = 3 * 4096 * 14336                            # 176,160,768
KV_TOKEN_LAYER = 2 * 8 * 128 * 2                  # bytes: k and v, bf16


def test_mistral_decode_tick_needs():
    m = config("mistral-7b-v0.3")
    needs = needs_of.decode_tick(m, live_rows=40, live_tokens=20000)
    weights = (16 * (ATTN + FFN) + 32768 * 4096) * 2
    assert weights == 7_247_757_312
    assert needs["weight_bytes"] == weights
    assert needs["kv_bytes"] == 16 * 20040 * KV_TOKEN_LAYER
    acts = 16 * 40 * 4096 * 2 * 2
    assert needs["bytes"] == weights + needs["kv_bytes"] + acts
    flops = 16 * (2 * 40 * (ATTN + FFN) + 4 * 20000 * 32 * 128) \
        + 2 * 40 * 32768 * 4096
    assert needs["flops"] == flops
    seconds, bound = roofline.least_seconds(needs, PEAK)
    assert bound == "bytes"
    assert seconds == pytest.approx(needs["bytes"] / 819e9)
    assert 0.0100 < seconds < 0.0110


def test_mixtral_decode_tick_reads_the_experts_its_rows_choose():
    m = config("mixtral-8x7b-v0.1")
    # one row chooses 2 of 8 experts; forty rows choose all but 8*0.75^40
    assert needs_of.experts_touched(m, 1) == pytest.approx(2.0)
    assert needs_of.experts_touched(m, 40) == pytest.approx(
        8 * (1 - 0.75 ** 40)
    )
    one = needs_of.decode_tick(m, live_rows=1, live_tokens=500)
    router = 4096 * 8
    assert one["weight_bytes"] == pytest.approx(
        (3 * (ATTN + router + 2 * FFN) + 32000 * 4096) * 2
    )
    full = needs_of.decode_tick(m, live_rows=64, live_tokens=30000)
    assert full["weight_bytes"] == pytest.approx(
        (3 * (ATTN + router + 8 * FFN) + 32000 * 4096) * 2, rel=1e-6
    )
    # operations: each row runs its two experts, not all eight
    assert full["flops"] == pytest.approx(
        3 * (2 * 64 * (ATTN + router + 2 * FFN) + 4 * 30000 * 32 * 128)
        + 2 * 64 * 32000 * 4096
    )
    seconds, bound = roofline.least_seconds(full, PEAK)
    assert bound == "bytes" and 0.0105 < seconds < 0.0120


@pytest.mark.parametrize("name,layers,vocab,experts", [
    ("mistral-7b-v0.3", 16, 32768, 0),
    ("mixtral-8x7b-v0.1", 3, 32000, 8),
])
def test_prefill_chunk_needs(name, layers, vocab, experts):
    m = config(name)
    needs = needs_of.prefill_chunk(m, chunk_tokens=64, context_tokens=1024)
    per_layer = ATTN + (4096 * experts) + (experts or 1) * FFN
    weights = (layers * per_layer + vocab * 4096) * 2
    assert needs["weight_bytes"] == pytest.approx(weights, rel=1e-6)
    assert needs["kv_bytes"] == layers * (1024 + 64) * KV_TOKEN_LAYER
    active = ATTN + 4096 * experts + (2 if experts else 1) * FFN
    attended = 64 * (1024 + 32)
    assert needs["flops"] == pytest.approx(
        layers * (2 * 64 * active + 4 * attended * 32 * 128)
        + 2 * 64 * vocab * 4096
    )
    assert roofline.least_seconds(needs, PEAK)[1] == "bytes"


def test_roofline_share_is_least_time_over_measured_time():
    m = config("mistral-7b-v0.3")
    needs = needs_of.decode_tick(m, 40, 20000)
    least = needs["bytes"] / 819e9
    run = {"peaks": PEAK,
           "trace": {"programs": {"jit__decode": {"median_ms": 50.0}}}}
    share = readers.roofline_share(run, needs, "jit__decode")
    assert share == pytest.approx(100 * least / 0.050)
    assert 0 < share < 100
    assert readers.roofline_share({"peaks": PEAK, "trace": None}, needs,
                                  "jit__decode") is None


def test_peaks_table_names_its_source():
    with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert all("source" in entry for entry in peaks.values())
