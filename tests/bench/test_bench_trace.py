"""The reduction from a trace to numbers, on a small trace recorded on
the chip (one prefill chunk and one decode tick of `mistral7b.chat`,
`recorded_trace.json`) against the same quantities worked out the
slow way, and on a hand-made trace whose answers are plain."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from perfbench.harness import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(recorded):
    return trace_reduce.reduce(recorded)


def timeline(recorded, step=1000.0):
    """Busy or not, microsecond by microsecond."""
    device = recorded["devices"]["/device:TPU:0"]
    every = device["ops"] + device["modules"] + recorded["host"]
    t0 = min(e[1] for e in every)
    t1 = max(e[1] + e[2] for e in every)
    cells = [False] * (int((t1 - t0) / step) + 1)
    for _name, start, dur in device["ops"]:
        for i in range(int((start - t0) / step), int((start + dur - t0) / step)):
            cells[i] = True
    return t0, t1, cells, step


def test_busy_and_window_match_a_slow_count(recorded, reduced):
    t0, t1, cells, step = timeline(recorded)
    assert reduced["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    slow_busy = sum(cells) * step * 1e-9
    # the slow count loses up to a microsecond at each interval's ends
    assert reduced["busy_s"] == pytest.approx(slow_busy, rel=0.02)
    assert 0.9 < reduced["busy_s"] / reduced["window_s"] < 1.0


def test_program_durations(reduced):
    programs = reduced["programs"]
    assert set(programs) == {"jit__prefill", "jit__decode"}
    assert programs["jit__prefill"]["count"] == 1
    assert programs["jit__prefill"]["median_ms"] == pytest.approx(50.798426)
    assert programs["jit__decode"]["median_ms"] == pytest.approx(82.652654)


def test_idle_gaps_add_up_to_the_idle_time_and_name_the_host_span(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # the host sat in the blocking fetch while the device ran, so the
    # idle time lies in the dispatches and between the two calls
    assert set(gaps) <= {
        f"inside_{call}_call:_{part}"
        for call in ("decode", "prefill_chunk")
        for part in ("dispatch", "fetch", "other")
    } | {"engine_loop_outside_both",
         "inside_device_programs:_between_operations"}
    assert gaps["inside_prefill_chunk_call:_dispatch"] > 0.0005


def test_top_operations_belong_to_their_program(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) == 10
    assert all(name.split(":")[0] in ("jit__prefill", "jit__decode")
               for name, _s in ops)
    assert [s for _n, s in ops] == sorted((s for _n, s in ops), reverse=True)
    # the loop over layers is a container, not an operation of its own
    assert not any(":while" in name for name, _s in ops)


HAND = {
    "devices": {"/device:TPU:0": {
        "ops": [["%fusion.1 = bf16[8,64]{1,0} fusion(x)", 100, 50],
                ["%copy.2 = f32[4]{0} copy(y)", 160, 20],
                ["%fusion.1 = bf16[8,64]{1,0} fusion(x)", 300, 50]],
        "modules": [["jit__decode(123)", 100, 85],
                    ["jit__decode(123)", 300, 50]],
    }},
    "host": [["decode", 90, 120], ["decode:fetch", 98, 110],
             ["decode", 290, 80], ["decode:fetch", 295, 70]],
}


def test_hand_made_trace():
    out = trace_reduce.reduce(HAND)
    ns = 1e-9
    assert out["window_s"] == pytest.approx(280 * ns)
    assert out["busy_s"] == pytest.approx(120 * ns)
    assert out["programs"]["jit__decode"]["count"] == 2
    assert out["programs"]["jit__decode"]["median_ms"] == pytest.approx(67.5e-6)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit__decode:fusion.1 bf16[8,64]"] == pytest.approx(100 * ns)
    assert ops["jit__decode:copy.2 f32[4]"] == pytest.approx(20 * ns)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "engine_loop_outside_both": 80 * ns,
        "inside_decode_call:_fetch": 45 * ns,
        "inside_decode_call:_other": 7 * ns,
        "inside_device_programs:_between_operations": 15 * ns,
        # a call's time before its first fetch: [90, 98] and [290, 295]
        "inside_decode_call:_dispatch": 13 * ns,
    })


def test_a_call_without_a_fetch_span_is_charged_whole():
    """A program that fetches another way loses the split, not the time."""
    host = [e for e in HAND["host"] if e[0] == "decode"]
    gaps = dict(trace_reduce.reduce(dict(HAND, host=host))["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "engine_loop_outside_both": 80e-9,
        "inside_decode_call:_other": 65e-9,
        "inside_device_programs:_between_operations": 15e-9,
    })


def test_a_trace_with_no_device_plane_reads_as_nothing():
    out = trace_reduce.reduce({"devices": {}, "host": HAND["host"]})
    assert out["busy_s"] == 0.0 and out["programs"] == {}


@pytest.mark.parametrize("name,want", [
    ("%fusion.262 = bf16[8,32,14336]{2,1,0:T(8,128)} fusion(a, b)",
     "fusion.262 bf16[8,32,14336]"),
    ("%while.5 = (s32[]{:T(128)}, bf16[1,64,4096]) while(x)", "while.5 s32[]"),
    ("%copy-start.5 = (bf16[4096]{0}, u32[]) copy-start(y)",
     "copy-start.5 bf16[4096]"),
    ("jit__decode(3080524521950613793)", "jit__decode(3080524521950613793)"),
])
def test_short_names(name, want):
    assert trace_reduce.short(name) == want


def test_holes_and_cover():
    cover = trace_reduce.Cover([(0, 10), (20, 30), (5, 12)])
    assert cover.merged == [[0, 12], [20, 30]]
    assert cover.within(5, 25) == 12
    assert cover.holes(5, 25) == [(12, 20)]
    assert cover.holes(-5, 40) == [(-5, 0), (12, 20), (30, 40)]
    assert cover.holes(1, 2) == []
