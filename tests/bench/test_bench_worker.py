"""What the benchmark needs of the program, held by name: its weight
tree is checked against the shapes of the program's own ``init_params``
before anything is built, and a program that moved one of the names
stops the worker entry with a message, not an AttributeError.  And the
sample the reference reads covers every row in use at one instant."""

import functools
import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_bench_reference import DENSE, program_config  # noqa: E402
from toyroot import TOY_MODEL, family  # noqa: E402


def program_shapes(model):
    import jax

    from dcos_commons_tpu.models import init_params

    return jax.eval_shape(
        functools.partial(init_params, program_config(model)),
        jax.random.key(0),
    )


@pytest.mark.parametrize("model", [TOY_MODEL, DENSE], ids=["mixture", "dense"])
def test_the_benchmarks_tree_is_the_programs(model):
    import jax.numpy as jnp

    from perfbench.harness.weights import tree_differences

    specs = family().weight_specs(model)
    assert tree_differences(specs, jnp.float32, program_shapes(model)) == []


def test_a_program_that_changed_its_tree_is_named_leaf_by_leaf():
    import jax.numpy as jnp

    from perfbench.harness.weights import tree_differences

    theirs = program_shapes(TOY_MODEL)
    layers = dict(theirs["layers"])
    layers["w_gate_up"] = layers.pop("w_gate")  # a fused feed-forward
    specs = family().weight_specs
    found = tree_differences(
        specs(TOY_MODEL), jnp.float32, dict(theirs, layers=layers)
    )
    assert any("no leaf layers/w_gate" in line for line in found)
    assert any("layers/w_gate_up" in line and "unknown" in line
               for line in found)
    wider = dict(TOY_MODEL, intermediate_size=128)
    found = tree_differences(specs(wider), jnp.float32, theirs)
    assert len(found) == 3 and all("(2, 4, 64, 96)" in line or
                                   "(2, 4, 96, 64)" in line for line in found)


def worker_entry():
    spec = importlib.util.spec_from_file_location(
        "perfbench_worker_entry",
        os.path.join(REPO, "perfbench", "worker", "serve_worker.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_moved_device_call_stops_the_entry_with_its_name(monkeypatch):
    from dcos_commons_tpu.serve import pool

    entry = worker_entry()
    monkeypatch.delattr(pool.PagedPoolModel, "prefill_chunk")
    with pytest.raises(SystemExit) as stopped:
        entry._annotate_device_calls()
    message = str(stopped.value)
    assert "PagedPoolModel.prefill_chunk is gone" in message
    assert all(name in message for name in entry.PROGRAM_NAMES)


def test_spans_touch_nothing_private_of_the_program(monkeypatch):
    """Only the two public calls and ``jax.device_get`` are wrapped; a
    fetch outside them is not spanned."""
    import jax

    from dcos_commons_tpu.serve import pool

    entry = worker_entry()
    cls = pool.PagedPoolModel
    private = {name: getattr(cls, name) for name in vars(cls)
               if name.startswith("_")}
    monkeypatch.setattr(cls, "prefill_chunk", cls.prefill_chunk)
    monkeypatch.setattr(cls, "decode", cls.decode)
    monkeypatch.setattr(jax, "device_get", jax.device_get)
    spans = []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            spans.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    monkeypatch.setattr(cls, "decode", lambda self: jax.device_get(7))
    entry._annotate_device_calls()
    assert {name: getattr(cls, name) for name in private} == private
    assert jax.device_get(3) == 3 and spans == []
    assert cls.decode(object()) == 7
    assert spans == ["decode", "decode:fetch"]


class Made:
    """An outcome as ``pick_sample`` reads it."""

    def __init__(self, index, sent, done, tokens, phase="window", status=200):
        from perfbench.harness.traffic import Request

        self.request = Request(index, phase, sent, 10 + index, tokens, None)
        self.sent, self.done, self.status = sent, done, status
        self.tokens = list(range(tokens))


@pytest.mark.parametrize("seed", [1, 2**31 + 3, 99])
def test_the_sample_covers_every_request_in_flight_at_the_fullest_instant(seed):
    from perfbench import run

    # five overlap at t = 4.5 (indices 2..6); the ramp's request 0 too
    outcomes = [Made(0, -1.0, 5.0, 40, phase="ramp")] + [
        Made(i, float(i), float(i) + 3.6, 20 + i) for i in range(1, 12)
    ] + [Made(12, 6.0, 7.0, 30, status=503)]
    judged = [o for o in outcomes if o.request.phase == "window"]
    mix = {"check_draw": 2, "check_tokens": 8}
    sample = run.pick_sample(outcomes, judged, 0.0, 12.0, mix, seed)
    chosen = {o.request.index: n for o, n in sample}
    together = max(
        ([o for o in outcomes if o.status == 200 and o.sent <= t < o.done]
         for t in (o.sent for o in outcomes)), key=len,
    )
    assert len(together) == 5
    assert {o.request.index for o in together} <= set(chosen)
    assert 0 in chosen and 12 not in chosen  # the ramp's counts; a 503 not
    assert chosen[11] == 31                   # the window's longest, whole
    whole = [i for i, n in chosen.items() if n == len(outcomes[i].tokens)]
    assert len(whole) == 3 and len(chosen) == len(sample)
    assert all(n == 8 for i, n in chosen.items() if i not in whole)
