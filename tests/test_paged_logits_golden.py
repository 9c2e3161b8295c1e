"""The three configurations the benchmark had before window layers keep
their programs: tiny twins of them (tests/golden_paged_logits.py) give,
call for call, the logits that the parent commit of PR 44 gave
(tests/golden_paged_logits.json, recorded from its tree).  What PR 44
threaded through the shared walk (a table's ring entries, a free
``head_dim``, the output gate, the second norms, the embedding's scale,
the blocked history of a long row) is off for them, and off means the
same arithmetic in the same order: equal to the bit on the machine that
recorded them."""

import json
import os
import warnings

import numpy as np
import pytest

import golden_paged_logits as golden

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "golden_paged_logits.json")) as f:
        return {k: np.asarray(v, np.float32) for k, v in json.load(f).items()}


@pytest.mark.parametrize("name", ["mixtral", "evabyte", "lfm2"])
def test_a_twins_logits_are_the_parents(recorded, name):
    got = golden.logits_of(golden.twins()[name])
    want = recorded[name]
    assert got.shape == want.shape == (3 + golden.STEPS, 128)
    if not np.array_equal(got, want):
        # another CPU sums a product in another order; a change of the
        # arithmetic moves these logits (magnitude 3) by far more
        warnings.warn(
            f"{name}: not equal to the bit on this machine, largest "
            f"difference {np.abs(got - want).max():.2e}"
        )
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_twins_take_the_paths_they_stand_for():
    from dcos_commons_tpu.models.decode import layer_plan

    twins = golden.twins()
    assert twins["mixtral"].one_kind and twins["mixtral"].n_experts == 4
    assert twins["evabyte"].attention == "eva" and twins["evabyte"].one_kind
    assert not twins["lfm2"].one_kind
    assert layer_plan(twins["lfm2"].layer_kinds) == (1, 4, 2, 0)
    for config in twins.values():
        assert config.d_head == 0 and not config.serving_only.replace(
            "qk_norm", "")
