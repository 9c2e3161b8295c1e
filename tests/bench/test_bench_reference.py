"""The first family's plain reference (``perfbench/families/
gqa_decoder/reference.py``) against the program's own forward pass at toy
size in float32, and the control: the same comparison fails when the
tokens come from int8 weights (the nearest precision below, and a
path the program has of its own)."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from toyroot import TOY_LIMITS, TOY_MODEL, family  # noqa: E402

DENSE = dict(TOY_MODEL, num_local_experts=0, model_type="mistral")
SEEDS = [3, 2**31 + 11, 77]


def make_weights(model, seed):
    """The seed's tree for ``model`` as the first family states it, in
    float32."""
    import jax.numpy as jnp

    from perfbench.harness import weights as w

    return w.make_weights(family().weight_specs(model), seed, jnp.float32)


def program_config(model):
    import jax.numpy as jnp

    from dcos_commons_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        n_experts=model.get("num_local_experts", 0), dtype=jnp.float32,
        remat=False, moe_capacity_factor=8.0,
    )


def first_choices(model, weights, tokens):
    """The token the program's forward pass puts first at each position."""
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import forward

    with jax.default_matmul_precision("highest"):
        logits = forward(program_config(model), weights, jnp.asarray(tokens))
    return np.asarray(jnp.argmax(logits, -1))


@pytest.mark.parametrize("model", [TOY_MODEL, DENSE],
                         ids=["mixture", "dense"])
def test_reference_agrees_with_the_program_in_float32(model):
    import jax
    import jax.numpy as jnp

    from dcos_commons_tpu.models import forward

    reference = family().reference
    weights = make_weights(model, 2**31 + 77)
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        got = forward(program_config(model), weights, jnp.asarray([tokens]))[0]
    want = reference.logits(model, weights, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    rows = reference.logits(model, weights, tokens, rows=[5, 39])
    assert float(jnp.max(jnp.abs(rows - want[jnp.asarray([5, 39])]))) < 1e-5


def test_padding_behind_the_sequence_changes_nothing():
    import jax.numpy as jnp

    reference = family().reference
    weights = make_weights(TOY_MODEL, 5)
    tokens = np.arange(20) % TOY_MODEL["vocab_size"]
    padded = np.concatenate([tokens, np.zeros(12, tokens.dtype)])
    a = reference.logits(TOY_MODEL, weights, tokens)
    b = reference.logits(TOY_MODEL, weights, padded)[:20]
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_weights_come_from_the_seed_alone():
    import jax
    import jax.numpy as jnp

    a = make_weights(TOY_MODEL, 2**31 + 77)
    b = make_weights(TOY_MODEL, 2**31 + 77)
    c = make_weights(TOY_MODEL, 2**31 + 78)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == c["embed"]).all())
    assert a["layers"]["router"].dtype == jnp.float32
    shapes = {
        "/".join(p): s
        for p, s, _k, _s, _d in family().weight_specs(TOY_MODEL)
    }
    assert shapes["layers/w_gate"] == (2, 4, 64, 96)
    assert shapes["layers/wk"] == (2, 64, 32)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_choices_pass_and_int8_choices_fail(seed):
    """The control at a size a test can hold: at each position of the
    same sequences, the token that the program's int8 weights put first
    fails the limits that its float32 choice passes."""
    import jax.numpy as jnp

    from dcos_commons_tpu.models import quantize_params_int8
    from perfbench.harness import check

    reference = family().reference
    model = TOY_MODEL
    weights = make_weights(model, seed)
    tokens = np.random.default_rng(seed).integers(
        0, model["vocab_size"], (6, 64)
    )
    sound = first_choices(model, weights, tokens)
    lower = first_choices(model, quantize_params_int8(weights), tokens)
    gaps = {"sound": [], "int8": []}
    for row in range(len(tokens)):
        logits = reference.logits(model, weights, tokens[row])
        gaps["sound"].append(check.chosen_gaps(logits, sound[row]))
        gaps["int8"].append(check.chosen_gaps(logits, lower[row]))
    everywhere = np.ones(tokens.size, bool)
    ok, compared = check.judge(
        np.concatenate(gaps["sound"]), everywhere, TOY_LIMITS
    )
    assert ok, compared
    ok, compared = check.judge(
        np.concatenate(gaps["int8"]), everywhere, TOY_LIMITS
    )
    assert not ok, compared
    # the reference's own int8 control fails the same limits
    own = []
    for row in tokens:
        first = np.asarray(jnp.argmax(
            reference.logits(model, weights, row, lower="int8"), -1
        ))
        own.append(check.chosen_gaps(
            reference.logits(model, weights, row), first
        ))
    ok, compared = check.judge(np.concatenate(own), everywhere, TOY_LIMITS)
    assert not ok, compared


def test_steady_numbers_leave_out_positions_with_a_narrow_routing_margin():
    from perfbench.harness import check

    gaps = np.array([0.0, 3.0, 0.0, 0.01])
    steady = np.array([True, False, True, True])
    limits = {"max_gap": 5.0, "steady_max_gap": 0.02,
              "steady_mismatch_share": 0.5, "wide_gap_share": 0.25,
              "steady_wide_gap_share": 0.0}
    ok, compared = check.judge(gaps, steady, limits, wide_gap=0.1)
    assert ok and compared["steady_max_gap"][0] == 0.01
    assert compared["wide_gap_share"][0] == 0.25
    assert compared["max_gap"][0] == 3.0
    assert compared["steady_mismatch_share"][0] == pytest.approx(1 / 3)
    ok, _ = check.judge(gaps, steady, {"max_gap": 1.0})
    assert not ok
    # nothing steady to read: a limit on a steady number cannot pass
    ok, _ = check.judge(gaps, np.zeros(4, bool), {"steady_max_gap": 9.0})
    assert not ok


def test_compare_reads_every_served_position_of_every_request():
    from perfbench.harness import check

    reference = family().reference
    weights = make_weights(TOY_MODEL, 9)
    tokens = np.random.default_rng(9).integers(0, 128, (1, 30))
    served = first_choices(TOY_MODEL, weights, tokens)[0]
    # teacher forcing: position i of the sequence predicts token i + 1,
    # so a request whose served tokens ARE the sequence's continuation
    # reads as sound only where the program's choice was followed
    prompt, rest = tokens[0, :20].tolist(), tokens[0, 20:].tolist()
    ok, compared, n, steady = check.compare(
        reference, TOY_MODEL, weights, [{"prompt": prompt, "served": rest}],
        TOY_LIMITS,
    )
    assert n == 10 and not ok  # random continuations are not first choices
    assert steady == 10  # a margin of 0 leaves every position in
    follow = [int(served[19])]
    ok, compared, n, _ = check.compare(
        reference, TOY_MODEL, weights,
        [{"prompt": prompt, "served": follow}], TOY_LIMITS,
    )
    assert n == 1 and ok, compared
    _, _, _, steady = check.compare(
        reference, TOY_MODEL, weights, [{"prompt": prompt, "served": rest}],
        TOY_LIMITS, routing_margin=0.2,
    )
    assert 0 < steady < 10
