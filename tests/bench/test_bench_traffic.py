"""The traffic generator offers the same work, in the same order and at
the same instants, for every seed: order and arrivals are the mix's,
the seed's part is the ids."""

import json
import os
import sys
from collections import Counter

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)

from perfbench.harness import traffic  # noqa: E402

SEEDS = [0, 1, 2, 3, 5, 8, 13, 2**31 - 1, 2**31, 2**31 + 12345,
         2**32 + 7, 987654321]


def mix(name):
    with open(os.path.join(REPO, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


def lengths(requests, phase=None):
    return Counter(
        (r.prompt_len, r.max_new_tokens) for r in requests
        if phase is None or r.phase == phase
    )


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_open_loop_same_multiset_and_count_for_every_seed(seed):
    chat = mix("chat-steady")
    first = traffic.schedule(chat, {"rate_rps": 7.3}, 32000, 51, SEEDS[0])
    other = traffic.schedule(chat, {"rate_rps": 7.3}, 32000, 51, seed)
    for phase in ("ramp", "window", "tail"):
        assert lengths(first, phase) == lengths(other, phase)
    assert len([r for r in other if r.phase == "window"]) == round(7.3 * 51)
    assert [r.due_s for r in first] == [r.due_s for r in other]
    assert [r.body() for r in first] != [r.body() for r in other]


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_open_loop_one_arrival_in_every_slot(seed):
    chat = mix("chat-steady")
    rate = 9.0
    requests = traffic.schedule(chat, {"rate_rps": rate}, 32000, 20, seed)
    window = [r for r in requests if r.phase == "window"]
    slots = sorted(int(r.due_s * rate) for r in window)
    assert slots == list(range(len(window)))
    # any stretch of the window holds its share of arrivals, to +-1
    for a in (0.0, 3.3, 7.9):
        inside = sum(a <= r.due_s < a + 10 for r in window)
        assert abs(inside - rate * 10) <= 1
    assert all(
        r.tokens.min() >= 0 and r.tokens.max() < 32000
        and len(r.tokens) == r.prompt_len for r in requests
    )
    assert requests == sorted(requests, key=lambda r: r.due_s)


def test_same_seed_gives_the_same_inputs():
    chat = mix("chat-steady")
    a = traffic.schedule(chat, {"rate_rps": 5}, 32000, 10, 2**31 + 5)
    b = traffic.schedule(chat, {"rate_rps": 5}, 32000, 10, 2**31 + 5)
    assert [r.body() for r in a] == [r.body() for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]


@pytest.mark.parametrize("name,key,lo,hi,median", [
    ("chat-steady", "prompt_tokens", 16, 1536, 256),
    ("chat-steady", "output_tokens", 8, 512, 128),
])
def test_quantile_lengths_follow_the_stated_distribution(name, key, lo, hi,
                                                         median):
    values = traffic.quantile_lengths(mix(name)[key], 401)
    assert values == sorted(values)
    assert values[0] >= lo and values[-1] <= hi
    assert abs(values[200] - median) <= 1
    assert len(set(values)) > 100


def test_requests_fit_the_server_they_are_sent_to():
    for name in sorted(os.listdir(os.path.join(REPO, "perfbench", "traffic"))):
        m = mix(name[:-len(".json")])
        size = m["sizing_env"]
        assert m["output_tokens"]["max"] <= size["MAX_NEW_TOKENS"]
        # the worker refuses prompts over MAX_LEN - MAX_NEW_TOKENS
        assert m["prompt_tokens"]["max"] <= (
            size["MAX_LEN"] - size["MAX_NEW_TOKENS"]
        )


def lengths_of(requests):
    return {phase: lengths(requests, phase)
            for phase in ("ramp", "window", "tail")}


def order_of(requests, phase=None):
    return [(r.prompt_len, r.max_new_tokens) for r in requests
            if phase is None or r.phase == phase]


# each cell's mix, rate and vocabulary (perfbench/cells/<cell>.json)
CELLS = [("chat-steady", 2.8, 32000), ("chat-steady", 3.6, 65536),
         ("docqa-steady", 1.05, 320)]


@pytest.mark.parametrize("seed", SEEDS[:6])
def test_the_mix_states_a_free_order_and_every_seed_keeps_it(seed):
    """Order and arrivals are the mix's own draw, the same for every
    seed, and a FREE one: nothing spreads the long prompts out, so at
    each cell's rate some stretch of eight arrivals holds two or more
    of the longest eighth, and an arrival falls anywhere in its slot.
    The seed draws the ids."""
    for name, rate, vocab in CELLS:
        stated = mix(name)
        first = traffic.schedule(stated, {"rate_rps": rate}, vocab, 51,
                                 SEEDS[6])
        requests = traffic.schedule(stated, {"rate_rps": rate}, vocab, 51,
                                    seed)
        assert order_of(requests) == order_of(first)
        assert [r.due_s for r in requests] == [r.due_s for r in first]
        assert any(
            not np.array_equal(a.tokens, b.tokens)
            for a, b in zip(requests, first)
        )
        # ramp, window and tail: each the mix's own draw for its count
        for phase, _start, count in traffic.phases(stated, 51, rate):
            paired = traffic.pairs(stated, count)
            chosen, offsets = traffic.dealt(stated, count)
            assert order_of(requests, phase) == [paired[i] for i in chosen]
            # somewhere an arrival comes early in its slot, somewhere late
            assert offsets.min() < 0.2 and offsets.max() > 0.8
        window = [r for r in requests if r.phase == "window"]
        cut = sorted(r.prompt_len for r in window)[-len(window) // 8]
        most = max(
            sum(r.prompt_len >= cut for r in window[i:i + 8])
            for i in range(len(window) - 7)
        )
        assert most >= 2
        assert window == sorted(window, key=lambda r: r.due_s)
        # another order_seed is another order of the same pairs
        other = traffic.schedule(
            dict(stated, order_seed=stated["order_seed"] + 1),
            {"rate_rps": rate}, vocab, 51, seed,
        )
        assert order_of(other) != order_of(requests)
        assert [r.due_s for r in other] != [r.due_s for r in requests]
        assert lengths_of(other) == lengths_of(requests)


def test_every_mix_states_an_order_of_its_own():
    """An ``order_seed`` is not its mix's ``pairing_seed`` (the same
    seed and count would deal the pairs in the order that paired them),
    every mix under ``traffic/`` states one, and what is dealt is the
    draw of that seed and the count: a permutation and a place in each
    slot, the same when asked again, others under another seed."""
    for name in sorted(os.listdir(os.path.join(REPO, "perfbench", "traffic"))):
        stated = mix(name[:-len(".json")])
        assert isinstance(stated["order_seed"], int)
        assert stated["order_seed"] != stated["pairing_seed"]
        chosen, offsets = traffic.dealt(stated, 54)
        assert sorted(chosen) == list(range(54))
        assert ((0 <= offsets) & (offsets < 1)).all()
        again = traffic.dealt(stated, 54)
        assert chosen == again[0] and (offsets == again[1]).all()
        for other_seed in (stated["order_seed"] + 1, stated["pairing_seed"]):
            other = traffic.dealt(dict(stated, order_seed=other_seed), 54)
            assert chosen != other[0] and (offsets != other[1]).any()


@pytest.mark.parametrize("case", ["no-order-seed", "the-pairing-seed"])
def test_a_mix_without_an_order_of_its_own_is_refused(case):
    """As a mix with an unknown ``loop`` is: there is one path."""
    chat = mix("chat-steady")
    if case == "no-order-seed":
        del chat["order_seed"]
        says = "order_seed"
    else:
        chat["order_seed"] = chat["pairing_seed"]
        says = "pairing_seed"
    with pytest.raises(ValueError, match=says):
        traffic.schedule(chat, {"rate_rps": 1}, 100, 5, 1)


def test_a_loop_the_generator_does_not_know_is_refused():
    with pytest.raises(ValueError):
        traffic.schedule(dict(mix("chat-steady"), loop="closed"),
                         {"rate_rps": 1}, 100, 5, 1)
