"""The one general traffic generator.  A mix is a data file of
parameters; this turns it, a cell's rate and a seed into a schedule of
requests, open loop.

Every seed offers the same work, in the same order, at the same
instants.  A phase of N requests takes its prompt and output lengths
from the N quantile mid-points of the mix's distributions, paired by a
permutation that is fixed in the mix (``pairing_seed``).  A second draw
that the mix fixes too (``order_seed``) deals the pairs over the
phase's N slots of ``1/rate`` seconds and says where inside its slot
each arrival falls: one FREE draw, so long prompts do follow one
another and arrivals bunch and thin as they do in real traffic, but the
same draw in every run, so that at any moment of the window every seed
has the same requests in flight.  The offsets are the mix's because
an arrival that moves by up to a slot (0.95 s in ``evabyte.docqa``)
decides which long prefills overlap as much as the order does: with
the order alone stated, six seeds spread as widely as with neither
(PERF.md section 2).  ``--seed`` decides the prompts' token ids (and
with them a mixture's routing), the weights, and the sample that
``correct`` reads (``run.py pick_sample``).  A mix without either seed
is refused: there is one path.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    index: int
    phase: str          # "ramp", "window" or "tail"
    due_s: float        # seconds from the window's start
    prompt_len: int
    max_new_tokens: int
    tokens: np.ndarray  # prompt token ids

    def body(self) -> bytes:
        return json.dumps({
            "tokens": [self.tokens.tolist()],
            "max_new_tokens": self.max_new_tokens,
            "temperature": 0.0,
        }).encode()


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """The n quantile mid-points of a clipped log-normal, as whole
    numbers, ascending."""
    if dist["kind"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['kind']!r}")
    normal = NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        value = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(round(min(max(value, dist["min"]), dist["max"]))))
    return out


def pairs(mix: dict, n: int) -> List[tuple]:
    """n (prompt, output) length pairs: the same for every seed."""
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outputs = quantile_lengths(mix["output_tokens"], n)
    order = np.random.default_rng(
        [int(mix["pairing_seed"]), n]
    ).permutation(n)
    return [(prompts[i], outputs[int(order[i])]) for i in range(n)]


def dealt(mix: dict, n: int) -> tuple:
    """(which of the n pairs each of a phase's n slots gets, where in
    its slot each arrival falls as a share of the slot): the mix's own
    free draw for this count, the same for every seed."""
    rng = np.random.default_rng([int(mix["order_seed"]), n])
    return [int(i) for i in rng.permutation(n)], rng.random(n)


def phases(mix: dict, seconds: float, rate: float) -> List[tuple]:
    """(name, start_s, count) of the ramp, the window and the tail."""
    return [
        ("ramp", -mix["ramp_s"], int(round(rate * mix["ramp_s"]))),
        ("window", 0.0, int(round(rate * seconds))),
        ("tail", seconds, int(round(rate * mix["tail_s"]))),
    ]


def schedule(mix: dict, cell: dict, vocab: int, seconds: float,
             seed: int) -> List[Request]:
    """The run's requests in sending order."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    if "order_seed" not in mix:
        raise ValueError("the mix states no order_seed")
    if int(mix["order_seed"]) == int(mix["pairing_seed"]):
        # the same seed and count would deal the pairs in the order
        # that paired them
        raise ValueError("order_seed is the mix's pairing_seed")
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    rate = float(cell["rate_rps"])
    out: List[Request] = []
    for name, start, count in phases(mix, seconds, rate):
        lengths = pairs(mix, count)
        chosen, offsets = dealt(mix, count)
        for slot in range(count):
            plen, new = lengths[chosen[slot]]
            out.append(Request(
                len(out), name, start + (slot + offsets[slot]) / rate,
                plen, new, rng.integers(0, vocab, plen, dtype=np.int32),
            ))
    return out
