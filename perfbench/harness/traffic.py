"""The one general traffic generator.  A mix is a data file of
parameters; this turns it, a cell's rate and a seed into a schedule of
requests, open loop.

Every seed offers the same work.  A phase of N requests takes its
prompt and output lengths from the N quantile mid-points of the mix's
distributions, paired by a permutation that is fixed in the mix (not
drawn from the seed), so the multiset of (prompt, output) pairs and the
tokens offered are identical for every seed.  ``--seed`` decides which
arrival gets which pair (a free permutation: long prompts may follow
one another, as they do in real traffic), where inside its slot of
``1/rate`` seconds an arrival falls, and the prompts' token ids.
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
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    rate = float(cell["rate_rps"])
    out: List[Request] = []
    for name, start, count in phases(mix, seconds, rate):
        lengths = pairs(mix, count)
        chosen = rng.permutation(count)
        offsets = rng.random(count)
        for slot in range(count):
            plen, new = lengths[int(chosen[slot])]
            out.append(Request(
                len(out), name, start + (slot + offsets[slot]) / rate,
                plen, new, rng.integers(0, vocab, plen, dtype=np.int32),
            ))
    return out
