"""A deterministic fake of the serving engine's device half.

``serve.engine.PagedEngine`` takes its model as two callables; the
chain model implements them over host state only, no jax.  Each row's
next token is a pure function of that row's own (token, position) —
exactly the independence the real pool provides — so ANY admission
order, chunking or slot assignment must reproduce ``chain_oracle``.
The engine, router and migration tests all drive this one fake.
"""

import threading

import numpy as np

V = 97  # fake vocab (prime: the chain wanders)


def chain_first(prompt):
    return (sum(prompt) * 31 + len(prompt)) % V


def chain_next(tok, pos):
    return (tok * 7 + pos * 3 + 1) % V


def chain_oracle(prompt, n, eos=None):
    """What whole-batch generate would produce for this row."""
    out = [chain_first(prompt)]
    pos = len(prompt)
    while len(out) < n and (eos is None or out[-1] != eos):
        out.append(chain_next(out[-1], pos))
        pos += 1
    return out


class ChainModel:
    """``prefill_chunk`` / ``decode`` of the paged signature.  Chunks
    of one slot's prompt arrive in order and accumulate (serve with
    the prefix cache OFF, which keeps start=0 on the first chunk);
    the final chunk's return is the chain's first token.  Both calls
    assert that every position they write has an allocated (nonzero)
    page behind it.  ``step_gate`` (an Event the test pulses) holds
    each decode until set; ``fail`` is raised by decode."""

    def __init__(self, page_tokens=4, slots=None, step_gate=None,
                 fail=None):
        self.page_tokens = page_tokens
        self.slots = slots
        self.step_gate = step_gate
        self.fail = fail
        self.partial = {}
        self.prefills = 0
        self.decode_calls = 0
        self.max_active = 0

    def prefill_chunk(self, padded, slot, table, start, true_len,
                      temp, seed):
        assert self.slots is None or 0 <= slot < self.slots
        self.prefills += 1
        if start == 0:
            self.partial[slot] = []
        buf = self.partial[slot]
        assert len(buf) == start, "chunks arrived out of order"
        buf.extend(int(t) for t in padded[0, :true_len])
        for pos in range(start, start + true_len):
            assert table[pos // self.page_tokens] != 0, (
                "write into unallocated page"
            )
        return chain_first(buf)

    def decode(self, tok, pos, temps, seeds, tables, n_active):
        if self.fail is not None:
            raise self.fail
        if self.step_gate is not None:
            assert self.step_gate.wait(10), "test never released the tick"
            self.step_gate.clear()
        self.decode_calls += 1
        self.max_active = max(self.max_active, n_active)
        for s in range(len(tok)):
            if pos[s] > 0:  # live row: write page must exist
                assert tables[s][int(pos[s]) // self.page_tokens] != 0
        return np.asarray(
            [chain_next(int(t), int(p)) for t, p in zip(tok, pos)],
            np.int32,
        )


def swarm(engine, jobs):
    """Submit each (rows, n, eos) concurrently; returns results."""
    results = [None] * len(jobs)
    errors = []

    def client(i):
        rows, n, eos = jobs[i]
        try:
            results[i] = engine.submit(rows, n, eos_id=eos)
        except Exception as e:  # noqa: BLE001 — surfaced via assert
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(i,))
        for i in range(len(jobs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    return results
