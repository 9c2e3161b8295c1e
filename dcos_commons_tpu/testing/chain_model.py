"""A deterministic fake of the serving engine's device half.

``serve.engine.PagedEngine`` takes its model as two callables; the
chain model implements them over host state only, no jax.  Each row's
next token is a pure function of that row's own (token, position) —
exactly the independence the real pool provides — so ANY admission
order, chunking or slot assignment must reproduce ``chain_oracle``.
The engine, router and migration tests all drive this one fake.
"""

import threading
import time

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


class OneAhead:
    """Any of the synchronous fakes behind the protocol of a device
    half that runs one call ahead (``serve.pool.PagedPoolModel``):
    ``prefill_chunk(..., final=)`` hands its token over only on a
    prompt's last chunk, ``decode(..., carry=)`` takes a carried
    row's token from the step before it and returns THAT step's
    tokens (none outstanding: an empty array), ``resolve_decode()``
    hands over the outstanding step.  The host never sees a step's
    tokens before it resolved it.

    ``log`` records, in the order the engine made them, ("chunk",
    slot, start, fetched), ("dispatch", step) and ("resolve", step);
    hand the engine ``engine_kwargs()`` beside the two callables."""

    def __init__(self, model):
        self.model = model
        self.log = []
        self.steps = 0
        self._outstanding = None  # the unresolved step's tokens

    def engine_kwargs(self):
        return {"resolve_decode_fn": self.resolve_decode}

    def prefill_chunk(self, padded, slot, table, start, true_len,
                      temp, seed, final=True):
        first = self.model.prefill_chunk(
            padded, slot, table, start, true_len, temp, seed
        )
        self.log.append(("chunk", slot, start, bool(final)))
        return first if final else None

    def decode(self, tok, pos, temps, seeds, tables, n_active, carry):
        return self._dispatch(
            "dispatch", tok, pos, temps, seeds, tables, n_active, carry
        )

    def _dispatch(self, kind, tok, pos, temps, seeds, tables, n_active,
                  carry):
        carry = np.asarray(carry, bool)
        if carry.any():
            assert self._outstanding is not None, (
                "a carried token with no step outstanding"
            )
            tok = np.where(carry, self._outstanding, tok)
        self.log.append((kind, self.steps))
        previous = self._take()
        self._outstanding = np.asarray(
            self.model.decode(tok, pos, temps, seeds, tables, n_active)
        )
        self.steps += 1
        return previous

    def resolve_decode(self):
        return self._take()

    def _take(self):
        previous, self._outstanding = self._outstanding, None
        if previous is None:
            return np.zeros(0, np.int32)
        self.log.append(("resolve", self.steps - 1))
        return previous


class ChunkRiders(OneAhead):
    """``OneAhead`` whose chunk program carries a decode step
    (``PagedPoolModel`` with ``chunk_riders``): ``prefill_chunk(...,
    riders=)`` is the chunk and then that step, by ``decode``'s own
    keywords, in ONE call, logged ("chunk", ...) then ("ride", step),
    and returns (the chunk's token or None, the previous step's
    tokens)."""

    def engine_kwargs(self):
        return {**super().engine_kwargs(), "chunk_riders": True}

    def prefill_chunk(self, padded, slot, table, start, true_len,
                      temp, seed, final=True, riders=None):
        first = super().prefill_chunk(
            padded, slot, table, start, true_len, temp, seed, final
        )
        if riders is None:
            return first
        live = int(np.asarray(riders["tables"]).any(axis=1).sum())
        return first, self._dispatch("ride", n_active=live, **riders)


def settled_stats(engine, timeout=10.0):
    """``engine.stats()`` once the loop has nothing left to do.  A
    client is answered when its last token is applied; a step that
    was queued behind that one (a row ended by ``eos``) is resolved
    and counted a moment later."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with engine._cv:
            if not engine._has_work_locked():
                break
        time.sleep(0.001)
    else:
        raise AssertionError("the engine loop never came to rest")
    return engine.stats()


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
