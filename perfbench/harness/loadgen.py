"""Sends a schedule to the worker's ``/generate`` from this process,
open loop (on the schedule's clock, whatever the server does), and
records every request's times on one monotonic clock.
"""

from __future__ import annotations

import http.client
import json
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from perfbench.harness.traffic import Request


@dataclass
class Outcome:
    request: Request
    due: float = 0.0         # monotonic seconds
    sent: float = 0.0
    done: float = 0.0
    status: int = 0          # 0: no answer
    tokens: Optional[list] = None
    error: str = ""


@dataclass
class LoadRun:
    """One run's load.  ``start`` is the monotonic time of the window's
    start; the ramp begins ``mix['ramp_s']`` before it."""

    address: str
    mix: dict
    requests: List[Request]
    start: float
    seconds: float
    clients: int
    outcomes: List[Outcome] = field(default_factory=list)

    def __post_init__(self):
        self._bodies = [r.body() for r in self.requests]
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self.end = self.start + self.seconds

    # -- one request ---------------------------------------------------

    def _send(self, index: int, due: float) -> None:
        request = self.requests[index]
        outcome = Outcome(request, due=due, sent=time.monotonic())
        with self._lock:
            self.outcomes.append(outcome)
        host, port = self.address.rsplit(":", 1)
        try:
            # a short limit on connecting (a SYN that a full accept queue
            # dropped retries for minutes), the mix's on the answer
            conn = http.client.HTTPConnection(host, int(port), timeout=10)
            try:
                conn.connect()
                conn.sock.settimeout(self.mix["request_timeout_s"])
                conn.request(
                    "POST", "/generate", body=self._bodies[index],
                    headers={"Content-Type": "application/json"},
                )
                response = conn.getresponse()
                payload = response.read()
                outcome.done = time.monotonic()
                outcome.status = response.status
            finally:
                conn.close()
            if outcome.status == 200:
                outcome.tokens = json.loads(payload)["tokens"][0]
            else:
                outcome.error = payload[:200].decode("utf-8", "replace")
        except (OSError, ValueError, KeyError, http.client.HTTPException) as e:
            outcome.done = time.monotonic()
            outcome.error = repr(e)

    # -- the loops -----------------------------------------------------

    def _open_worker(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            self._send(*item)

    def _open_dispatch(self) -> None:
        for index, request in enumerate(self.requests):
            due = self.start + request.due_s
            while True:
                wait = due - time.monotonic()
                if wait <= 0 or self._stop.is_set():
                    break
                time.sleep(min(wait, 0.05))
            if self._stop.is_set():
                break
            self._queue.put((index, due))
        for _ in range(self.clients):
            self._queue.put(None)

    def begin(self) -> None:
        targets = [self._open_worker] * self.clients + [self._open_dispatch]
        self._threads = [
            threading.Thread(target=t, daemon=True) for t in targets
        ]
        for thread in self._threads:
            thread.start()

    def judged(self) -> List[Outcome]:
        """The window's requests: those DUE inside it."""
        with self._lock:
            outcomes = list(self.outcomes)
        return [o for o in outcomes if o.request.phase == "window"]

    def drain(self) -> List[Outcome]:
        """Wait, after the window has closed, for its requests to end
        (bounded by the mix's drain limit), then stop sending.  What
        has no answer by then keeps status 0 and counts as failed."""
        expected = sum(r.phase == "window" for r in self.requests)
        deadline = self.end + self.mix["drain_limit_s"]
        while time.monotonic() < deadline:
            judged = self.judged()
            if time.monotonic() >= self.end and all(
                o.done > 0.0 for o in judged
            ) and len(judged) >= expected:
                break
            time.sleep(0.05)
        self._stop.set()
        return self.judged()

    def finish(self) -> int:
        """After the service is down every thread ends (an abandoned
        request fails on its closed connection).  Returns how many did
        not within half a minute: 0 in a sound run."""
        deadline = time.monotonic() + 30
        for thread in self._threads:
            thread.join(timeout=max(0.0, deadline - time.monotonic()))
        return sum(t.is_alive() for t in self._threads)
