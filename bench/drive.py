"""Load loops: the open loop for questions, closed loops for bulk work.

The loops take plain callables that submit one request and return a
:class:`concurrent.futures.Future`, so the accounting here can be tested
against a stub server.  Every request gets an :class:`Outcome` slot.

Open loop: each request is sent when it is due, whatever the state of
earlier ones, and its latency runs from the moment it was *due*, so a
stall shows in every request that waited behind it; how late the
generator itself sent is reported apart.  Closed loop: each client sends
its next request when the previous one resolved; after ``seconds`` no
new request starts and the ones in flight finish, so a rate divides all
the work by all the time up to the last completion.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np


class Outcome:
    """One request: when it was due, sent and resolved, and its result."""

    __slots__ = ("item", "due", "sent", "done", "ok", "value", "error")

    def __init__(self, item, due: float) -> None:
        self.item = item
        self.due = due
        self.sent: Optional[float] = None
        self.done: Optional[float] = None
        self.ok = False
        self.value = None
        self.error: Optional[BaseException] = None

    def resolve(self, fut: Future, now: float) -> None:
        exc = fut.exception()
        if exc is None:
            self.ok, self.value = True, fut.result()
        else:
            self.error = exc
        self.done = now


def open_loop(items: Sequence, due_s: Callable[[object], float],
              submit: Callable[[object], Future],
              clock: Callable[[], float] = time.perf_counter,
              sleep: Callable[[float], None] = time.sleep
              ) -> Tuple[List[Outcome], float]:
    """Send every item at ``t0 + due_s(item)``; returns the slots, whose
    ``done`` fills in as answers arrive (see :func:`wait_open`), and
    ``t0``."""
    t0 = clock()
    slots: List[Outcome] = []
    for item in items:
        slot = Outcome(item, t0 + due_s(item))
        wait = slot.due - clock()
        if wait > 0:
            sleep(wait)
        slot.sent = clock()
        try:
            fut = submit(item)
        except Exception as exc:        # shed or refused at admission
            slot.error, slot.done = exc, clock()
        else:
            fut.add_done_callback(
                lambda f, s=slot: s.resolve(f, clock()))
        slots.append(slot)
    return slots, t0


def wait_open(slots: Sequence[Outcome], until: float,
              clock: Callable[[], float] = time.perf_counter,
              sleep: Callable[[float], None] = time.sleep) -> float:
    """Wait until every slot resolved or ``until`` passed; returns the
    time the last one resolved (or ``until``)."""
    while clock() < until and any(s.done is None for s in slots):
        sleep(0.002)
    done = [s.done for s in slots if s.done is not None]
    if any(s.done is None for s in slots):
        return until
    return max(done) if done else clock()


def latencies_ms(slots: Sequence[Outcome], end: float) -> np.ndarray:
    """Due-to-answer latency of every slot in ms; a request that failed
    or never resolved counts as answered at ``end`` (never earlier than
    its own resolution)."""
    out = []
    for s in slots:
        if s.ok:
            out.append(s.done - s.due)
        else:
            out.append(max(end, s.done or end) - s.due)
    return np.asarray(out, dtype=np.float64) * 1e3


def percentile(values_ms: np.ndarray, q: float) -> float:
    return float(np.percentile(values_ms, q)) if len(values_ms) \
        else float("nan")


def lateness_ms(slots: Sequence[Outcome]) -> np.ndarray:
    """How late the generator sent each request, in ms."""
    return np.asarray([s.sent - s.due for s in slots if s.sent is not None],
                      dtype=np.float64) * 1e3


def closed_loop(clients: int, make: Callable[[int, int], Optional[tuple]],
                seconds: float, timeout_s: float,
                clock: Callable[[], float] = time.perf_counter
                ) -> tuple:
    """Run ``clients`` callers back to back for ``seconds``.

    ``make(client, k)`` returns ``(item, submit)`` for the client's
    ``k``-th request, with ``submit()`` returning a Future, or ``None``
    when the client has nothing more to send.  Returns ``(slots, t0,
    end)``: every request's slot in submission order per client, the
    window's start, and the time the last request resolved."""
    t0 = clock()
    t_end = t0 + seconds
    per_client: List[List[Outcome]] = [[] for _ in range(clients)]

    def run(c: int) -> None:
        k = 0
        while clock() < t_end:
            made = make(c, k)
            if made is None:
                return
            item, submit = made
            slot = Outcome(item, clock())
            slot.sent = slot.due
            per_client[c].append(slot)
            k += 1
            try:
                fut = submit()
            except Exception as exc:    # refused at admission
                slot.error, slot.done = exc, clock()
                continue
            try:
                fut.exception(timeout=timeout_s)
            except FutureTimeout as exc:    # never resolved: unanswered
                slot.error = exc
                return
            slot.resolve(fut, clock())

    threads = [threading.Thread(target=run, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + timeout_s + 5.0)
    slots = [s for client in per_client for s in client]
    done = [s.done for s in slots if s.done is not None]
    return slots, t0, (max(done) if done else clock())
