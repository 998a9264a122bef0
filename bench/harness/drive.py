"""Drive the served entry's scheduler through one measured window.

The loop is ``Scheduler.run``'s own (step; sleep half a millisecond when
nothing is runnable), with two additions: the closed loop's clients send
their next request as each answer comes back, and the window closes at a
fixed time.  What is sent comes from the entry's load (``Load``): the
open mix's schedule, or each client's next input and task.  Every
request due inside the window is followed to its end, up to ``DRAIN_S``
past the close; one that is still open then has failed.
Times are the scheduler's clock, seconds from its start.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Any, Protocol

import numpy as np

__all__ = ["Load", "Window", "run_window", "step_counters", "DRAIN_S",
           "SLOW_STEP_S"]

DRAIN_S = 60.0
SLOW_STEP_S = 0.4               # two forwards' time: a stall, not work


class Load(Protocol):
    """An entry's traffic for one window.  ``item`` is what the entry
    needs to find a request's input again (a frame's index in the pool,
    a prompt's), kept on the window beside each request."""

    kind: str                       # "open" | "closed"
    clients: int                    # closed: clients sending at once

    def schedule(self, seconds: float) -> list:
        """Open: [(due time in s from the window's start, item, task)] in
        due order."""

    def first(self, client: int) -> tuple[Any, int]:
        """Closed: (item, task) of the client's first request."""

    def then(self, client: int) -> tuple[Any, int]:
        """Closed: (item, task) of the client's next request."""

    def request(self, rid: int, item, task: int, arrival: float):
        """The scheduler's request for ``item`` and ``task``."""


@dataclass
class Window:
    start: float
    end: float
    closed_at: float = 0.0          # when the last due request finished
    requests: list = field(default_factory=list)     # scheduler Requests
    items: list = field(default_factory=list)        # item of each request
    steps: int = 0                                   # scheduler steps run
    longest_step: float = 0.0       # host seconds of the longest loop turn
    slow_steps: int = 0             # turns longer than SLOW_STEP_S
    gc_longest: float = 0.0         # host seconds of the longest collection
    gc_full: int = 0                # full (generation 2) collections

    def due(self):
        return [r for r in self.requests if self.start <= r.arrival < self.end]

    def latencies(self) -> np.ndarray:
        """Due time to answer of every request due in the window, in s.  A
        request still open when the run gave up counts with the time it
        had waited by then, a lower bound."""
        return np.asarray([(r.t_done if r.t_done is not None
                            else self.closed_at) - r.arrival
                           for r in self.due()], np.float64)


def step_counters(sched) -> tuple[int, int]:
    """(steps, slot steps) of the scheduler's buckets so far: bucket
    forwards or decode steps, and the requests they carried."""
    steps = slot_steps = 0
    for b in sched.buckets.values():
        steps += getattr(b, "steps", 0)
        slot_steps += getattr(b, "slot_steps", 0)
    return steps, slot_steps


def run_window(sched, load: Load, seconds: float, step=None) -> Window:
    """Offer ``load`` to ``sched`` for ``seconds``.  ``step`` stands in
    for ``sched.step`` (a traced run wraps it in a span)."""
    step = step or sched.step
    start = sched.now()
    win = Window(start=start, end=start + seconds)
    began = [0.0]

    def collected(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
            return
        win.gc_longest = max(win.gc_longest, time.perf_counter() - began[0])
        win.gc_full += info["generation"] == 2

    gc.callbacks.append(collected)
    try:
        _drive(win, sched, load, seconds, step)
    finally:
        gc.callbacks.remove(collected)
    return win


def _drive(win, sched, load, seconds, step):
    start = win.start
    closed = load.kind == "closed"

    def send(item, task: int, due: float):
        r = load.request(len(win.requests), item, task, due)
        win.requests.append(r)
        win.items.append(item)
        sched.submit(r)
        return r

    client_of: dict[int, int] = {}
    if closed:
        for c in range(load.clients):
            r = send(*load.first(c), start)
            client_of[r.rid] = c
    else:
        for due, item, task in load.schedule(seconds):
            send(item, task, start + due)
    seen = len(sched.finished)
    deadline = win.end + DRAIN_S
    turn = time.perf_counter()
    while True:
        ran = step()
        win.steps += 1
        done = sched.finished[seen:]
        seen = len(sched.finished)
        for r in done:
            c = client_of.pop(r.rid, None) if closed else None
            if c is not None and r.t_done < win.end:
                nr = send(*load.then(c), r.t_done)
                client_of[nr.rid] = c
        now = sched.now()
        if not sched.pending() and (not closed or now >= win.end):
            break
        if now > deadline:
            break
        if not ran:
            time.sleep(0.0005)
        # a turn is one step, or one idle poll: a long one is a stall
        took, turn = time.perf_counter() - turn, time.perf_counter()
        win.longest_step = max(win.longest_step, took)
        win.slow_steps += took > SLOW_STEP_S
    win.closed_at = sched.now()
