"""Drive the served entry's scheduler through one measured window.

The loop is ``Scheduler.run``'s own (step; sleep half a millisecond when
nothing is runnable), with two additions: the closed loop's clients send
their next frame as each answer comes back, and the window closes at a
fixed time.  Every request due inside the window is followed to its end,
up to ``DRAIN_S`` past the close; one that is still open then has failed.
Times are the scheduler's clock, seconds from its start.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

from harness.traffic import FrameOrder, Mix, open_schedule

__all__ = ["Window", "run_window", "DRAIN_S", "SLOW_STEP_S"]

DRAIN_S = 60.0
SLOW_STEP_S = 0.4               # two forwards' time: a stall, not work


@dataclass
class Window:
    start: float
    end: float
    closed_at: float = 0.0          # when the last due request finished
    requests: list = field(default_factory=list)     # scheduler Requests
    frames: list = field(default_factory=list)       # frame of each request
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


def run_window(sched, make_request, mix: Mix, seconds: float, seed: int,
               images, step=None) -> Window:
    """Offer ``mix`` to ``sched`` for ``seconds``.  ``make_request(rid,
    task, image, arrival)`` builds the entry's request; ``step`` stands in
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
        _drive(win, sched, make_request, mix, seconds, seed, images, step)
    finally:
        gc.callbacks.remove(collected)
    return win


def _drive(win, sched, make_request, mix, seconds, seed, images, step):
    start = win.start

    def send(task: int, frame: int, due: float):
        r = make_request(len(win.requests), task, images[frame], due)
        win.requests.append(r)
        win.frames.append(frame)
        sched.submit(r)
        return r

    client_of: dict[int, int] = {}
    next_task: dict[int, int] = {}
    rng = np.random.default_rng(seed)
    if mix.kind == "open":
        for due, frame, task in open_schedule(mix, seconds, seed):
            send(task, frame, start + due)
    else:
        order = FrameOrder(mix.frame_pool, rng)
        for c in range(mix.clients):
            k = c % len(mix.tasks)
            r = send(mix.tasks[k], order.next(), start)
            client_of[r.rid] = c
            next_task[c] = (k + 1) % len(mix.tasks)
    seen = len(sched.finished)
    deadline = win.end + DRAIN_S
    turn = time.perf_counter()
    while True:
        ran = step()
        win.steps += 1
        done = sched.finished[seen:]
        seen = len(sched.finished)
        for r in done:
            c = client_of.pop(r.rid, None) if mix.kind == "closed" else None
            if c is not None and r.t_done < win.end:
                k = next_task[c]
                nr = send(mix.tasks[k], order.next(), r.t_done)
                client_of[nr.rid] = c
                next_task[c] = (k + 1) % len(mix.tasks)
        now = sched.now()
        if not sched.pending() and (mix.kind == "open" or now >= win.end):
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
