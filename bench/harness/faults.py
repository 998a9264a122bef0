"""Faults planted in the timed path, to show that ``correct`` catches
them (``bench/tests``) and to read them on the chip (``bench/control.py``).
Each takes the warm ``M3ViTServer`` and breaks it in place."""

from __future__ import annotations

import numpy as np

__all__ = ["FAULTS", "plant"]


def stale_slot(server, slot: int = 0) -> None:
    """Paging in the last MoE layer leaves one slot stale: an expert paged
    into ``slot`` is booked as resident there, but the slot keeps the
    weights it held before, so tokens routed to that expert (about a
    quarter of them at top-4 of 16) are served by another's."""
    cache = server.paged[max(server.paged)].cache
    commit, commit_batch = cache._commit, cache._commit_batch

    def skip_write(e, s, arrays):
        write, cache._write = cache._write, (lambda slots, dev, i: slots)
        try:
            commit(e, s, arrays)
        finally:
            cache._write = write

    def stale_commit(e, s, arrays):
        (skip_write if s == slot else commit)(e, s, arrays)

    def stale_batch(batch):
        commit_batch([b for b in batch if b[1] != slot])
        for b in batch:
            if b[1] == slot:
                skip_write(*b)

    cache._commit, cache._commit_batch = stale_commit, stale_batch


def _wrap_infer(server, change) -> None:
    infer = server.infer
    state: dict = {}

    def broken(images, task):
        return change(np.array(infer(images, task)), task, state)
    server.infer = broken


def stale_answer(server) -> None:
    """Each forward returns the answers of the task's previous forward."""
    def change(y, task, state):
        prev = state.get(task)
        state[task] = y
        return y if prev is None else prev
    _wrap_infer(server, change)


def half_batch(server) -> None:
    """The batch's second half is left out: its rows repeat the first's."""
    def change(y, task, state):
        h = len(y) // 2
        y[h:] = y[:h]
        return y
    _wrap_infer(server, change)


def altered(server) -> None:
    """One answer of each forward is altered where it is produced."""
    def change(y, task, state):
        y[0] = -y[0]
        return y
    _wrap_infer(server, change)


FAULTS = {"stale_slot": stale_slot, "stale_answer": stale_answer,
          "half_batch": half_batch, "altered": altered}


def plant(server, name: str) -> None:
    FAULTS[name](server)
