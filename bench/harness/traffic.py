"""The one traffic generator: every mix is a data file of parameters.

A mix (``bench/traffic/<name>.json``) is either

  open    frames arrive on a schedule, whatever the server does:
          ``rate_frames_per_s`` (mean), ``burst`` frames per arrival
          (1: a steady Poisson stream), each frame asking for every task
          in ``tasks`` at the same due time;
  closed  ``clients`` clients each send one frame with one task and,
          when its answer is back, the next frame with the next task of
          ``tasks`` (client c starts at task ``c mod len(tasks)``);

plus ``slots`` (scheduler slots in all) and ``frame_pool`` (distinct
frames, drawn from the seed; frames are taken from the pool in a seeded
order).

Every seed of an open mix gets the same arrival times: the gaps are the
exponential distribution's quantiles at (i + 1/2) / n in one fixed
shuffled order, scaled so that the n arrivals span the window exactly.
The seed picks which frames arrive, in which order (and the weights).  A
seed that also reordered the gaps would move the tail more than anything
the server does: two runs of one seed read p95 within 1-4% of each other,
six seeds 843 to 1151 ms (m3vit.frames.rate, TPU v5e).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

__all__ = ["Mix", "load_mix", "open_schedule", "FrameOrder"]

KINDS = ("open", "closed")
ARRIVAL_ORDER_SEED = 20230529     # the one order of the arrival gaps


@dataclass(frozen=True)
class Mix:
    name: str
    kind: str
    slots: int
    tasks: tuple[int, ...]
    frame_pool: int
    rate_frames_per_s: float = 0.0
    burst: int = 1
    clients: int = 0


def load_mix(path: Path) -> Mix:
    raw = json.loads(Path(path).read_text())
    kind = raw["kind"]
    if kind not in KINDS:
        raise ValueError(f"{path}: kind {kind!r} is not one of {KINDS}")
    mix = Mix(name=Path(path).name[:-len(".json")], kind=kind,
              slots=int(raw["slots"]), tasks=tuple(int(t) for t in raw["tasks"]),
              frame_pool=int(raw["frame_pool"]),
              rate_frames_per_s=float(raw.get("rate_frames_per_s", 0.0)),
              burst=int(raw.get("burst", 1)),
              clients=int(raw.get("clients", 0)))
    if kind == "open" and (mix.rate_frames_per_s <= 0 or mix.burst < 1):
        raise ValueError(f"{path}: an open mix needs a rate and burst >= 1")
    if kind == "closed" and mix.clients < 1:
        raise ValueError(f"{path}: a closed mix needs clients >= 1")
    return mix


class FrameOrder:
    """Frame indices into the pool, in a seeded order that visits every
    frame once before any repeats."""

    def __init__(self, pool: int, rng: np.random.Generator):
        self.pool = pool
        self.rng = rng
        self._order: list[int] = []

    def next(self) -> int:
        if not self._order:
            self._order = list(self.rng.permutation(self.pool))
        return int(self._order.pop())


def open_schedule(mix: Mix, seconds: float, seed: int):
    """[(due time in s from the window's start, frame, task)] of an open
    mix, in due order."""
    n = max(1, int(round(mix.rate_frames_per_s * seconds / mix.burst)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = gaps[np.random.default_rng(ARRIVAL_ORDER_SEED).permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * seconds / gaps.sum()
    frames = FrameOrder(mix.frame_pool, np.random.default_rng(seed))
    out = []
    for t in due:
        for _ in range(mix.burst):
            f = frames.next()
            out.extend((float(t), f, task) for task in mix.tasks)
    return out
