"""What every entry's traffic shares: the arrival times of an open mix and
the seeded order in which a pool of inputs is sent.

A mix is a data file of parameters (``bench/traffic/<name>.json``), read
by the entry that its configuration names (``harness/entries/<entry>.py``
has ``load_mix``).  Each is either

  open    requests arrive on a schedule, whatever the server does;
  closed  a fixed number of clients each send their next request when
          the answer to the last one is back.

Every seed of an open mix gets the same arrival times: the gaps are the
exponential distribution's quantiles at (i + 1/2) / n in one fixed
shuffled order, scaled so that the n arrivals span the window exactly.
The seed picks which inputs arrive, in which order (and the weights).  A
seed that also reordered the gaps would move the tail more than anything
the server does: two runs of one seed read p95 within 1-4% of each other,
six seeds 843 to 1151 ms (m3vit.frames.rate, TPU v5e).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

__all__ = ["KINDS", "ARRIVAL_ORDER_SEED", "read_mix", "arrival_times",
           "PoolOrder"]

KINDS = ("open", "closed")
ARRIVAL_ORDER_SEED = 20230529     # the one order of the arrival gaps


def read_mix(path: Path, rate_key: str) -> tuple[str, dict]:
    """(name, parameters) of a mix file whose kind is one of ``KINDS``:
    an open mix states a rate (``rate_key``, requests a second) and
    ``burst`` >= 1 requests an arrival (1 if not given), a closed one
    ``clients`` >= 1."""
    raw = json.loads(Path(path).read_text())
    kind = raw.get("kind")
    if kind not in KINDS:
        raise ValueError(f"{path}: kind {kind!r} is not one of {KINDS}")
    if kind == "open" and (float(raw.get(rate_key, 0.0)) <= 0
                           or int(raw.get("burst", 1)) < 1):
        raise ValueError(f"{path}: an open mix needs a rate and burst >= 1")
    if kind == "closed" and int(raw.get("clients", 0)) < 1:
        raise ValueError(f"{path}: a closed mix needs clients >= 1")
    return Path(path).name[:-len(".json")], raw


def arrival_times(rate: float, burst: int, seconds: float) -> np.ndarray:
    """The arrival times of an open mix of ``rate`` requests a second
    (mean), ``burst`` at each arrival, over ``seconds``, in s from the
    window's start, in order: the same for every seed."""
    n = max(1, int(round(rate * seconds / burst)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = gaps[np.random.default_rng(ARRIVAL_ORDER_SEED).permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * seconds / gaps.sum()


class PoolOrder:
    """Indices into a pool, in a seeded order that visits every entry once
    before any repeats."""

    def __init__(self, pool: int, rng: np.random.Generator):
        self.pool = pool
        self.rng = rng
        self._order: list[int] = []

    def next(self) -> int:
        if not self._order:
            self._order = list(self.rng.permutation(self.pool))
        return int(self._order.pop())
