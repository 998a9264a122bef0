"""The verdict that every entry gives: each number it compared beside its
limit from the configuration file, and ``correct`` when every one is
within its limit.  What the numbers are is the entry's
(``harness/entries/<entry>.py``): each against the plain reference.

Answers never given and answers that are not finite are counted too,
each with the limit 0.
"""

from __future__ import annotations

import numpy as np

__all__ = ["rel_l2", "verdict"]


def rel_l2(y, ref) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(y - ref) / max(np.linalg.norm(ref), 1e-30))


def verdict(got: dict, limits: dict, shown: tuple = ()) -> dict:
    """``got`` holds every number named in ``limits`` (the
    configuration's), ``unanswered``, ``nonfinite``, ``compared`` (how
    many answers were compared) and the numbers in ``shown``, which are
    logged and not compared."""
    limits = {**limits, "unanswered": 0, "nonfinite": 0}
    checks = {k: {"value": got[k], "limit": v} for k, v in limits.items()}
    # NaN (nothing compared) fails every comparison
    correct = got["compared"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks,
            "compared": got["compared"], "shown": {k: got[k] for k in shown}}
