"""Autotuned tile schedules: per-(op, shape-bucket, backend) block sizes.

Replaces the hard-coded ``block_q=128, block_k=512`` constants that used to
live in ``kernels/ops.py``.  The table (``schedules.json`` next to this
module) is a small measured artifact produced by ``benchmarks/ops_autotune.py``
and shipped with sane defaults for both the CPU ``interpret`` backend (what
CI measures) and ``tpu`` (Mosaic lowering).  Each backend's section stands
alone: every kernel has a ``tpu`` entry that compiles at the M³ViT widths,
so a TPU never runs the tiles tuned for the CPU interpreter.

Resolution order for a block size, strongest last:

  1. table ``defaults`` for ``"<op>.<impl>"``;
  2. every ``buckets`` entry whose ``min`` dims the call shape meets
     (buckets are listed ascending, so the tightest match wins);
  3. the ambient :class:`~repro.ops.policy.ComputePolicy` ``tiles``
     override (applied by the caller, see ``registry.dispatch``);
  4. an explicit ``block_*=`` keyword at the call site.

No ``repro`` imports — ``kernels/ops.py`` consults this module directly.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Optional

__all__ = ["schedule_for", "load_table", "table_entries", "backend_key"]

_TABLE_PATH = os.path.join(os.path.dirname(__file__), "schedules.json")


@functools.lru_cache(maxsize=None)
def load_table(path: Optional[str] = None) -> dict:
    # plain-dict cache (no device arrays) — safe to memoize across mesh
    # changes, unlike lru_caches over jax.Arrays

    with open(path or _TABLE_PATH) as f:
        table = json.load(f)
    if "backends" not in table:
        raise ValueError(f"schedule table {path or _TABLE_PATH} has no "
                         "'backends' section")
    return table


def backend_key() -> str:
    """``"tpu"`` on TPU, ``"interpret"`` everywhere else (kernels run in
    interpret mode off-TPU — see ``kernels/ops.py``)."""
    import jax

    return "tpu" if jax.default_backend() == "tpu" else "interpret"


def table_entries(path: Optional[str] = None) -> dict:
    """Flat {backend: {op.impl: entry}} view, for validation tooling."""
    return load_table(path)["backends"]


def _bucket_matches(min_dims: dict, dims: dict) -> bool:
    return all(dims.get(k, 0) >= v for k, v in min_dims.items())


def schedule_for(op: str, impl: str, dims: Optional[dict] = None,
                 backend: Optional[str] = None,
                 path: Optional[str] = None) -> dict:
    """Resolved block sizes for ``op`` served by ``impl`` at shape ``dims``.

    ``dims`` carries the bucketing dimensions (attention: sq/skv/d; linear:
    m/n/k; moe: e/c/d/f).  Unknown ops return {} so callers can fall back
    to their own defaults.
    """
    backends = load_table(path)["backends"]
    key = f"{op}.{impl}"
    bk = backend or backend_key()
    entry = backends.get(bk, {}).get(key)
    if entry is None:
        return {}
    blocks = dict(entry.get("defaults", {}))
    dims = dims or {}
    for bucket in entry.get("buckets", ()):
        if _bucket_matches(bucket.get("min", {}), dims):
            blocks.update({k: v for k, v in bucket.items() if k != "min"})
    return blocks
