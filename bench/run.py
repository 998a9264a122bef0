"""Chip benchmark of the M³ViT vision serving path.

    python3 bench/run.py --workload m3vit.frames.rate --seed 7 \
        --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json`` (one configuration under one traffic
mix) on the machine it is started on, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones read
from a profiler trace of the window), ``device`` and, traced,
``breakdown``; then ``checks``, each number compared with its limit.  It
exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell needs.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), so only a
cell's first run in a checkout compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def enable_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("bench: the program under test (src/repro) is not "
                         "in this checkout")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    enable_cache()
    from harness.cell import run

    out = run(BENCH, args.workload, args.seed, args.seconds,
              bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
