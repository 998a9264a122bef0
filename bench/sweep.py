"""Find the knee of an open-loop cell once, on the chip: the highest
offered rate the served entry keeps up with.  The benchmark's runs do not
run this; a cell's traffic file holds a rate fixed from its result.

    python3 bench/sweep.py --workload m3vit.frames.rate --rates 6,8,10 \
        --seconds 20 --seed 5

For each rate (frames per second) one run of the cell with its traffic at
that rate; one JSON line each, with the offered and completed predictions
per second and the latency percentiles.  Past the knee the completed
rate falls short of the offered one and the tail grows with the window.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import run as bench_run

    bench_run.enable_cache()
    from harness.cell import load_cell, run

    _, _, mix, _, _ = load_cell(BENCH, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        m = replace(mix, rate_frames_per_s=rate)
        out = run(BENCH, args.workload, args.seed, args.seconds, False,
                  time.perf_counter(), mix=m,
                  also=("predictions_per_s",))
        metrics = {k: v["value"] for k, v in out["metrics"].items()}
        print(json.dumps({"rate_frames_per_s": rate,
                          "offered_pred_per_s": rate * len(mix.tasks),
                          "correct": out["correct"], **metrics}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
