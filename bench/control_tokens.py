"""Readings that set a token configuration's correctness limits: whole
runs of a cell of the tokens entry, on the machine this is started on.
The benchmark's runs do not run this.

    python3 bench/control_tokens.py --workload <cell> --seconds 10 \
        --program-seeds 1,2,... --fp8-seeds 1,2,3 --fault-seeds 1,2,3

For each seed, one run of the cell (``harness.cell.run``: the window at
the cell's own load, then the comparison of its answers with the plain
float32 reference), under the configuration's limits:

  program  the served model as the cell runs it: sound runs, whose
           largest readings are the limits' lower ones;
  fp8      the control: at each position of the program's answers, the
           token that the reference computed in float8 operands puts
           first, judged in place of the served one; its smallest
           readings are the limits' upper ones;
  faults   the program with each of ``--faults`` planted
           (``harness.faults``; all of the tokens entry's by default).

``--root`` names another checkout's ``bench/`` (a cell that is not in
this one's ``BENCHMARK.json``), ``--cpu`` runs without a chip (small
sizes).  One JSON line per reading on standard output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NUMBERS = ("worst_first_gap", "worst_answer_gap", "far_position_share",
           "unanswered", "nonfinite")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--fp8-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--root", default=str(BENCH))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import run as bench_run

    if not args.cpu:
        bench_run.enable_cache()
    from harness import faults as FL
    from harness.cell import run
    from harness.entries import tokens as TK

    def fp8(served, answers):
        return TK.Served.verdict(served, answers, judge="fp8")

    names = [f for f in args.faults.split(",") if f] or list(FL.TOKEN_FAULTS)

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    todo = [("program", s) for s in seeds(args.program_seeds)]
    todo += [("fp8", s) for s in seeds(args.fp8_seeds)]
    todo += [(f, s) for s in seeds(args.fault_seeds) for f in names]
    for kind, seed in todo:
        plant = None if kind in ("program", "fp8") else (
            lambda sched, k=kind: FL.plant(sched.backend, k))
        t0 = time.perf_counter()
        out = run(Path(args.root), args.workload, seed, args.seconds, False,
                  t0, require_tpu=not args.cpu, plant=plant,
                  judge=fp8 if kind == "fp8" else None)
        checks = out["checks"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "kind": kind, "correct": out["correct"],
                          "attempted": out["attempted"],
                          **{k: checks[k]["value"] for k in NUMBERS},
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
