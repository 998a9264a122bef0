"""Readings that set a configuration's correctness limits, on the chip at
the cell's own size.  The benchmark's runs do not run this.

    python3 bench/control.py --config m3vit --program-seeds 1,2,... \
        --int8-seeds 1,2,3 --fp8-seeds 1,2,3 --fault-seeds 1,2,3

For each seed, over every frame of the cell's pool and every task it
sends (as many distinct answers as a run compares), the numbers of
``harness.correct`` against the float32 reference, and the verdict that
``harness.correct.compare`` gives them under the configuration's limits:

  program  the served forward (``M3ViTServer.infer`` of the entry's
           backend, one bucket's batch at a time, the entry's defaults):
           sound runs, whose largest readings are the limits' lower ones;
  int8     the control: the program's own int8-weight path
           (``quant.quantize_tree`` and the ``xla_int8`` policy), the
           precision step below the bfloat16 that the configuration
           states; its smallest readings are the limits' upper ones;
  fp8      the reference computed in float8 (e4m3) operands;
  faults   the program with each of ``--faults`` planted
           (``harness.faults``), by default a stale expert slot in the
           last MoE layer.

One JSON line per reading on standard output.
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


def served_answers(arch, params, frames, tasks, batch, fault=None):
    from harness.faults import plant
    from repro.serve.vision import M3ViTServer

    server = M3ViTServer(arch, params)
    if fault is not None:
        # planted once warm, as a run plants it: the slots hold experts
        for t in tasks:
            server.infer(frames[:batch], t)
        plant(server, fault)
    out = {}
    for t in tasks:
        for i in range(0, len(frames), batch):
            idx = list(range(i, min(i + batch, len(frames))))
            idx += [idx[0]] * (batch - len(idx))
            y = server.infer(frames[idx], t)
            for j, f in enumerate(idx):
                out[(f, t)] = y[j]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="frames.sat")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--int8-seeds", default="")
    ap.add_argument("--fp8-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="stale_slot")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import run as bench_run

    bench_run.enable_cache()
    from harness.correct import compare, numbers, reference_answers
    from harness.device import check_device
    from harness.model import (arch_from, load_config, load_reference,
                               make_frames, served_params)
    from harness.traffic import load_mix
    from harness.weights import make_weights

    check_device(1)
    cfg = load_config(BENCH / "configs" / f"{args.config}.json")
    mix = load_mix(BENCH / "traffic" / f"{args.traffic}.json")
    batch = mix.slots // len(mix.tasks)
    patch = cfg["image"]["patch"]
    ref_mod = load_reference(BENCH, cfg)
    arch = arch_from(cfg)
    faults = [f for f in args.faults.split(",") if f]

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    todo: dict[int, list[str]] = {}
    for kind in ("program", "int8", "fp8", "fault"):
        for s in seeds(getattr(args, f"{kind}_seeds")):
            todo.setdefault(s, []).extend(faults if kind == "fault"
                                          else [kind])
    for seed, kinds in todo.items():
        t0 = time.perf_counter()
        frames = make_frames(cfg, mix.frame_pool, seed)
        pairs = [(f, t) for f in range(len(frames)) for t in mix.tasks]
        w = make_weights(ref_mod.param_spec(cfg), seed)
        ref = reference_answers(ref_mod, cfg, w, frames, pairs)
        got = {}
        if "fp8" in kinds:
            got["fp8"] = reference_answers(ref_mod, cfg, w, frames, pairs,
                                           "fp8")
        del w
        served = [k for k in kinds if k != "fp8"]
        if served:
            params = served_params(arch, ref_mod.param_spec(cfg), seed)
        for kind in served:
            if kind == "int8":
                from repro.ops import policy_named
                from repro.quant import quantize_tree

                got[kind] = served_answers(
                    replace(arch, policy=policy_named("xla_int8")),
                    quantize_tree(params, bits=8), frames, mix.tasks, batch)
            else:
                got[kind] = served_answers(
                    arch, params, frames, mix.tasks, batch,
                    None if kind == "program" else kind)
        for kind, ans in got.items():
            answers = [(f, t, y) for (f, t), y in ans.items()]
            verdict = compare(answers, ref, cfg["limits"], patch)
            print(json.dumps({"config": cfg["name"], "seed": seed,
                              "kind": kind, "correct": verdict["correct"],
                              **numbers(answers, ref, patch),
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
