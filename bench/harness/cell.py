"""One run of one cell: set up the served entry, warm it, offer the cell's
traffic for the window, read the metrics, and check the answers against
the plain reference.

Set-up (``setup_s``) is everything from the process's start to the
window's start: imports, the device check, weights and frames made on the
device, the entry built, and every shape the window uses run once.  The
reference runs after the window, after the device's peak memory has been
read and the served model has been let go; it is not counted anywhere.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

from harness import correct as C
from harness import flops as F
from harness.device import check_device, memory_peak_bytes, peak_for
from harness.drive import SLOW_STEP_S, run_window
from harness.model import (arch_from, load_config, load_reference,
                           make_frames, served_params)
from harness.spans import CompileClock, Spans, instrument
from harness.traffic import load_mix
from harness.weights import make_weights

__all__ = ["Ctx", "run", "load_cell", "reader_path", "WARM_ROUNDS"]

WARM_ROUNDS = 2          # full batches per task before the window


@dataclass
class Ctx:
    """What a metric reader may read."""
    cfg: dict
    seconds: float
    setup_s: float
    window: Any                        # harness.drive.Window
    completed_in_window: int
    steps: int                         # bucket forwards in the window
    slot_steps: int                    # requests they carried
    slots_per_bucket: int
    cache: dict                        # expert-cache counters, window only
    spans: Optional[Spans] = None
    trace: Any = None                  # harness.trace.Summary (traced run)
    peak: Any = None
    flops: Any = F


def load_cell(bench_root: Path, workload: str):
    spec = json.loads((bench_root.parent / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = load_config(bench_root.parent / conf["file"])
    mix = load_mix(bench_root / "traffic" / f"{wl['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    # a per-layer metric without a list of cells is read in every cell
    # that reports the end-to-end metric it moves
    reported = {m["name"] for m in e2e}
    trace_metrics = [m for m in spec["per_layer"]
                     if (workload in m["workloads"] if "workloads" in m
                         else m["moves"] in reported)]
    return wl, cfg, mix, e2e, trace_metrics


def reader_path(bench_root: Path, name: str) -> Path:
    """``metrics/<name>.py``, or for a name ``<base>.<cells>`` without a
    file of its own, its base's reader: one quantity split by the
    end-to-end metric it moves in different cells reads the same way."""
    path = bench_root / "metrics" / f"{name}.py"
    if not path.is_file():
        path = bench_root / "metrics" / f"{name.split('.')[0]}.py"
    return path


def _reader(bench_root: Path, name: str) -> Callable:
    path = reader_path(bench_root, name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _counters(sched) -> tuple[int, int]:
    steps = slot_steps = 0
    for b in sched.buckets.values():
        steps += getattr(b, "steps", 0)
        slot_steps += getattr(b, "slot_steps", 0)
    return steps, slot_steps


def _cache(sched) -> dict:
    stats = getattr(sched.backend, "cache_stats", None)
    s = stats() if callable(stats) else {}
    return {k: s.get(k, 0) for k in ("hits", "misses", "bytes_paged")}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(bench_root: Path, workload: str, seed: int, seconds: float,
        trace: bool, t_start: float, *, require_tpu: bool = True,
        cfg: Optional[dict] = None, mix=None,
        plant: Optional[Callable] = None,
        trace_dir: Optional[Path] = None, also: tuple = ()) -> dict:
    """One run; returns the result line's object.  ``cfg`` and ``mix``
    replace the cell's files (tests run small sizes on the CPU; the knee
    sweep varies the rate); ``plant(sched)`` is called once the entry is
    warm (tests break the timed path there); ``also`` names metrics read
    besides the cell's own (the sweep's completed rate)."""
    import jax

    wl, file_cfg, file_mix, e2e, per_layer = load_cell(bench_root, workload)
    cfg = cfg or file_cfg
    mix = mix or file_mix
    dev = check_device(wl["chips"], require_tpu=require_tpu)
    clock = CompileClock()

    from repro import ops
    from repro.launch.serve import vision_scheduler
    from repro.serve import Request

    ref_mod = load_reference(bench_root, cfg)
    arch = arch_from(cfg)
    params = served_params(arch, ref_mod.param_spec(cfg), seed)
    frames = make_frames(cfg, mix.frame_pool, seed)
    sched = vision_scheduler(arch, params, batch=mix.slots)

    def request(rid, task, image, arrival):
        return Request(rid=rid, task_id=task, prompt=image, arrival=arrival)

    # warm-up: full batches of every task the window sends, through the
    # window's own scheduler and backend
    per_bucket = sched.slots_per_bucket
    for r in range(WARM_ROUNDS):
        now = sched.now()
        warm = [request(-1, t, frames[(r * per_bucket + j) % len(frames)],
                        now)
                for t in mix.tasks for j in range(per_bucket)]
        sched.run(warm)
    log("dispatch report (which implementation served each op): "
        + json.dumps(ops.dispatch_report(), sort_keys=True, default=str))
    if plant is not None:
        plant(sched)

    spans = None
    step = None
    if trace:
        spans = Spans()
        instrument(sched, spans)
        step = spans.wrap(sched.step, "step")
    steps0, slot0 = _counters(sched)
    cache0 = _cache(sched)
    compile0 = clock.mark()
    tdir = None
    if trace:
        tdir = Path(trace_dir or (bench_root / "out" / "trace"))
        _empty(tdir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the harness's spans suffice
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=options)
    setup_s = time.perf_counter() - t_start
    if trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            win = run_window(sched, request, mix, seconds, seed, frames,
                             step=step)
        jax.profiler.stop_trace()
    else:
        win = run_window(sched, request, mix, seconds, seed, frames)
    compile1 = clock.mark()
    steps1, slot1 = _counters(sched)
    cache1 = _cache(sched)
    due = win.due()
    done_in = sum(1 for r in due
                  if r.t_done is not None and r.t_done <= win.end)
    log(f"window: {len(due)} requests due, {done_in} done inside it, "
        f"{win.steps} scheduler steps (longest {win.longest_step:.3f} s, "
        f"{win.slow_steps} over {SLOW_STEP_S} s; {win.gc_full} full "
        f"collections, longest collection {win.gc_longest:.3f} s), closed "
        f"{win.closed_at - win.end:.3f} "
        f"s after its end; compiles in it {compile1[1] - compile0[1]} "
        f"({compile1[0] - compile0[0]:.3f} s), cache loads "
        f"{compile1[2] - compile0[2]}")

    device = dict(dev)
    device["memory_peak_bytes"] = memory_peak_bytes(jax.devices()[:wl["chips"]])
    ctx = Ctx(cfg=cfg, seconds=seconds, setup_s=setup_s, window=win,
              completed_in_window=done_in, steps=steps1 - steps0,
              slot_steps=slot1 - slot0, slots_per_bucket=per_bucket,
              cache={k: cache1[k] - cache0[k] for k in cache1}, spans=spans,
              peak=peak_for(dev["kind"]) if require_tpu else None)
    answers = [(f, r.task_id, None if r.t_done is None else
                np.asarray(r.result))
               for r, f in zip(win.requests, win.frames)
               if win.start <= r.arrival < win.end]
    failed = sum(1 for _, _, y in answers
                 if y is None or not np.isfinite(y).all())

    # the served model goes before the reference comes
    del sched, params, step
    gc.collect()

    breakdown = None
    if trace:
        from harness import trace as T

        t0 = time.perf_counter()
        summary = T.reduce(T.load(T.find_xplane(str(tdir))))
        ctx.trace = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = _breakdown(summary)
        log(f"trace reduced in {time.perf_counter() - t0:.2f} s: busy "
            f"{summary.busy_s:.4f} s of {summary.window_s:.4f} s")

    metrics = {}
    for m in (per_layer if trace else e2e):
        value = _reader(bench_root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for name in also:
        metrics[name] = {"value": float(_reader(bench_root, name)(ctx)),
                         "unit": ""}

    t0 = time.perf_counter()
    ref_w = make_weights(ref_mod.param_spec(cfg), seed)
    reference = C.reference_answers(ref_mod, cfg, ref_w, frames,
                                    [(f, t) for f, t, _ in answers])
    verdict = C.compare(answers, reference, cfg["limits"],
                        cfg["image"]["patch"])
    log(f"reference over {len(reference)} frame-task pairs in "
        f"{time.perf_counter() - t0:.2f} s; {verdict['compared']} answers "
        f"compared; shown, not compared: {verdict['shown']!r}")
    for name, c in verdict["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": verdict["correct"], "attempted": len(answers),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = verdict["checks"]
    return out


def _breakdown(summary) -> dict:
    progs = sorted(summary.program_s.items(), key=lambda kv: -kv[1])[:5]
    ops_ = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:5]
    idle = sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[f"program {k}", v] for k, v in progs]
            + [[f"op {k}", v] for k, v in ops_],
            "idle_gaps": [[k, v] for k, v in idle]}


def _empty(path: Path) -> None:
    import shutil

    if path.exists():
        shutil.rmtree(path)
    path.mkdir(parents=True)
