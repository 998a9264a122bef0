"""One run of one cell: set up the served entry, warm it, offer the cell's
traffic for the window, read the metrics, and check the answers against
the plain reference.

Set-up (``setup_s``) is everything from the process's start to the
window's start: imports, the device check, weights and inputs made on the
device, the entry built, and every shape the window uses run once.  The
reference runs after the window, after the device's peak memory has been
read and the served model has been let go; it is not counted anywhere.

What is served, and how, is the configuration's entry
(``harness/entries/<entry>.py``, named by the file's ``"entry"``);
everything else about a run is here.
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

from harness import flops as F
from harness.device import check_device, memory_peak_bytes, peak_for
from harness.drive import SLOW_STEP_S
from harness.model import load_config, load_reference
from harness.spans import CompileClock, Spans

__all__ = ["Ctx", "run", "load_cell", "load_entry", "reader_path"]

ENTRIES = Path(__file__).resolve().parent / "entries"


@dataclass
class Ctx:
    """What a metric reader may read."""
    cfg: dict
    seconds: float
    setup_s: float
    window: Any                        # harness.drive.Window
    completed_in_window: int
    steps: int                         # bucket forwards or decode steps
    slot_steps: int                    # requests they carried
    slots_per_bucket: int
    cache: dict                        # the entry's cache counters, window
    spans: Optional[Spans] = None
    trace: Any = None                  # harness.trace.Summary (traced run)
    peak: Any = None
    flops: Any = F


def load_entry(cfg: dict):
    """``harness/entries/<entry>.py`` of the configuration's ``entry``."""
    name = cfg["entry"]
    if not (ENTRIES / f"{name}.py").is_file():
        raise ValueError(f"no served entry {name!r} (harness/entries/"
                         f"{name}.py)")
    return importlib.import_module(f"harness.entries.{name}")


def load_cell(bench_root: Path, workload: str):
    spec = json.loads((bench_root.parent / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = load_config(bench_root.parent / conf["file"])
    mix = load_entry(cfg).load_mix(
        bench_root / "traffic" / f"{wl['traffic']}.json")
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


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(bench_root: Path, workload: str, seed: int, seconds: float,
        trace: bool, t_start: float, *, require_tpu: bool = True,
        cfg: Optional[dict] = None, mix=None,
        plant: Optional[Callable] = None,
        judge: Optional[Callable] = None,
        trace_dir: Optional[Path] = None, also: tuple = ()) -> dict:
    """One run; returns the result line's object.  ``cfg`` and ``mix``
    replace the cell's files (tests run small sizes on the CPU; the knee
    sweep varies the rate); ``plant(sched)`` is called once the entry is
    warm (tests break the timed path there); ``judge(served, answers)``
    takes the place of ``served.verdict(answers)`` (a control judges the
    window's answers another way); ``also`` names metrics read besides
    the cell's own (the sweep's completed rate)."""
    import jax

    wl, file_cfg, file_mix, e2e, per_layer = load_cell(bench_root, workload)
    cfg = cfg or file_cfg
    mix = mix or file_mix
    dev = check_device(wl["chips"], require_tpu=require_tpu)
    clock = CompileClock()

    from repro import ops

    entry = load_entry(cfg)
    served = entry.Served(cfg, mix, seed, load_reference(bench_root, cfg))
    served.warm()
    log("dispatch report (which implementation served each op): "
        + json.dumps(ops.dispatch_report(), sort_keys=True, default=str))
    if plant is not None:
        plant(served.sched)

    spans = None
    step = None
    if trace:
        spans = Spans()
        served.instrument(spans)
        step = spans.wrap(served.sched.step, "step")
    count0 = served.counters()
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
            win = served.drive(seconds, seed, step)
        jax.profiler.stop_trace()
    else:
        win = served.drive(seconds, seed)
    compile1 = clock.mark()
    count1 = served.counters()
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
              completed_in_window=done_in,
              steps=count1["steps"] - count0["steps"],
              slot_steps=count1["slot_steps"] - count0["slot_steps"],
              slots_per_bucket=served.sched.slots_per_bucket,
              cache={k: v - count0["cache"][k]
                     for k, v in count1["cache"].items()},
              spans=spans,
              peak=peak_for(dev["kind"]) if require_tpu else None)
    answers, failed = served.collect(win)

    # the served model goes before the reference comes
    served.release()
    del step
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
    verdict = (judge(served, answers) if judge is not None
               else served.verdict(answers))
    log(f"reference over {verdict['reference']} in "
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
