"""Device idle time labelled by the served program's own spans.

The serving path (``src/repro/serve``) marks its layers with
``jax.profiler.TraceAnnotation``s named ``repro.<group>.<what>``
(``repro.sched.step``, ``repro.vision.dense``, ``repro.moe.readback``,
``repro.paging.page_in``, ...).  They land in the traced run's
``.xplane.pb`` on the same clock as the device's operations.

``load`` reads the file with ``harness.trace.load`` (device operations and
the benchmark's ``bench.*`` spans, untouched) and keeps, besides, the
``repro.*`` events of the serving thread: the host line that holds most of
them.  ``reduce`` labels each idle gap of each device by the innermost
program span open at its midpoint, as ``harness.trace.reduce`` labels them
by the benchmark's spans, and sums each span name's time inside the
window.

``read(ctx)`` is what the metric readers call.  It finds the traced run's
file where ``harness.cell.run`` writes it, checks that its window is the
one ``ctx.trace`` was reduced from, reduces it once per process and logs
the table on stderr.  It gives None where the run was not traced, the file
is another run's, or the program emits no ``repro.*`` span (a program
older than its spans).
"""

from __future__ import annotations

import functools
import os
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from harness import trace as T

__all__ = ["PREFIX", "NO_SPAN", "ProgramSpans", "load", "reduce", "read"]

PREFIX = "repro."
NO_SPAN = "no span"
TRACE_DIR = Path(__file__).resolve().parents[1] / "out" / "trace"


@dataclass
class ProgramSpans:
    window_s: float
    devices: int
    idle_s: float                      # idle seconds, summed over devices
    # idle seconds by the innermost program span open at the gap's middle
    idle_by_program_span: dict[str, float]
    # seconds per span name, clipped to the window (nested spans of one
    # name count once each)
    program_span_s: dict[str, float]

    def idle_share(self, prefix: str) -> Optional[float]:
        """Percent of the window in which the device idled under a program
        span whose name starts with ``prefix`` (mean over devices); None
        where the trace holds no device operation."""
        if not self.devices:
            return None
        s = sum(v for k, v in self.idle_by_program_span.items()
                if k.startswith(prefix))
        return 100.0 * s / (self.window_s * self.devices)


def load(path: str) -> tuple[T.Trace, list]:
    """The trace as ``harness.trace.load`` reads it, and the serving
    thread's program spans ``[(start_ns, end_ns, name)]``."""
    from jax.profiler import ProfileData

    tr = T.load(path)
    best: list = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in line.events if e.name.startswith(PREFIX)]
            if len(spans) > len(best):
                best = spans
    return tr, best


def _window(tr: T.Trace) -> Optional[tuple[float, float]]:
    """The window ``harness.trace.reduce`` takes."""
    windows = [(s, e) for s, e, n in tr.spans if n == T.WINDOW_SPAN]
    if windows:
        return windows[0]
    every = [x for ops in tr.ops.values() for x in ops]
    if not every:
        return None
    return min(x[0] for x in every), max(x[1] for x in every)


def _labeller(spans):
    """The innermost span open at each time of a non-decreasing sequence
    (spans of one thread nest, so a stack suffices)."""
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    stack: list = []
    i = 0

    def label(t) -> str:
        nonlocal i
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        return stack[-1][2] if stack else NO_SPAN
    return label


def reduce(tr: T.Trace, spans: list) -> Optional[ProgramSpans]:
    window = _window(tr) if spans else None
    if window is None:
        return None
    lo, hi = window
    idle: dict[str, float] = {}
    for ops in tr.ops.values():
        merged = T._merge(T._clip(s, e, lo, hi) for s, e, _ in ops)
        label = _labeller(spans)
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                name = label((prev + s) / 2)
                idle[name] = idle.get(name, 0.0) + (s - prev) * 1e-9
            prev = max(prev, e)
    span_s: dict[str, float] = {}
    for s, e, name in spans:
        cs, ce = T._clip(s, e, lo, hi)
        if ce > cs:
            span_s[name] = span_s.get(name, 0.0) + (ce - cs) * 1e-9
    return ProgramSpans(window_s=(hi - lo) * 1e-9, devices=len(tr.ops),
                        idle_s=sum(idle.values()),
                        idle_by_program_span=idle, program_span_s=span_s)


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> Optional[ProgramSpans]:
    got = reduce(*load(path))
    if got is not None:
        _log(got)
    return got


def _log(ps: ProgramSpans) -> None:
    def table(d):
        return ", ".join(f"{k} {v:.4f}" for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1]))
    bare = ps.idle_by_program_span.get(NO_SPAN, 0.0)
    print(f"[bench] device idle by program span (s, {ps.idle_s:.4f} s idle "
          f"in all; with no program span open {bare:.4f} s, "
          f"{100.0 * bare / ps.idle_s if ps.idle_s else 0.0:.2f}% of it): "
          f"{table(ps.idle_by_program_span)}", file=sys.stderr, flush=True)
    print(f"[bench] program span time in the window (s): "
          f"{table(ps.program_span_s)}", file=sys.stderr, flush=True)


def read(ctx) -> Optional[ProgramSpans]:
    """The program spans of the traced run ``ctx`` was read from."""
    if ctx.trace is None:
        return None
    try:
        path = T.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    got = _reduced(path, os.stat(path).st_mtime_ns)
    if got is None or abs(got.window_s - ctx.trace.window_s) > 1e-6:
        return None
    return got
