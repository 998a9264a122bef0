"""From a profiler trace to device busy time, idle share, per-program
device time and idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's
own ``ProfileData`` reader, into plain tuples; ``reduce`` does the
arithmetic on those, so a test can feed it a recorded file or events it
made up.

Device planes are those named ``/device:<PLATFORM>:<n>``.  On each, the
``XLA Ops`` line holds one event per operation that ran (the ``XLA
Modules`` line is used only where there is none) and the ``XLA Modules``
line one event per program execution, named after the jitted function.
Host spans are the benchmark's own ``TraceAnnotation`` events, named
``bench.<what>``, on any host line.  The span ``bench.window`` marks the
measured window; without it the window runs from the first to the last
device event.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

__all__ = ["Trace", "Summary", "load", "find_xplane", "reduce",
           "program_name", "op_name"]

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:([A-Za-z]+):(\d+)$")


@dataclass
class Trace:
    # per device: [(start_ns, end_ns, op name)]
    ops: dict[int, list] = field(default_factory=dict)
    # per device: [(start_ns, end_ns, program name)]
    programs: dict[int, list] = field(default_factory=dict)
    # host spans: [(start_ns, end_ns, name)]
    spans: list = field(default_factory=list)
    platform: str = ""


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the devices with events
    devices: int
    program_s: dict[str, float]        # device time per program name
    program_calls: dict[str, int]
    op_s: dict[str, float]             # device time per operation name
    idle_by_span: dict[str, float]     # idle seconds by innermost host span
    longest_gaps: list                 # [(seconds, span)] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0


def program_name(raw: str) -> str:
    """``jit_dense_block(17)`` -> ``dense_block``."""
    name = re.sub(r"\(\d+\)$", "", raw.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(raw: str) -> str:
    """``%fusion.4 = f32[1671168]{...} fusion(...)`` -> ``fusion.4``."""
    head = raw.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            tr.platform = m.group(1).lower()
            dev = int(m.group(2))
            ops, progs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        progs.append((e.start_ns, e.start_ns + e.duration_ns,
                                      program_name(e.name)))
            if not ops:
                ops = list(progs)
            if ops:
                tr.ops[dev] = ops
                tr.programs[dev] = progs
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.start_ns,
                                         e.start_ns + e.duration_ns, e.name))
    return tr


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Labeller:
    """Names the innermost host span at each time of a non-decreasing
    sequence (spans of one thread nest, so a stack suffices)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: (x[0], -x[1]))
        self.i = 0
        self.stack: list = []

    def __call__(self, t) -> str:
        while self.i < len(self.spans) and self.spans[self.i][0] <= t:
            nxt = self.spans[self.i]
            while self.stack and self.stack[-1][1] <= nxt[0]:
                self.stack.pop()
            self.stack.append(nxt)
            self.i += 1
        while self.stack and self.stack[-1][1] <= t:
            self.stack.pop()
        return self.stack[-1][2][len(SPAN_PREFIX):] if self.stack \
            else "no span"


def reduce(tr: Trace, top: int = 10) -> Summary:
    windows = [(s, e) for s, e, n in tr.spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        every = [x for ops in tr.ops.values() for x in ops]
        if not every:
            raise ValueError("the trace holds no device operation")
        lo = min(x[0] for x in every)
        hi = max(x[1] for x in every)
    window = hi - lo
    spans = [x for x in tr.spans if x[2] != WINDOW_SPAN]
    busy, op_s, prog_s, calls = [], {}, {}, {}
    idle_by_span: dict[str, float] = {}
    gaps = []
    for dev, ops in sorted(tr.ops.items()):
        clipped = [(*_clip(s, e, lo, hi), n) for s, e, n in ops]
        clipped = [x for x in clipped if x[1] > x[0]]
        merged = _merge((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in clipped:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        for s, e, p in tr.programs.get(dev, []):
            cs, ce = _clip(s, e, lo, hi)
            if ce > cs:
                prog_s[p] = prog_s.get(p, 0.0) + (ce - cs) * 1e-9
                calls[p] = calls.get(p, 0) + 1
        prev = lo
        label_at = _Labeller(spans)
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                label = label_at((prev + s) / 2)
                sec = (s - prev) * 1e-9
                idle_by_span[label] = idle_by_span.get(label, 0.0) + sec
                gaps.append((sec, label))
            prev = max(prev, e)
    n = max(len(busy), 1)
    gaps.sort(key=lambda g: -g[0])
    return Summary(window_s=window * 1e-9, busy_s=sum(busy) * 1e-9 / n,
                   devices=len(busy), program_s=prog_s, program_calls=calls,
                   op_s=op_s, idle_by_span=idle_by_span,
                   longest_gaps=gaps[:top])
