"""Host spans that the benchmark's own code puts around calls into each
layer of the served entry, and the clock of JAX's compilations.

``Spans`` wraps a callable so that each call is a
``jax.profiler.TraceAnnotation`` named ``bench.<name>`` (what labels the
device's idle gaps in a trace) and, where asked, records its host time.
A traced run instruments; an untimed run never does.  An attribute that
the program does not have is skipped: a span then goes missing, and its
time falls to the span around it.
"""

from __future__ import annotations

import time
from collections import defaultdict

import jax

__all__ = ["Spans", "CompileClock", "instrument"]


class Spans:
    def __init__(self):
        self.seconds: dict[str, list[float]] = defaultdict(list)

    def wrap(self, fn, name: str, timed: bool = False):
        label = "bench." + name
        record = self.seconds[name].append if timed else None

        def call(*args, **kwargs):
            with jax.profiler.TraceAnnotation(label):
                if record is None:
                    return fn(*args, **kwargs)
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                record(time.perf_counter() - t0)
                return out
        return call

    def wrap_attr(self, obj, attr: str, name: str, timed: bool = False):
        fn = getattr(obj, attr, None)
        if callable(fn):
            setattr(obj, attr, self.wrap(fn, name, timed))

    def wrap_items(self, mapping, name: str):
        if isinstance(mapping, dict):
            for k, fn in list(mapping.items()):
                if callable(fn):
                    mapping[k] = self.wrap(fn, name)


class _Layer:
    """A paged MoE layer seen through a span: calls go through one,
    everything else reaches the layer itself."""

    def __init__(self, layer, call):
        self._layer = layer
        self._call = call

    def __call__(self, *args, **kwargs):
        return self._call(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._layer, name)


def instrument(sched, spans: Spans) -> None:
    """Spans around the served entry's layers: the scheduler's step is
    wrapped by the caller; here the vision backend's forward (timed), its
    stages, each paged MoE layer, the expert cache's ``ensure`` and slot
    writes, and the task heads."""
    server = getattr(sched.backend, "server", None)
    if server is None:
        return
    spans.wrap_attr(server, "infer", "infer", timed=True)
    spans.wrap_attr(server, "prefetch", "prefetch")
    for attr, name in (("_embed", "embed"), ("_dense", "dense"),
                       ("_moe_pre", "moe_pre"), ("_final", "final_norm")):
        spans.wrap_attr(server, attr, name)
    spans.wrap_items(getattr(server, "_heads", None), "head")
    paged = getattr(server, "paged", None)
    if isinstance(paged, dict):
        for i, layer in list(paged.items()):
            cache = getattr(layer, "cache", None)
            if cache is not None:
                spans.wrap_attr(cache, "ensure", "ensure")
                spans.wrap_attr(cache, "_write", "cache_write")
            paged[i] = _Layer(layer, spans.wrap(layer, "moe"))


class CompileClock:
    """Seconds JAX spends compiling (or loading from the persistent cache)
    and the persistent-cache hits, from ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits
