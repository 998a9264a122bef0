"""Spans in the trace: the benchmark's own wrappers find every attribute
they wrap on the real server, and the program's ``repro.*`` spans label
the device's idle gaps (``harness.program_spans``) without moving any
number the trace reduction gave before."""

import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from harness import program_spans as PS
from harness import trace as T
from harness.cell import _reader
from harness.entries.vision import _Layer, instrument
from harness.spans import Spans
from test_bench_run import small_run
from test_bench_trace import FIXTURE, synthetic

BENCH = Path(__file__).resolve().parents[1]
MS = 1_000_000

SERVER_CALLS = ("infer", "prefetch", "_embed", "_dense", "_moe_pre",
                "_final")
CACHE_CALLS = ("ensure", "_write")


def _wrapped(fn) -> bool:
    return getattr(fn, "__qualname__", "") == "Spans.wrap.<locals>.call"


def test_instrument_finds_every_attribute_on_the_server():
    """``instrument`` skips a missing attribute without a word, so a
    rename in the server would drop a ``bench.*`` span unseen."""
    from repro import configs
    from repro.launch.serve import vision_scheduler
    from repro.models import vit as V

    cfg = configs.get("m3vit", smoke=True)
    params = V.init_params(jax.random.PRNGKey(0), cfg)
    sched = vision_scheduler(cfg, params, batch=4)
    server = sched.backend.server
    for attr in SERVER_CALLS:
        assert callable(getattr(server, attr, None)), attr
    assert server._heads and all(callable(f) for f in server._heads.values())
    assert server.paged
    for layer in server.paged.values():
        for attr in CACHE_CALLS:
            assert callable(getattr(layer.cache, attr, None)), attr

    instrument(sched, Spans())
    for attr in SERVER_CALLS:
        assert _wrapped(getattr(server, attr)), attr
    assert all(_wrapped(f) for f in server._heads.values())
    for layer in server.paged.values():
        assert isinstance(layer, _Layer)
        for attr in CACHE_CALLS:
            assert _wrapped(getattr(layer.cache, attr)), attr


def with_program_spans():
    """The synthetic trace of ``test_bench_trace``, and program spans of
    its serving thread: a step over the window (and past it), an ensure
    whose page-in holds the gap at 4-6 ms, a router readback over the gap
    at 7-10 ms, and a span wholly after the window."""
    tr = synthetic()
    spans = [(0, 10_500_000, "repro.sched.step"),
             (4 * MS, 6 * MS, "repro.paging.ensure"),
             (4_200_000, 5_500_000, "repro.paging.page_in"),
             (7 * MS, 9 * MS, "repro.moe.readback"),
             (12 * MS, 13 * MS, "repro.sched.step")]
    return tr, spans


def test_program_spans_label_the_idle_gaps():
    ps = PS.reduce(*with_program_spans())
    assert ps.window_s == pytest.approx(0.010)
    assert ps.devices == 1
    assert ps.idle_s == pytest.approx(0.006)
    # gaps [0,1] ms (midpoint under the step), [4,6] (the page-in at 5),
    # [7,10] (8.5: the readback)
    assert ps.idle_by_program_span == pytest.approx({
        "repro.sched.step": 0.001, "repro.paging.page_in": 0.002,
        "repro.moe.readback": 0.003})
    # clipped to the window; the later step lies outside it
    assert ps.program_span_s == pytest.approx({
        "repro.sched.step": 0.010, "repro.paging.ensure": 0.002,
        "repro.paging.page_in": 0.0013, "repro.moe.readback": 0.002})
    assert ps.idle_share("repro.paging.") == pytest.approx(20.0)
    assert ps.idle_share("repro.moe.") == pytest.approx(30.0)
    assert ps.idle_share("repro.vision.") == 0.0


def test_gaps_outside_every_program_span():
    tr, spans = with_program_spans()
    ps = PS.reduce(tr, [s for s in spans if s[2] != "repro.sched.step"])
    assert ps.idle_by_program_span == pytest.approx({
        PS.NO_SPAN: 0.001, "repro.paging.page_in": 0.002,
        "repro.moe.readback": 0.003})


def test_existing_summary_is_untouched_by_program_spans():
    """The benchmark's own reduction reads the same numbers with the
    program's spans in the trace as without them."""
    tr, _ = with_program_spans()
    assert T.reduce(tr) == T.reduce(synthetic())
    s = T.reduce(tr)
    assert s.idle_by_span == pytest.approx({"step": 0.004, "ensure": 0.002})
    assert s.longest_gaps[0] == pytest.approx((0.003, "step"))


def test_recorded_trace_without_program_spans():
    """The recorded v5e trace predates the program's spans: the trace
    reduction reads what it always read, and the program spans read
    nothing."""
    tr, spans = PS.load(str(FIXTURE))
    assert spans == []
    assert PS.reduce(tr, spans) is None
    s = T.reduce(tr)
    assert s == T.reduce(T.load(str(FIXTURE)))
    assert s.window_s == pytest.approx(0.009684969)
    assert s.longest_gaps[0][1] == "host_wait"


def test_program_spans_without_device_operations():
    tr, spans = with_program_spans()
    tr.ops, tr.programs = {}, {}
    ps = PS.reduce(tr, spans)            # the window is the bench span
    assert ps.devices == 0 and ps.idle_by_program_span == {}
    assert ps.idle_share("repro.paging.") is None
    assert ps.program_span_s["repro.paging.page_in"] == pytest.approx(
        0.0013)


def _ctx(completed=4):
    return SimpleNamespace(trace=SimpleNamespace(window_s=0.010),
                           completed_in_window=completed)


@pytest.mark.parametrize("name,value", [
    ("paging_idle_share", 20.0),
    ("paging_idle_share.rate", 20.0),
    ("moe_control_idle_share", 30.0),
    ("moe_control_idle_share.rate", 30.0),
    ("page_in_ms_per_pred", 1.3 / 4),
])
def test_readers(monkeypatch, name, value):
    ps = PS.reduce(*with_program_spans())
    monkeypatch.setattr(PS, "read", lambda ctx: ps)
    assert _reader(BENCH, name)(_ctx()) == pytest.approx(value)
    # a trace without program spans (the parent program) reads nothing
    monkeypatch.setattr(PS, "read", lambda ctx: None)
    assert _reader(BENCH, name)(_ctx()) is None


def test_page_ins_without_answers_read_nothing(monkeypatch):
    ps = PS.reduce(*with_program_spans())
    monkeypatch.setattr(PS, "read", lambda ctx: ps)
    assert _reader(BENCH, "page_in_ms_per_pred")(_ctx(0)) is None


def test_read_takes_only_this_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(PS, "TRACE_DIR", tmp_path)
    assert PS.read(SimpleNamespace(trace=None)) is None
    assert PS.read(_ctx()) is None             # no file
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("repro.sched.step"):
                time.sleep(0.002)
    tr, spans = PS.load(T.find_xplane(str(tmp_path)))
    assert [s[2] for s in spans] == ["repro.sched.step"]
    window = T.reduce(tr).window_s
    got = PS.read(SimpleNamespace(trace=SimpleNamespace(window_s=window)))
    assert got.program_span_s["repro.sched.step"] > 0.0015
    # another run's window: the file is not this run's
    assert PS.read(SimpleNamespace(
        trace=SimpleNamespace(window_s=window + 1e-3))) is None


def test_traced_run_reads_the_program_spans(tmp_path, monkeypatch):
    """A whole traced run of the closed-loop cell on the CPU: the CPU's
    trace holds no device operations, so the idle shares read nothing,
    but the page-in time does."""
    monkeypatch.setattr(PS, "TRACE_DIR", tmp_path)
    out = small_run(trace=True, tmp_path=tmp_path)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    assert m["page_in_ms_per_pred"]["value"] > 0
    assert m["page_in_ms_per_pred"]["unit"] == "ms/pred"
    assert "paging_idle_share" not in m
    assert "moe_control_idle_share" not in m


def test_read_reduces_a_file_once(tmp_path, monkeypatch):
    """``read`` reduces a file once, however many readers ask."""
    tr, spans = with_program_spans()
    calls = []
    monkeypatch.setattr(PS, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(PS, "load", lambda p: calls.append(p) or (tr, spans))
    (tmp_path / "a.xplane.pb").write_bytes(b"")
    PS._reduced.cache_clear()
    for _ in range(3):
        assert PS.read(_ctx()) is not None
    assert len(calls) == 1
    PS._reduced.cache_clear()
