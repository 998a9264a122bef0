"""The trace reduction: busy union, idle share, per-program device time
and idle gaps labelled by the host span they fall in."""

from pathlib import Path

import pytest

from harness import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_small_step.xplane.pb"


def synthetic():
    ms = 1_000_000
    tr = T.Trace(platform="tpu")
    # two overlapping ops, one inside a later program, one outside the window
    tr.ops[0] = [(1 * ms, 3 * ms, "fusion.1"), (2 * ms, 4 * ms, "fusion.2"),
                 (6 * ms, 7 * ms, "fusion.1"), (20 * ms, 21 * ms, "copy")]
    tr.programs[0] = [(1 * ms, 4 * ms, "wave"), (6 * ms, 7 * ms, "wave"),
                      (20 * ms, 21 * ms, "route")]
    tr.spans = [(0, 10 * ms, "bench.window"), (0, 10 * ms, "bench.step"),
                (4 * ms, 6 * ms, "bench.ensure"),
                (4 * ms, 5 * ms, "bench.cache_write")]
    return tr


def test_reduce_synthetic():
    s = T.reduce(synthetic())
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.004)          # [1,4] and [6,7] ms
    assert s.idle_share == pytest.approx(0.6)
    assert s.program_s == pytest.approx({"wave": 0.004})
    assert s.program_calls == {"wave": 2}
    assert s.op_s == pytest.approx({"fusion.1": 0.003, "fusion.2": 0.002})
    # gaps: [0,1] step, [4,6] midpoint 5 ms: cache_write ended, ensure
    # holds; [7,10] step
    assert s.idle_by_span == pytest.approx({"step": 0.004, "ensure": 0.002})
    assert s.longest_gaps[0] == pytest.approx((0.003, "step"))


def test_window_defaults_to_device_span():
    tr = synthetic()
    tr.spans = [x for x in tr.spans if x[2] != "bench.window"]
    s = T.reduce(tr)
    assert s.window_s == pytest.approx(0.020)        # 1 ms to 21 ms


def test_program_and_op_names():
    assert T.program_name("jit_dense_block(5016042454466438535)") == \
        "dense_block"
    assert T.program_name("jit__lambda(1)") == "_lambda"
    assert T.op_name("%fusion.4 = f32[16]{0} fusion(f32[8] %p)") == "fusion.4"


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three calls of one jitted step, each
    followed by a 2 ms host sleep in its own span."""
    tr = T.load(str(FIXTURE))
    assert tr.platform == "tpu"
    assert set(tr.ops) == {0}
    assert {p for *_, p in tr.programs[0]} == {"small_step"}
    assert sum(1 for *_, n in tr.spans if n == "bench.step") == 3
    s = T.reduce(tr)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.009684969)
    assert 0 < s.busy_s < 1e-5
    assert s.idle_share > 0.99
    # the sleeps are the longest idle stretches, and labelled as such
    assert s.idle_by_span["host_wait"] > 0.006
    assert s.longest_gaps[0][1] == "host_wait"
    assert s.program_calls["small_step"] >= 2
