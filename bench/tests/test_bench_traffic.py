"""The traffic generator is a function of the mix and the seed."""

from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from harness.traffic import FrameOrder, load_mix, open_schedule

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("name", ["frames.rate", "frames.burst"])
def test_open_schedule_is_deterministic(name):
    mix = load_mix(TRAFFIC / f"{name}.json")
    a = open_schedule(mix, 30.0, 2**33 + 7)
    assert a == open_schedule(mix, 30.0, 2**33 + 7)
    assert a != open_schedule(mix, 30.0, 2**33 + 8)


@pytest.mark.parametrize("name", ["frames.rate", "frames.burst"])
def test_every_seed_offers_the_same_work(name):
    mix = load_mix(TRAFFIC / f"{name}.json")
    runs = [open_schedule(mix, 30.0, s) for s in (1, 2**31 + 5, 2**40)]
    frames = round(mix.rate_frames_per_s * 30.0 / mix.burst) * mix.burst
    for sched in runs:
        assert len(sched) == frames * len(mix.tasks)
        due = [t for t, _, _ in sched]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
        assert Counter(t for _, _, t in sched) == \
            {t: frames for t in mix.tasks}
        # every seed: the same arrival times, other frames
        assert due == [t for t, _, _ in runs[0]]
    assert [f for _, f, _ in runs[0]] != [f for _, f, _ in runs[1]]
    # the gaps (with the last one, to the window's end) are the
    # exponential distribution's quantiles, scaled to the window
    due = sorted(set(t for t, _, _ in runs[0]))
    gaps = np.sort(np.diff(due + [30.0]))
    n = len(gaps)
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    np.testing.assert_allclose(gaps, q * 30.0 / q.sum(), rtol=1e-9)


def test_burst_frames_share_a_due_time():
    mix = load_mix(TRAFFIC / "frames.burst.json")
    sched = open_schedule(mix, 10.0, 3)
    per_time = Counter(t for t, _, _ in sched)
    assert set(per_time.values()) == {mix.burst * len(mix.tasks)}


def test_frame_order_visits_the_pool_before_repeating():
    order = FrameOrder(8, np.random.default_rng(4))
    first = [order.next() for _ in range(8)]
    assert sorted(first) == list(range(8))
    again = FrameOrder(8, np.random.default_rng(4))
    assert [again.next() for _ in range(8)] == first


def test_closed_mix_has_clients():
    mix = load_mix(TRAFFIC / "frames.sat.json")
    assert mix.kind == "closed" and mix.clients == 8 and mix.slots == 8


def test_a_mix_without_its_parameters_is_refused(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"kind": "open", "slots": 8, "tasks": [0, 1], '
                   '"frame_pool": 4}')
    with pytest.raises(ValueError, match="needs a rate"):
        load_mix(bad)
    bad.write_text('{"kind": "spiky", "slots": 8, "tasks": [0], '
                   '"frame_pool": 4}')
    with pytest.raises(ValueError, match="kind"):
        load_mix(bad)
