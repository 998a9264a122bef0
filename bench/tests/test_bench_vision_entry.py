"""The vision entry reads what the harness read before it was split into
entries: for one seed, the frames, the served weights, the open cell's
schedule and the requests that the window driver sends in both cells are
byte for byte those of the harness before the split (hashes recorded
there, on the CPU)."""

import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from harness.drive import run_window
from harness.entries.vision import (FrameLoad, arch_from, load_mix,
                                    make_frames, open_schedule,
                                    served_params)
from harness.model import load_config, load_reference

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**32 + 2**31 + 12345
BEFORE = {
    "frames": "597f1ba50e566aecfbc06e05c9ed027c"
              "97ebab2058ddfdb615264ab4c08d63e8",
    "params": "d59b9d65271295fb61b0cc4fb77c3265"
              "e9985cb48085647268c0157ca1b0d3d2",
    "frames.rate schedule": "4960d442d8324c3ca5f0f71c2f8636b7"
                            "37a75ca8026a07e8bdce26d771035869",
    "frames.sat sent": "1f9db50e0576e805fc4f7e045010254c"
                       "c23097d97d33bcfe948c573b4d5742fa",
    "frames.rate sent": "1c7e27e27defa25c5e11e790a899c4c6"
                        "68d519ef94e525c8a750077af7a6028e",
}


def sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


class FakeSched:
    """Answers one due request per step, 10 ms of its clock a step."""

    def __init__(self):
        self.t, self.q, self.finished = 0.0, [], []

    def now(self):
        return self.t

    def submit(self, r):
        self.q.append(r)

    def pending(self):
        return bool(self.q)

    def step(self):
        self.t = round(self.t + 0.01, 6)
        due = [r for r in self.q if r.arrival <= self.t]
        if not due:
            return False
        r = due[0]
        self.q.remove(r)
        r.t_done = self.t
        self.finished.append(r)
        return True


@pytest.fixture(scope="module")
def m3vit():
    cfg = load_config(BENCH / "configs" / "m3vit.json")
    return cfg, make_frames(cfg, 64, SEED)


def test_frames_and_weights_are_unchanged(m3vit):
    cfg, frames = m3vit
    assert frames.shape == (64, 128, 256, 3)
    assert sha(frames.tobytes()) == BEFORE["frames"]
    ref = load_reference(BENCH, cfg)
    params = served_params(arch_from(cfg), ref.param_spec(cfg), SEED)
    h = hashlib.sha256()
    for a in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == BEFORE["params"]


def test_open_schedule_is_unchanged():
    mix = load_mix(BENCH / "traffic" / "frames.rate.json")
    sched = open_schedule(mix, 51.0, SEED)
    assert len(sched) == 652
    assert sha(json.dumps(sched).encode()) == BEFORE["frames.rate schedule"]


@pytest.mark.parametrize("name, sent", [("frames.sat", 207),
                                        ("frames.rate", 26)])
def test_driver_sends_the_same_requests(m3vit, name, sent):
    """Arrival, frame and task of every request the window driver sends,
    the closed loop's frame order among them."""
    _, frames = m3vit
    mix = load_mix(BENCH / "traffic" / f"{name}.json")
    win = run_window(FakeSched(), FrameLoad(mix, SEED, frames), 2.0)
    got = [(r.arrival, f, r.task_id) for r, f in zip(win.requests,
                                                     win.items)]
    assert len(got) == sent
    assert sha(json.dumps(got).encode()) == BEFORE[f"{name} sent"]
