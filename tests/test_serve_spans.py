"""The serving path's own profiler spans (``repro.*`` TraceAnnotations).

A small M³ViT is served through the scheduler at a residency that forces
paging, once untraced and once under ``jax.profiler.trace``; the trace
must hold every span at its site, nested where the code nests it, in the
numbers the counters give, and the answers must not move."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.configs import m3vit as MV
from repro.launch.serve import vision_scheduler
from repro.models import vit as V
from repro.serve.scheduler import Request

SPANS = {
    "repro.sched.step", "repro.sched.admit", "repro.sched.lookahead",
    "repro.vision.quantum", "repro.vision.prefetch", "repro.vision.stack",
    "repro.vision.embed", "repro.vision.dense", "repro.vision.moe_pre",
    "repro.vision.final", "repro.vision.head", "repro.vision.readback",
    "repro.moe.call", "repro.moe.route", "repro.moe.readback",
    "repro.moe.plan", "repro.moe.launch", "repro.moe.finish",
    "repro.paging.ensure", "repro.paging.page_in",
    "repro.paging.device_put", "repro.paging.slot_write",
}
SLOTS = 4                     # two per task bucket


@pytest.fixture(scope="module")
def model():
    cfg = configs.get("m3vit", smoke=True)
    params = V.init_params(jax.random.PRNGKey(0), cfg)
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (SLOTS, MV.IMAGE_H, MV.IMAGE_W, 3)),
        np.float32)
    return cfg, params, imgs


def _serve(sched, imgs):
    """One full batch per task: two bucket forwards."""
    reqs = [Request(rid=i, task_id=i % len(MV.TASKS), prompt=imgs[i])
            for i in range(len(imgs))]
    sched.run(reqs)
    return reqs


def _host_events(directory):
    """``[(start_ns, end_ns, name, stats)]`` of the serving thread's
    ``repro.*`` spans, and every host event name."""
    path = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)[0]
    best, names = [], set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            names.update(e.name for e in events)
            spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                      dict(e.stats))
                     for e in events if e.name.startswith("repro.")]
            if len(spans) > len(best):
                best = spans
    return best, names


def _inside(span, outers):
    return any(o[0] <= span[0] and span[1] <= o[1] for o in outers)


@pytest.mark.parametrize("async_paging", [False, True])
def test_spans_cover_the_serving_path(model, tmp_path, async_paging):
    cfg, params, imgs = model
    sched = vision_scheduler(cfg, params, batch=SLOTS, resident_fraction=0.5,
                             async_paging=async_paging)
    untraced = _serve(sched, imgs)             # also compiles every shape
    before = sched.backend.cache_stats()
    with jax.profiler.trace(str(tmp_path)):
        traced = _serve(sched, imgs)
    after = sched.backend.cache_stats()

    # spans never change what is served
    for a, b in zip(untraced, traced):
        assert np.array_equal(np.asarray(a.result), np.asarray(b.result))

    spans, names = _host_events(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)
    # an asynchronous page-in lands through a batched write, with no
    # device_put of its own
    want = SPANS - ({"repro.paging.device_put"} if async_paging else set())
    assert want <= set(by), want - set(by)
    assert set(by) <= SPANS

    moe_layers = len(sched.backend.server.paged)
    assert moe_layers >= 2
    # two bucket forwards: every MoE layer ran once in each
    assert len(by["repro.vision.quantum"]) == 2
    assert len(by["repro.moe.call"]) == 2 * moe_layers
    assert after["forwards"] - before["forwards"] == 2 * moe_layers
    assert len(by["repro.moe.launch"]) == after["waves"] - before["waves"]
    assert {s[3]["layer"] for s in by["repro.moe.call"]} == \
        set(sched.backend.server.paged)
    for s in by["repro.moe.call"]:
        assert _inside(s, by["repro.vision.quantum"])
    # paging happened, and each page-in is one committed copy, made for a
    # wave's ensure or for the bucket's prefetch
    page_ins = after["page_ins"] - before["page_ins"]
    assert page_ins > 0
    assert len(by["repro.paging.page_in"]) == page_ins
    outers = by["repro.paging.ensure"] + by["repro.vision.prefetch"]
    for s in by["repro.paging.page_in"]:
        assert _inside(s, outers)

    # the request names the bucket forward that served it
    quanta = {(s[3]["task"], s[3]["step"]): s[3]["batch"]
              for s in by["repro.vision.quantum"]}
    for r in traced:
        assert quanta[(r.task_id, r.step)] == SLOTS // len(MV.TASKS)

    # the jitted programs that were lambdas carry names in the trace
    for prog in ("expert_slot_write", "final_norm", "task_head"):
        assert f"PjitFunction({prog})" in names, prog
    assert not any(n.startswith("PjitFunction(<lambda>") for n in names)


def test_counters_reset_with_the_stats(model):
    cfg, params, imgs = model
    sched = vision_scheduler(cfg, params, batch=SLOTS, resident_fraction=0.5)
    _serve(sched, imgs)
    s = sched.backend.cache_stats()
    moe_layers = len(sched.backend.server.paged)
    assert s["forwards"] == 2 * moe_layers
    assert s["waves"] >= s["forwards"]
    # every miss is one committed copy; so is every prefetched expert
    assert s["page_ins"] >= s["misses"] > 0
    assert s["bytes_paged"] == s["page_ins"] * next(
        iter(sched.backend.server.paged.values())).cache._expert_bytes
    sched.backend.reset_stats()
    s = sched.backend.cache_stats()
    assert s["forwards"] == s["waves"] == s["page_ins"] == 0
