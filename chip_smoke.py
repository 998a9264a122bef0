"""Smoke test: M³ViT serving on a TPU through the repo's own entry points.

    python chip_smoke.py              # one chip: device, default, kernels
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Everything runs in this one process (a chip belongs to one process); the
script starts no child and sets no platform.  Weights are random, made from
``--seed``.  Phases:

  device   reads ``jax.devices()`` first and exits non-zero unless the
           platform is ``tpu``.
  default  M³ViT at its published widths (``configs.get("m3vit")``) served
           by ``VisionBackend`` + ``Scheduler`` (``launch.serve``'s vision
           path, default ``resident_fraction`` so experts page) under the
           config's default policy.  Every output is finite and within
           ``TOL`` of the float32 reference.
  kernels  the same requests under the ``pallas`` preset (``moe_gemm``,
           ``flash_attention``, ``unified_linear`` and the LUT activation
           kernel on the paged path), then ``models.vit.forward`` under
           ``pallas_fused`` (the ``moe_ffn`` megakernel).  Every kernel op
           a preset requests is a compiled hit with no fallback, and the
           outputs are within ``TOL`` of the default phase and of
           the reference.
  mesh     (``--chips 4`` only) the ``--mesh 1x4`` serving path
           (``ShardingRules`` + expert-parallel ``PagedMoE``) against a
           one-device run of the same requests, within ``TOL``; every
           expert slot store spans the four devices.

Each comparison of two servers is made twice: on whole predictions within
``TOL``, and stage by stage (``stage_check``) within ``STAGE_TOL``, where
each stage of one server runs on the other's input to that stage.  The
default phase also ties an f32 server to the reference within ``TIE_TOL``,
and the kernel phase checks the ``moe_ffn`` megakernel against the default
paged MoE layer on each layer's input.

Compile seconds (time JAX spent compiling or loading programs from the
persistent cache) and per-request latency are printed for each pass.  The
last stdout line is ``{"ok": true, "device": {...}}``; any failed phase
raises and the script exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# TOL bounds the relative L2 error ||y - ref|| / ||ref|| of each request's
# dense prediction (semseg logits or depth map) between any bf16 serving path
# and the float32 reference, and between two bf16 paths.  It is loose on
# purpose: end to end, six top-4 routers amplify any difference.  A
# near-tied gate logit flips a token's expert set (about 1% of token-layers
# between bf16 and f32), and the flipped token's new value spreads through
# attention.  So a 1x4 mesh whose every stage matches one device within
# 2.6e-5 still ends 2.2e-2 away from it.  Worst of 8 requests on a TPU
# v5e, seeds 0 and 1: 3.2e-2 default vs reference, 4.0e-2 `pallas` vs
# reference.  TOL leaves a margin of 1.5x; the stage check holds each
# kernel to STAGE_TOL.
TOL = 6e-2
# STAGE_TOL bounds each stage of the teacher-forced check (``walk_stages``):
# one stage on the other path's input, so no error carries over and a
# routing flip is counted, not measured.  Two paths then differ only by
# bf16 rounding inside that stage: at most 7.2e-3 per stage on a TPU v5e
# (every pair, seeds 0 and 1).  A kernel that drops a bias, reads the wrong
# table, shifts attention values or loses one expert's rows puts its worst
# stage at 0.15 or more there.
STAGE_TOL = 2e-2
# an f32 server against the f32 ``vit.forward`` reference: the same math in
# f32, so only summation order differs (1.9e-7 on a TPU v5e)
TIE_TOL = 1e-5

ROOT = os.path.dirname(os.path.abspath(__file__))

# kernel impl each preset must hit, per op, on the path it is run on
PALLAS_KERNELS = {"attention": "pallas", "linear": "pallas",
                  "moe_grouped_gemm": "pallas", "activation": "pallas"}
FUSED_KERNELS = {"moe_ffn": "pallas_fused"}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_device(chips: int) -> dict:
    """First phase: what JAX sees.  A platform other than TPU, or fewer
    chips than asked for, ends the run before anything is computed."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: {json.dumps(dev)}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{dev['platform']!r} ({dev['kind']})")
    if dev["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"chips, JAX sees {dev['count']}")
    return dev


class CompileClock:
    """Seconds JAX spends compiling (or loading from the persistent cache)
    and the persistent-cache hits, accumulated from ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.cache_hits

    def since(self, mark) -> str:
        return (f"compile_s={self.seconds - mark[0]:.2f} "
                f"cache_hits={self.cache_hits - mark[1]}")


def rel_err(y, ref) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(y - ref) / max(np.linalg.norm(ref), 1e-30))


def check_close(name: str, got, want, tol: float) -> float:
    """Every output finite and within ``tol`` relative L2 of ``want``;
    returns the worst error."""
    errs = []
    for i, (y, ref) in enumerate(zip(got, want)):
        if not np.isfinite(y).all():
            raise RuntimeError(f"{name}: request {i} has non-finite values")
        errs.append(rel_err(y, ref))
    worst = max(errs)
    log(f"{name}: rel_l2 max {worst:.3e} mean {np.mean(errs):.3e} "
        f"(tol {tol:.0e}) over {len(errs)} requests")
    if worst > tol:
        raise RuntimeError(f"{name}: rel_l2 {worst:.3e} > {tol:.0e} "
                           f"(per request: {[f'{e:.2e}' for e in errs]})")
    return worst


def make_inputs(seed: int, n: int):
    """``n`` seeded images and their tasks (alternating semseg/depth)."""
    from repro.launch.serve import vision_requests

    reqs = vision_requests(jax.random.PRNGKey(seed + 1), n)
    return (np.stack([np.asarray(r.prompt) for r in reqs]),
            [r.task_id for r in reqs])


def make_params(cfg, seed: int):
    """Random M³ViT parameters from ``seed``.  ``init_params`` leaves every
    bias at 0 and every norm scale at 1; such constant leaves get seeded
    noise (0.1 standard deviation) as well, so that a path that drops a
    bias or a norm parameter gives another answer."""
    from repro.models import vit as V

    params = V.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    leaves = [
        (a + 0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) and float(jnp.ptp(a)) == 0
        else a for k, a in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def serve(name, cfg, params, images, tasks, *, batch: int, clock,
          rules=None):
    """Serve the requests twice through one scheduler: a cold pass that
    compiles, then a warm pass, and log both.  All requests arrive at once,
    so a latency includes the wait for a free slot.  Returns (warm outputs
    in request order, the scheduler)."""
    from repro.launch.serve import vision_scheduler
    from repro.serve import Request

    sched = vision_scheduler(cfg, params, batch=batch, rules=rules)
    for pass_name in ("cold", "warm"):
        mark = clock.mark()
        now = sched.now()
        reqs = [Request(rid=i, task_id=t, prompt=images[i], arrival=now)
                for i, t in enumerate(tasks)]
        sched.run(reqs)
        lat = np.asarray([r.latency for r in reqs]) * 1e3
        log(f"{name} {pass_name} pass: {clock.since(mark)} "
            f"latency_ms p50={np.median(lat):.2f} max={lat.max():.2f}")
    return [np.asarray(r.result) for r in reqs], sched


def forward(cfg, params, images, tasks):
    """``models.vit.forward`` per task, jitted; outputs in request order."""
    from repro.configs import m3vit as MV
    from repro.models import vit as V

    out = [None] * len(tasks)
    for t in sorted(set(tasks)):
        idx = [i for i, tt in enumerate(tasks) if tt == t]
        fwd = jax.jit(lambda p, x, _t=MV.TASKS[t]: V.forward(p, x, cfg,
                                                             task=_t)[0])
        y = np.asarray(fwd(params, jnp.asarray(images[idx])))
        for j, i in enumerate(idx):
            out[i] = y[j]
    return out


def f32_model(cfg, params):
    """``cfg`` and ``params`` in float32 with exact activations (the
    ``xla`` preset); run it under ``highest()``."""
    from repro.ops import policy_named

    return (replace(cfg, dtype="float32", policy=policy_named("xla")),
            jax.tree.map(lambda a: a.astype(jnp.float32)
                         if jnp.issubdtype(a.dtype, jnp.floating) else a,
                         params))


def highest():
    """f32 matmuls on the TPU (its default rounds f32 operands to bf16)."""
    return jax.default_matmul_precision("highest")


def reference(cfg, params, images, tasks):
    """The float32 reference: ``models.vit.forward`` of ``f32_model``."""
    with highest():
        return forward(*f32_model(cfg, params), images, tasks)


class Stages:
    """The stages of one ``M3ViTServer`` as its ``infer`` runs them (the
    same jitted functions, rules and policy scopes), each callable on any
    input.  ``ctx`` wraps every call (the f32 reference's precision)."""

    def __init__(self, server, ctx=contextlib.nullcontext):
        self.srv = server
        self.ctx = ctx
        self.dtype = server.cfg.activation_dtype

    @contextlib.contextmanager
    def _scope(self, policy=False):
        from repro.dist.sharding import use_rules
        from repro.ops import use_policy

        with self.ctx(), use_rules(self.srv.rules), \
                use_policy(self.srv.cfg.policy if policy else None):
            yield

    def embed(self, images):
        with self._scope():
            return self.srv._embed(self.srv.params, images)

    def dense(self, i, x, pos):
        with self._scope():
            return self.srv._dense(self.srv.layer_params[i], x, pos)

    def moe_pre(self, i, x, pos):
        with self._scope():
            return self.srv._moe_pre(self.srv.layer_params[i], x, pos)

    def moe(self, i, h, task_id):
        """The paged MoE layer's output and each token's sorted dispatch:
        its expert set, each expert marked kept or dropped at capacity (a
        flip elsewhere in the group can push a token past an expert's
        capacity)."""
        paged = self.srv.paged[i]
        with self._scope(policy=True):
            y, _ = paged(h, task_id=task_id)
        r = paged.last_routing
        key = 2 * np.asarray(r.expert) + np.asarray(r.valid)
        sets = np.sort(key.reshape(-1, key.shape[-1]), axis=-1)
        return y, sets[:h.shape[0] * h.shape[1]]

    def head(self, x, task_id):
        from repro.configs import m3vit as MV

        with self._scope():
            feats = self.srv._final(self.srv.params, x)
            return self.srv._heads[MV.TASKS[task_id]](self.srv.params, feats)


def rows(a) -> np.ndarray:
    a = np.asarray(a, np.float64)
    return a.reshape(-1, a.shape[-1])


def walk_stages(test: Stages, base: Stages, images, task_id: int,
                fused=None):
    """Teacher-forced comparison of two servers on one batch: ``base`` runs
    its own trajectory, and each stage of ``test`` runs on ``base``'s input
    to that stage (cast to ``test``'s dtype), so no error carries from one
    stage to the next.  A residual stage is compared by what it adds to its
    input; a MoE stage over the tokens that both servers sent to the same
    expert set, the others counted as routing flips.  ``fused(i, h, task)``
    is a third MoE implementation checked against ``base``'s on ``base``'s
    input.  Returns ({stage: rel_l2}, flips, tokens, base's prediction)."""
    def feed(a):
        return jnp.asarray(np.asarray(a)).astype(test.dtype)

    def added(y, x):
        return rows(y) - rows(x)

    imgs = jnp.asarray(images)
    xb = base.embed(imgs)
    errs = {"embed": rel_err(test.embed(imgs), xb)}
    b, s = xb.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    flips = 0
    for i, kind in enumerate(base.srv.kinds):
        xt = feed(xb)
        if kind != "attn_moe":
            y = base.dense(i, xb, pos)
            errs[f"L{i}"] = rel_err(added(test.dense(i, xt, pos), xt),
                                    added(y, xb))
            xb = y
            continue
        xr, h = base.moe_pre(i, xb, pos)
        xr_t, h_t = test.moe_pre(i, xt, pos)
        errs[f"L{i}.attn"] = rel_err(added(xr_t, xt), added(xr, xb))
        errs[f"L{i}.ln"] = rel_err(h_t, h)
        y, sets = base.moe(i, h, task_id)
        y_t, sets_t = test.moe(i, feed(h), task_id)
        same = (sets == sets_t).all(axis=-1)
        flips += int((~same).sum())
        errs[f"L{i}.moe"] = rel_err(rows(y_t)[same], rows(y)[same])
        if fused is not None:
            errs[f"L{i}.moe_ffn"] = rel_err(fused(i, feed(h), task_id), y)
        xb = xr + y
    pred = base.head(xb, task_id)
    errs["head"] = rel_err(test.head(feed(xb), task_id), pred)
    return errs, flips, b * s * len(base.srv.paged), pred


def stage_check(name, test: Stages, base: Stages, images, tasks, *,
                slots: int, fused=None, ref=None) -> float:
    """``walk_stages`` over every request, batched per task as the
    scheduler's buckets batch them.  Every stage within ``STAGE_TOL``; with
    ``ref``, ``base``'s own prediction within ``TIE_TOL`` of it (ties an
    f32 server to the f32 ``vit.forward`` reference).  Returns the worst
    stage error."""
    worst, flips, tokens, tie = {}, 0, 0, 0.0
    for t in sorted(set(tasks)):
        idx = [i for i, tt in enumerate(tasks) if tt == t]
        for c in range(0, len(idx), slots):
            chunk = idx[c:c + slots]
            batch = images[chunk + [chunk[0]] * (slots - len(chunk))]
            errs, f, n, pred = walk_stages(test, base, batch, t, fused)
            for k, v in errs.items():
                worst[k] = max(worst.get(k, 0.0), v)
            flips, tokens = flips + f, tokens + n
            if ref is not None:
                tie = max([tie] + [rel_err(pred[j], ref[i])
                                   for j, i in enumerate(chunk)])
    top = max(worst, key=worst.get)
    log(f"{name} stages (teacher-forced): worst {top} {worst[top]:.3e} "
        f"(tol {STAGE_TOL:.0e}); moe routing flips {flips}/{tokens} "
        f"token-layers; " + " ".join(f"{k}={v:.2e}" for k, v in worst.items()))
    if ref is not None:
        log(f"{name}: f32 server vs f32 vit.forward rel_l2 max {tie:.3e} "
            f"(tol {TIE_TOL:.0e})")
        if tie > TIE_TOL:
            raise RuntimeError(f"{name}: f32 server {tie:.3e} from the f32 "
                               f"reference > {TIE_TOL:.0e}")
    bad = {k: f"{v:.2e}" for k, v in worst.items() if v > STAGE_TOL}
    if bad:
        raise RuntimeError(f"{name}: stages over {STAGE_TOL:.0e}: {bad}")
    return worst[top]


def fused_moe(cfg):
    """``core.moe.apply_moe`` under ``pallas_fused`` (the ``moe_ffn``
    megakernel) on one layer of ``cfg``'s parameters, jitted."""
    from repro import ops
    from repro.core import moe as M
    from repro.models import transformer as T

    mcfg = T.moe_config(cfg)
    policy = ops.policy_named("pallas_fused")

    @partial(jax.jit, static_argnums=2)
    def run(moe_params, h, task_id):
        with ops.use_policy(policy):
            return M.apply_moe(moe_params, mcfg, h, task_id=task_id)[0]
    return run


def check_kernels(name: str, report: dict, want: dict, mode: str) -> None:
    """Every op in ``want`` ran its kernel impl as a hit in ``mode``, and no
    op anywhere fell back or ran a kernel in another mode."""
    log(f"{name} dispatch report: {json.dumps(report, sort_keys=True)}")
    problems = []
    for op, entry in report.items():
        for fb in entry["fallbacks"]:
            problems.append(f"{op}: {fb['requested']} fell back to "
                            f"{fb['used']} ({'; '.join(fb['reasons'])})")
        for impl, modes in entry["modes"].items():
            if set(modes) != {mode}:
                problems.append(f"{op}.{impl} ran in {modes}, not {mode}")
    for op, impl in want.items():
        if not report.get(op, {}).get("hits", {}).get(impl):
            problems.append(f"{op}: no hit for kernel impl {impl!r}")
    if problems:
        raise RuntimeError(f"{name}: " + " | ".join(problems))


def phase_default(cfg, params, images, tasks, *, batch, clock):
    from repro.serve.vision import M3ViTServer

    mark = clock.mark()
    ref = reference(cfg, params, images, tasks)
    log(f"reference (f32 vit.forward): {clock.since(mark)}")
    got, sched = serve("default", cfg, params, images, tasks, batch=batch,
                       clock=clock)
    cache = sched.metrics().get("expert_cache", {})
    log(f"default: expert_hit_rate={cache.get('hit_rate', 1.0):.3f} "
        f"resident_fraction={cache.get('resident_fraction', 1.0):.2f}")
    check_close("default vs f32 reference", got, ref, TOL)
    mark = clock.mark()
    stage_check("default vs f32", Stages(sched.backend.server),
                Stages(M3ViTServer(*f32_model(cfg, params)), ctx=highest),
                images, tasks, slots=sched.slots_per_bucket, ref=ref)
    log(f"default vs f32 stages: {clock.since(mark)}")
    return ref, got, sched


def phase_kernels(cfg, params, images, tasks, ref, base, base_sched, *,
                  batch, clock, mode="compiled"):
    from repro import ops

    ops.reset_dispatch_report()
    got, sched = serve("pallas",
                       replace(cfg, policy=ops.policy_named("pallas")),
                       params, images, tasks, batch=batch, clock=clock)
    check_kernels("pallas", ops.dispatch_report(), PALLAS_KERNELS, mode)
    check_close("pallas vs default", got, base, TOL)
    check_close("pallas vs f32 reference", got, ref, TOL)

    ops.reset_dispatch_report()
    mark = clock.mark()
    t0 = time.perf_counter()
    got = forward(replace(cfg, policy=ops.policy_named("pallas_fused")),
                  params, images, tasks)
    log(f"pallas_fused (vit.forward): {clock.since(mark)} "
        f"wall_s={time.perf_counter() - t0:.2f}")
    check_kernels("pallas_fused", ops.dispatch_report(), FUSED_KERNELS, mode)
    check_close("pallas_fused vs default", got, base, TOL)
    check_close("pallas_fused vs f32 reference", got, ref, TOL)

    mark = clock.mark()
    default = Stages(base_sched.backend.server)
    run = fused_moe(cfg)
    stage_check("pallas vs default", Stages(sched.backend.server), default,
                images, tasks, slots=sched.slots_per_bucket,
                fused=lambda i, h, t: run(
                    default.srv.layer_params[i]["moe"], h, t))
    log(f"pallas vs default stages: {clock.since(mark)}")


def slot_store_devices(sched) -> set[int]:
    """Device count of every expert slot store of a served scheduler."""
    counts = set()
    for layer in sched.backend.server.paged.values():
        for arr in layer.cache.slots.values():
            counts.add(len(arr.sharding.device_set))
    return counts


def phase_mesh(cfg, params, images, tasks, *, batch, clock, spec="1x4"):
    from repro.launch.serve import mesh_rules

    base, one = serve("one device", cfg, params, images, tasks, batch=batch,
                      clock=clock)
    rules = mesh_rules(spec)
    n_dev = rules.mesh.devices.size
    got, sched = serve(f"mesh {spec}", cfg, params, images, tasks,
                       batch=batch, clock=clock, rules=rules)
    spans = slot_store_devices(sched)
    log(f"mesh {spec}: expert slot stores span {sorted(spans)} devices")
    if spans != {n_dev}:
        raise RuntimeError(f"mesh {spec}: expert slot stores span "
                           f"{sorted(spans)} devices, not {n_dev}")
    check_close(f"mesh {spec} vs one device", got, base, TOL)
    stage_check(f"mesh {spec} vs one device", Stages(sched.backend.server),
                Stages(one.backend.server), images, tasks,
                slots=sched.slots_per_bucket)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="scheduler slots in all (split over the 2 tasks)")
    args = ap.parse_args(argv)

    dev = check_device(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = configs.get("m3vit")
    params = make_params(cfg, args.seed)
    images, tasks = make_inputs(args.seed, args.requests)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(cfg, params, images, tasks, batch=args.batch, clock=clock)
    else:
        ref, base, sched = phase_default(cfg, params, images, tasks,
                                         batch=args.batch, clock=clock)
        phase_kernels(cfg, params, images, tasks, ref, base, sched,
                      batch=args.batch, clock=clock)
    log(f"total: compile_s={clock.seconds:.2f} "
        f"cache_hits={clock.cache_hits} wall_s={time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
