"""Serving throughput: continuous-batching scheduler vs static batching.

Drives a synthetic mixed-task open-loop workload (Poisson arrivals, two
gating tasks, variable output lengths) through:

  * the static-batch ``ServingEngine`` (one task per batch, every batch
    runs until its longest request finishes — the tail waste), and
  * the task-bucketed continuous-batching ``Scheduler`` at equal total
    batch capacity (slots admit new requests the moment one finishes),

and reports sustained tok/s, p50/p99 request latency, and the speedup.
Also serves the paper's own M³ViT (semseg+depth) through the same
scheduler with paged expert weights at a bounded residency fraction,
reporting items/s and the expert-cache hit rate — once with uniform
random gating (no task sparsity: the honest worst case) and once with
task-sparse gating (each task's routing concentrated on a disjoint expert
subset, the paper's §IV-F regime, where usage-driven prefetch pays off).

Emits CSV rows through the harness and writes a JSON artifact for the CI
benchmark trajectory (``BENCH_JSON`` env var overrides the path).

``run_mesh_sweep`` (registered as the ``serve_dist`` benchmark) extends
this with the DISTRIBUTED serving trajectory: the paged M³ViT server at
mesh sizes 1/2/4/8 (forced host CPU shards, one subprocess per mesh so
each gets its own jax device count), at a FIXED per-device expert-weight
byte budget.  Expert parallelism over the ``model`` axis means each mesh
size holds ``shards ×`` more experts resident in the same per-device
budget, so both the aggregate patch tok/s (fewer sequential expert waves,
less demand paging) and the expert-cache hit rate must rise with the mesh
— the acceptance flags in ``bench/serve_dist.json`` record exactly that
(mesh 4 ≥ 2× mesh-1 tok/s, strictly higher hit rate).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.serve import LMBackend, Request, Scheduler, ServeConfig, ServingEngine

JSON_PATH = os.environ.get(
    "BENCH_JSON",
    os.path.join(os.path.dirname(__file__), "out", "serve_throughput.json"))

DIST_JSON_PATH = os.environ.get(
    "BENCH_DIST_JSON",
    os.path.join(os.path.dirname(__file__), "out", "serve_dist.json"))


def _lm_workload(n, num_tasks, prompt_len, vocab, rng,
                 mean_interarrival=0.002):
    """Open-loop mixed-task workload with a heavy-tailed output-length mix
    (75% short chats, 25% long generations) — the length variance that
    makes static batches wait on their slowest member."""
    prompts = rng.integers(0, vocab, (n, prompt_len), dtype=np.int32)
    short = rng.integers(4, 11, n)
    long = rng.integers(40, 57, n)
    lengths = np.where(rng.random(n) < 0.75, short, long)
    tasks = np.arange(n) % num_tasks
    rng.shuffle(tasks)
    arrivals = np.cumsum(rng.exponential(mean_interarrival, n))
    return [Request(rid=i, task_id=int(tasks[i]), prompt=prompts[i],
                    max_new_tokens=int(lengths[i]), arrival=float(arrivals[i]))
            for i in range(n)]


def _run_static(engine, requests, capacity):
    """Static baseline: group by task (arrival order), batches of
    ``capacity``; each batch must decode until its longest request is done
    — requests that finished early occupy dead slots (the tail waste).
    Batches are padded up to ``capacity`` so every step runs at the same
    batch width the scheduler gets (strictly favorable to the baseline:
    arrival times are ignored entirely)."""
    useful = 0
    t0 = time.perf_counter()
    for task in sorted({r.task_id for r in requests}):
        batch = [r for r in requests if r.task_id == task]
        for i in range(0, len(batch), capacity):
            chunk = batch[i:i + capacity]
            prompts = np.stack([r.prompt for r in chunk])
            if len(chunk) < capacity:   # keep the compiled batch shape
                prompts = np.concatenate(
                    [prompts, np.repeat(prompts[:1],
                                        capacity - len(chunk), axis=0)])
            engine.generate(jnp.asarray(prompts),
                            max(r.max_new_tokens for r in chunk),
                            task_id=task)
            useful += sum(r.max_new_tokens for r in chunk)
    dt = time.perf_counter() - t0
    return useful / dt, dt


def _run_scheduler(backend, requests, capacity, num_tasks, quantum=6):
    sched = Scheduler(backend, total_slots=capacity, quantum=quantum,
                      num_tasks=num_tasks)
    sched.run([replace_req(r) for r in requests])
    return sched.metrics()


def replace_req(r: Request) -> Request:
    return Request(rid=r.rid, task_id=r.task_id, prompt=r.prompt,
                   max_new_tokens=r.max_new_tokens, arrival=r.arrival)


def _task_sparse_gates(params, num_tasks, num_experts, penalty=-25.0):
    """Concentrate each task's routing on a disjoint expert subset via a
    per-task gate logit bias (``gate_bias``, the routing-control hook in
    ``core/moe.py``): non-preferred experts get a large negative logit
    offset, so top-k always lands in the task's subset — a synthetic
    stand-in for trained task-level sparsity (§IV-F)."""
    per = max(1, num_experts // num_tasks)
    bias = np.full((num_tasks, num_experts), penalty, np.float32)
    for t in range(num_tasks):
        for j in range(per):
            bias[t, (t * per + j) % num_experts] = 0.0

    def walk(d):
        if isinstance(d, dict):
            if "gate" in d:
                g = np.asarray(d["gate"])
                if g.ndim == 4:   # stacked scanned layers: lead period axis
                    d["gate_bias"] = jnp.asarray(np.broadcast_to(
                        bias, (g.shape[0],) + bias.shape).copy())
                else:
                    d["gate_bias"] = jnp.asarray(bias)
            for v in list(d.values()):
                walk(v)
        elif isinstance(d, (list, tuple)):
            for v in d:
                walk(v)
    walk(params)
    return params


def _vision_section(quick, rows, out, rng, resident_fraction=0.5):
    from repro.configs import m3vit as MV
    from repro.models import vit as V
    from repro.serve.scheduler import Scheduler
    from repro.serve.vision import VisionBackend

    cfg = configs.get("m3vit", smoke=True)
    n = 8 if quick else 24
    batch = 2
    imgs = rng.standard_normal((4, MV.IMAGE_H, MV.IMAGE_W, 3)).astype(
        np.float32)

    def _pass(backend, count):
        sched = Scheduler(backend, total_slots=batch * len(MV.TASKS),
                          quantum=1, num_tasks=len(MV.TASKS))
        sched.run([Request(rid=i, task_id=i % len(MV.TASKS),
                           prompt=imgs[i % imgs.shape[0]])
                   for i in range(count)])
        return sched.metrics()

    def _measure(label, backend):
        _pass(backend, n)   # warmup: compiles + usage-EMA/cache warm-in
        # reset demand counters so the measured pass reports steady state
        for paged in backend.server.paged.values():
            c = paged.cache
            c.hits = c.misses = c.evictions = c.bytes_paged = 0
        m = _pass(backend, n)  # measured: same backend, warm caches & stats
        cache = m["expert_cache"]
        cache["resident_experts"] = next(
            iter(backend.server.paged.values())).cache.max_resident
        out[f"vision_{label}"] = {
            "items_per_s": m["items_per_s"],
            "latency_p50_s": m["latency_p50_s"],
            "latency_p99_s": m["latency_p99_s"],
            "expert_cache": cache,
        }
        rows.append((
            f"serve_vision_{label}",
            1e6 / max(m["items_per_s"], 1e-9),
            f"hit_rate={cache['hit_rate']:.3f};"
            f"resident_fraction={cache['resident_fraction']:.2f}"))
        return backend

    backend = None
    for label, sparse in (("uniform", False), ("task_sparse", True)):
        params = V.init_params(jax.random.PRNGKey(0), cfg)
        if sparse:
            params = _task_sparse_gates(params, len(MV.TASKS),
                                        cfg.moe.num_experts)
        backend = _measure(label, VisionBackend(
            cfg, params, resident_fraction=resident_fraction))

    # int8 experts at the SAME device byte budget as the fp task-sparse
    # pass: packed weights fit more resident experts, so the demand hit
    # rate rises (the quantization × paging multiplier)
    from repro.ops import policy_named
    from repro.quant import quantize_tree

    fp_cache = next(iter(backend.server.paged.values())).cache
    budget = fp_cache.max_resident * fp_cache._expert_bytes
    params = V.init_params(jax.random.PRNGKey(0), cfg)
    params = _task_sparse_gates(params, len(MV.TASKS), cfg.moe.num_experts)
    qparams = quantize_tree(params, bits=8)
    qcfg = replace(cfg, policy=policy_named("xla_int8"))
    _measure("task_sparse_int8", VisionBackend(
        qcfg, qparams, expert_budget_bytes=budget))


def _async_section(quick, rows, out):
    """Async expert streaming vs synchronous paging, at the honest worst
    case: 25% residency, UNIFORM gating (no task sparsity to prefetch
    from), serving-scale expert pool (64 experts, d_ff=1024 — the regime
    where copy volume is real).  Same model, same inputs, same slots; the
    only difference is the TransferEngine: double-buffered waves + router
    lookahead submit wave k+1's copies while wave k computes.

    The acceptance contract (enforced here AND by the CI artifact flags):
    ``overlap_ratio`` must be reported, and async items/s must reach
    ≥ 1.15× the synchronous path."""
    from repro.core.moe import expert_param_names
    from repro.models import transformer as T
    from repro.models import vit as V
    from repro.serve.expert_cache import _per_expert_bytes
    from repro.serve.vision import M3ViTServer

    cfg = configs.get("m3vit", smoke=True)
    cfg = replace(cfg, moe=replace(cfg.moe, num_experts=64, d_ff=1024))
    params = V.init_params(jax.random.PRNGKey(0), cfg)
    per_expert = _per_expert_bytes({
        name: np.asarray(params["layers"]["b1"]["moe"][name][0])
        for name in expert_param_names(T.moe_config(cfg))})
    budget = 16 * per_expert          # 16 of 64 slots = 25% residency
    toks_per_img = 128
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (2, toks_per_img, cfg.d_model)), np.float32)
    iters = 3 if quick else 6

    def _measure(server):
        for t in (0, 1):              # warm: compiles + EMA/residency warm-in
            server.infer(imgs, t)
        server.reset_stats()
        rounds = []
        for _ in range(iters):
            t0 = time.perf_counter()
            for t in (0, 1):
                server.infer(imgs, t)
            rounds.append(time.perf_counter() - t0)
        best = sorted(rounds)[1] if len(rounds) > 1 else rounds[0]
        per_round = 2 * imgs.shape[0]
        return per_round / best, server.cache_stats()

    sync_ips, sync_stats = _measure(M3ViTServer(
        cfg, params, expert_budget_bytes=budget))
    async_ips, async_stats = _measure(M3ViTServer(
        cfg, params, expert_budget_bytes=budget, async_paging=True))

    if "overlap_ratio" not in async_stats:
        raise RuntimeError(
            "async paging did not report overlap_ratio — the stall "
            "accounting contract is broken")
    speedup = async_ips / sync_ips if sync_ips else float("inf")
    out["vision_async"] = {
        "residency": 0.25, "gating": "uniform",
        "num_experts": cfg.moe.num_experts, "d_ff": cfg.moe.d_ff,
        "sync_items_per_s": sync_ips,
        "async_items_per_s": async_ips,
        "speedup": speedup,
        "stall_s": async_stats["stall_s"],
        "hidden_s": async_stats["hidden_s"],
        "overlap_ratio": async_stats["overlap_ratio"],
        "async_prefetches": async_stats["async_prefetches"],
        "inflight_joins": async_stats["inflight_joins"],
        "async_cancelled": async_stats["async_cancelled"],
        "sync_hit_rate": sync_stats["hit_rate"],
        "async_hit_rate": async_stats["hit_rate"],
        "waves_per_forward": async_stats["waves"] / async_stats["forwards"],
        "page_ins": async_stats["page_ins"],
        "accept_overlap_reported": True,
        "accept_async_speedup_1p15x": speedup >= 1.15,
    }
    rows.append(("serve_vision_async_sync", 1e6 / max(sync_ips, 1e-9),
                 f"items_per_s={sync_ips:.2f}"))
    rows.append(("serve_vision_async", 1e6 / max(async_ips, 1e-9),
                 f"items_per_s={async_ips:.2f};speedup={speedup:.2f}x;"
                 f"overlap={async_stats['overlap_ratio']:.2f}"))
    print(f"[serve_throughput] async paging {speedup:.2f}x sync at 25% "
          f"residency (overlap_ratio "
          f"{async_stats['overlap_ratio']:.2f}, stall "
          f"{async_stats['stall_s']*1e3:.0f}ms)")
    if not out["vision_async"]["accept_async_speedup_1p15x"]:
        raise RuntimeError(
            f"async paging acceptance failed: {speedup:.3f}x < 1.15x "
            f"({out['vision_async']})")


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    rows: list[tuple] = []
    out: dict = {"quick": bool(quick)}

    # ---- LM mixed-task decode: static vs continuous at equal capacity
    cfg = configs.get("kimi_k2_1t_a32b", smoke=True)
    cfg = replace(cfg, moe=replace(cfg.moe, num_tasks=2))
    num_tasks = 2
    capacity = 8
    n = 32 if quick else 64
    params_key, _ = jax.random.split(jax.random.PRNGKey(0))
    from repro.models import model as M
    params = M.init_params(params_key, cfg)
    scfg = ServeConfig(max_len=80)
    requests = _lm_workload(n, num_tasks, prompt_len=8,
                            vocab=cfg.vocab_size, rng=rng)

    # warmup (jit compiles at the measured shapes): reuse the SAME engine /
    # backend for the measured pass so compiles stay out of the timings
    engine = ServingEngine(cfg, params, scfg)
    backend = LMBackend(cfg, params, scfg)
    warm = [Request(rid=-1 - i, task_id=i % num_tasks,
                    prompt=requests[i].prompt, max_new_tokens=3)
            for i in range(2 * capacity)]
    _run_static(engine, warm, capacity)
    _run_scheduler(backend, warm, capacity, num_tasks)

    static_tps, static_dt = _run_static(engine, requests, capacity)
    m = _run_scheduler(backend, requests, capacity, num_tasks)
    ratio = m["tok_per_s"] / static_tps if static_tps else float("inf")
    out["lm"] = {
        "arch": cfg.name, "requests": n, "capacity": capacity,
        "num_tasks": num_tasks,
        "static_tok_per_s": static_tps,
        "continuous_tok_per_s": m["tok_per_s"],
        "speedup": ratio,
        "latency_p50_s": m["latency_p50_s"],
        "latency_p99_s": m["latency_p99_s"],
        "ttft_p50_s": m["ttft_p50_s"],
        "slot_utilization": m.get("slot_utilization"),
        "expert_usage_task_overlap": m.get("expert_usage_task_overlap"),
    }
    rows.append(("serve_lm_static", 1e6 / max(static_tps, 1e-9),
                 f"tok_per_s={static_tps:.1f}"))
    rows.append(("serve_lm_continuous", 1e6 / max(m["tok_per_s"], 1e-9),
                 f"tok_per_s={m['tok_per_s']:.1f};speedup={ratio:.2f}x"))

    # ---- M³ViT vision serving with paged experts
    _vision_section(quick, rows, out, rng)

    # ---- async expert streaming vs synchronous paging
    _async_section(quick, rows, out)

    os.makedirs(os.path.dirname(JSON_PATH), exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[serve_throughput] wrote {JSON_PATH}; "
          f"lm speedup {ratio:.2f}x; "
          f"vision hit_rate uniform="
          f"{out['vision_uniform']['expert_cache']['hit_rate']:.2f} "
          f"task_sparse="
          f"{out['vision_task_sparse']['expert_cache']['hit_rate']:.2f}")
    return rows


# ------------------------------------------------------ mesh sweep (dist)

_MESH_CHILD = textwrap.dedent("""
    import os, sys
    n = int(sys.argv[1]); iters = int(sys.argv[2])
    use_async = len(sys.argv) > 3 and sys.argv[3] == "async"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, time
    import jax, numpy as np
    from repro import configs
    from repro.dist import make_mesh
    from repro.dist.sharding import ShardingRules
    from repro.models import vit as V
    from repro.serve.vision import M3ViTServer

    from dataclasses import replace
    cfg = configs.get("m3vit", smoke=True)
    # smoke trunk, serving-scale expert pool: 64 experts at smoke width.
    # This is the regime where serving time is dominated by expert-wave
    # dispatch and demand paging rather than raw FLOPs — host-device
    # shards share one physical CPU, so compute-bound work cannot show
    # aggregate scaling; the paging and wave-count overheads that expert
    # parallelism removes can (and on real shards the FFN waves would
    # additionally run concurrently)
    cfg = replace(cfg, moe=replace(cfg.moe, num_experts=64, d_ff=1024))
    params = V.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1, n), ("data", "model")) if n > 1 else None
    # hybrid placement (the M3ViT/UbiMoE co-design split): the tiny dense
    # trunk replicates, ONLY the expert banks partition — every mesh size
    # pays an identical trunk cost and the measured delta is pure expert
    # serving: sequential wave count + demand paging volume
    from repro.core.moe import expert_param_names
    from repro.models import transformer as T
    from repro.serve.expert_cache import _per_expert_bytes
    # per-expert device bytes straight from one MoE layer's stacked leaves
    # (layer b1 is the first attn_moe block; [0] drops the scan axis) — no
    # throwaway fully-resident server needed just to read this number
    per_expert = _per_expert_bytes({
        name: np.asarray(params["layers"]["b1"]["moe"][name][0])
        for name in expert_param_names(T.moe_config(cfg))})
    # fixed PER-DEVICE budget of 16 expert slots (a quarter of the
    # pool).  Mesh 1 drags the 64-expert working set through 16 slots: 4
    # sequential waves + ~48 demand page-ins per MoE layer per batch.
    # Mesh 4 holds all 64 resident (4 shards x 16 slots): one wave, zero
    # steady-state paging.
    server = M3ViTServer(cfg, params,
                         expert_budget_bytes=16 * per_expert,
                         ep_mesh=mesh, async_paging=use_async)
    # pre-patchified inputs (the serving path also accepts embeddings);
    # per-image tokens = the paper's 128 patches
    toks_per_img = 128
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (2, toks_per_img, cfg.d_model)), np.float32)
    for t in (0, 1, 0, 1):          # warm: compiles + cache/EMA warm-in
        server.infer(imgs, t)
    server.reset_stats()            # cache counters + transfer ledger
    # best-of-rounds: the shared-CPU shards make wall time sensitive to
    # system load; the minimum round is the structural cost (standard
    # microbenchmark practice) and is what the acceptance flags compare
    rounds = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for t in (0, 1):
            server.infer(imgs, t)
        rounds.append(time.perf_counter() - t0)
    # second-smallest round: robust to a single lucky/unlucky sample on
    # the shared-CPU shards
    best = sorted(rounds)[1] if len(rounds) > 1 else rounds[0]
    per_round = 2 * imgs.shape[0]
    images = iters * per_round
    cache = server.cache_stats()
    first = next(iter(server.paged.values())).cache
    result = {
        "mesh": n,
        "async": use_async,
        "images": images,
        "seconds": sum(rounds),
        "round_seconds": rounds,
        "items_per_s": per_round / best,
        "tok_per_s": per_round * toks_per_img / best,
        "hit_rate": cache["hit_rate"],
        "bytes_paged": cache["bytes_paged"],
        "resident_slots_per_device": first.max_resident,
        "resident_slots_total": getattr(first, "total_slots",
                                        first.max_resident),
    }
    if use_async:
        # stall-time ledger from the shared TransferEngine: copy time the
        # dispatch thread actually blocked on vs time hidden behind waves
        result["stall_s"] = cache["stall_s"]
        result["hidden_s"] = cache["hidden_s"]
        result["overlap_ratio"] = cache["overlap_ratio"]
    print("RESULT " + json.dumps(result))
""")


def refuse_on_tpu(name: str) -> None:
    """The mesh sweeps time forced host CPU devices in child processes.
    With a TPU attached they would print CPU numbers as if they were the
    chip's (and a child could not reach the chip this process holds), so
    they refuse to run there."""
    if jax.default_backend() == "tpu":
        raise SystemExit(f"{name}: this sweep times forced host CPU devices "
                         "and does not run with a TPU attached")


def run_mesh_sweep(quick: bool = False):
    """Distributed-serving benchmark (registered as ``serve_dist``).

    One subprocess per mesh size (the forced host device count must be set
    before jax initializes), all at the same per-device expert budget.
    Writes ``serve_dist.json`` (override via ``BENCH_DIST_JSON``) with the
    acceptance flags; raises if the scaling contract breaks.
    """
    refuse_on_tpu("serve_dist mesh sweep")
    sizes = (1, 4) if quick else (1, 2, 4, 8)
    iters = 4 if quick else 10
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {}
    for n in sizes:
        r = subprocess.run(
            [sys.executable, "-c", _MESH_CHILD, str(n), str(iters)],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "PYTHONPATH": "src"}, cwd=repo)
        if r.returncode != 0:
            raise RuntimeError(f"mesh {n} child failed: {r.stderr[-2000:]}")
        line = [l for l in r.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        results[n] = json.loads(line[len("RESULT "):])
        print(f"[serve_dist] mesh {n}: "
              f"{results[n]['tok_per_s']:.0f} tok/s, "
              f"hit_rate {results[n]['hit_rate']:.2f}, "
              f"{results[n]['resident_slots_total']} resident slots")
    # async streaming children: same budget, TransferEngine-backed paging.
    # The scaling acceptance stays sync-vs-sync (apples to apples); these
    # runs put the stall-time ledger for the sharded async path into the
    # artifact — per-shard page-ins submitted across every book before
    # any fence, so shard copies overlap each other and the waves.
    async_results = {}
    for n in (1, max(sizes)):
        r = subprocess.run(
            [sys.executable, "-c", _MESH_CHILD, str(n), str(iters), "async"],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "PYTHONPATH": "src"}, cwd=repo)
        if r.returncode != 0:
            raise RuntimeError(f"async mesh {n} child failed: "
                               f"{r.stderr[-2000:]}")
        line = [l for l in r.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        async_results[n] = json.loads(line[len("RESULT "):])
        print(f"[serve_dist] mesh {n} async: "
              f"{async_results[n]['tok_per_s']:.0f} tok/s, overlap_ratio "
              f"{async_results[n]['overlap_ratio']:.2f}, stall "
              f"{async_results[n]['stall_s']*1e3:.0f}ms")
    m1, m4 = results[1], results[4]
    out = {
        "quick": bool(quick),
        "arch": "m3vit",
        "budget": "16 expert slots per device",
        "meshes": {str(n): results[n] for n in sizes},
        "meshes_async": {str(n): async_results[n] for n in async_results},
        "tok_per_s_ratio_mesh4_vs_1": m4["tok_per_s"] / m1["tok_per_s"],
        "accept_tok_per_s_2x": m4["tok_per_s"] >= 2.0 * m1["tok_per_s"],
        "accept_hit_rate_up": m4["hit_rate"] > m1["hit_rate"],
        "accept_async_overlap_reported": all(
            "overlap_ratio" in v for v in async_results.values()),
    }
    os.makedirs(os.path.dirname(DIST_JSON_PATH), exist_ok=True)
    with open(DIST_JSON_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[serve_dist] wrote {DIST_JSON_PATH}; mesh4/mesh1 tok/s "
          f"{out['tok_per_s_ratio_mesh4_vs_1']:.2f}x, hit_rate "
          f"{m1['hit_rate']:.2f} -> {m4['hit_rate']:.2f}")
    if not (out["accept_tok_per_s_2x"] and out["accept_hit_rate_up"]
            and out["accept_async_overlap_reported"]):
        raise RuntimeError(f"serve_dist acceptance failed: {out}")
    rows = [(f"serve_dist_mesh{n}", 1e6 / max(results[n]["tok_per_s"], 1e-9),
             f"tok_per_s={results[n]['tok_per_s']:.0f};"
             f"hit_rate={results[n]['hit_rate']:.2f}")
            for n in sizes]
    return rows
