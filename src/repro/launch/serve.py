"""Serving launcher: batched generation for any ``--arch``.

Usage:
  python -m repro.launch.serve --arch llama3_2_1b --smoke --tokens 32
  python -m repro.launch.serve --arch xlstm_350m --smoke --tokens 64 \
      --prefill-chunk 8
  # continuous-batching scheduler over a mixed-task workload:
  python -m repro.launch.serve --arch kimi_k2_1t_a32b --smoke --scheduler \
      --requests 16 --tasks 2
  # the paper's M3ViT (semseg+depth) through the same scheduler with
  # paged expert weights:
  python -m repro.launch.serve --arch m3vit --smoke --scheduler
  # quantized serving: int8 experts/weights + int8 KV cache under the
  # xla_int8 compute policy (~4x more resident experts per byte):
  python -m repro.launch.serve --arch m3vit --smoke --scheduler --quant int8
  python -m repro.launch.serve --arch llama3_2_1b --smoke --quant int8 \
      --dispatch-report
  # mesh serving ("DxM" = data x model): batch/KV state sharded over data,
  # tensor/expert parallelism over model, on the devices the process sees
  # (four TPU chips for 1x4).  A CPU rehearsal forces host devices itself:
  python -m repro.launch.serve --arch m3vit --smoke --scheduler --mesh 1x4
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m repro.launch.serve --arch llama3_2_1b --smoke --mesh 2x2
  # factored experts: shared basis (pinned on device) + low-rank or
  # butterfly per-expert deltas (paged) — 10-100x more experts per byte
  # of --expert-budget-bytes; composes with --quant (int8 deltas):
  python -m repro.launch.serve --arch m3vit_many --smoke --scheduler \
      --factor rank:8 --expert-budget-bytes 2000000
  python -m repro.launch.serve --arch m3vit --smoke --scheduler \
      --factor butterfly --quant int8 --dispatch-report
  # SLO-aware serving: tiered admission + preemption (KV park/restore) +
  # chunked-prefill interleave, driven by a bursty multi-tenant trace,
  # with a shared prompt-prefix cache:
  python -m repro.launch.serve --arch kimi_k2_1t_a32b --smoke --scheduler \
      --slo --trace bursty --prefix-cache 16 --prefill-chunk 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.dist import make_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import LMBackend, Request, Scheduler, ServeConfig, ServingEngine


def _parse_factor(spec: str) -> tuple[str, int]:
    """``rank:R`` -> ("rank", R); ``butterfly`` -> ("butterfly", 0)."""
    s = spec.lower()
    if s == "butterfly":
        return "butterfly", 0
    if s.startswith("rank:"):
        try:
            r = int(s.split(":", 1)[1])
        except ValueError:
            raise SystemExit(f"--factor rank:R needs an integer R, "
                             f"got {spec!r}")
        if r < 0:
            raise SystemExit(f"--factor rank must be >= 0, got {r}")
        return "rank", r
    raise SystemExit(f"--factor expects rank:R or butterfly, got {spec!r}")


def _factor_spec(args):
    """``--factor``/``--quant`` -> the ``(kind, rank, delta_bits)`` triple
    the backends and ``factor.factorize_tree`` consume (deltas quantize at
    the precision ``--quant`` picks; the basis stays fp)."""
    kind, rank = _parse_factor(args.factor)
    return kind, rank, {"int8": 8, "int4": 4}.get(args.quant)


def _factorize_params(params, args):
    """Apply ``--factor`` to an LM params tree.  Only ndim-3 expert stacks
    next to their router factor (``factorize_tree``'s gate-sibling rule);
    scanned layer stacks (ndim 4) pass through unchanged — the vit-moe
    serving path (per-layer factorization in ``M3ViTServer``) is the
    primary target."""
    from repro.factor import factorize_tree

    kind, rank, delta_bits = _factor_spec(args)
    return factorize_tree(params, kind=kind, rank=rank,
                          delta_bits=delta_bits)


def _parse_mesh(spec: str) -> tuple[int, int]:
    try:
        d, m = (int(v) for v in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh expects DxM (e.g. 2x4), got {spec!r}")
    if d < 1 or m < 1:
        raise SystemExit(f"--mesh axes must be >= 1, got {spec!r}")
    return d, m


def _serve_scheduler_lm(cfg, params, scfg, args, key, rules=None) -> int:
    from repro.serve.slo import SLOPolicy, TraceConfig, TraceGenerator

    backend = LMBackend(cfg, params, scfg, rules=rules)
    num_tasks = max(args.tasks, 1)
    if cfg.moe is not None:      # gate table bounds the task-id space
        num_tasks = min(num_tasks, backend.num_tasks)
    slo = SLOPolicy() if args.slo else None
    sched = Scheduler(backend, total_slots=args.batch, quantum=4,
                      num_tasks=num_tasks, slo=slo)
    if args.trace:
        if cfg.embed_input != "tokens":
            raise SystemExit("--trace generates token prompts; "
                             f"arch {cfg.name} embeds raw inputs")
        tc = TraceConfig(
            n=args.requests, seed=args.seed, vocab=cfg.vocab_size,
            num_tasks=num_tasks,
            burst_factor=8.0 if args.trace == "bursty" else 1.0,
            shared_prefix_len=16 if scfg.prefix_cache > 0 else 0)
        reqs = TraceGenerator(tc).generate()
    else:
        rng = np.random.default_rng(args.seed)
        if cfg.embed_input == "tokens":
            prompts = rng.integers(0, cfg.vocab_size,
                                   (args.requests, args.prompt_len))
        else:
            prompts = rng.standard_normal(
                (args.requests, args.prompt_len, cfg.d_model)
            ).astype(np.float32)
        lengths = rng.integers(max(args.tokens // 4, 1), args.tokens + 1,
                               args.requests)
        reqs = [Request(rid=i, task_id=i % num_tasks,
                        prompt=np.asarray(prompts[i], prompts.dtype),
                        max_new_tokens=int(lengths[i]))
                for i in range(args.requests)]
    done = sched.run(reqs)
    m = sched.metrics()
    print(f"[serve] arch={cfg.name} scheduler served {len(done)} requests "
          f"({m['tokens']} tokens) over {num_tasks} tasks: "
          f"{m['tok_per_s']:.1f} tok/s, p50 {m['latency_p50_s']*1e3:.0f}ms, "
          f"p99 {m['latency_p99_s']*1e3:.0f}ms, "
          f"slot util {m.get('slot_utilization', 0):.2f}")
    for name, tm in sorted(m.get("tiers", {}).items()):
        if slo is None and not args.trace:
            break
        print(f"[serve]   tier {name}: {tm['requests']} reqs, "
              f"ttft p50 {tm['ttft_p50_s']*1e3:.0f}ms / "
              f"p99 {tm['ttft_p99_s']*1e3:.0f}ms, "
              f"slo_attainment {tm['slo_attainment']:.2f}, "
              f"preemptions {tm['preemptions']}")
    if slo is not None:
        print(f"[serve] slo: goodput {m['goodput_rps']:.1f} req/s "
              f"({m['goodput_tok_per_s']:.1f} tok/s), "
              f"preemptions {m['preemptions']}, restores {m['restores']}, "
              f"parked peak {m['parked_bytes_peak']/1e6:.2f} MB")
    if "prefix_cache" in m:
        pc = m["prefix_cache"]
        print(f"[serve] prefix cache: {pc['entries']} entries, "
              f"hit_rate {pc['hit_rate']:.2f}, "
              f"{pc['hit_tokens']} prefill tokens skipped")
    return 0


def mesh_rules(spec: str):
    """``--mesh DxM`` -> sharding rules over the first D*M devices the
    process sees; too few devices is an error, never a fallback."""
    from repro.dist.sharding import ShardingRules

    d, m = _parse_mesh(spec)
    if d * m > jax.device_count():
        raise SystemExit(
            f"--mesh {spec} needs {d * m} devices, the process sees "
            f"{jax.device_count()} ({jax.devices()[0].platform})")
    mesh = make_mesh((d, m), ("data", "model"),
                     devices=jax.devices()[:d * m])
    # serving keeps dense weights replicated over data (no FSDP): decode
    # is latency-bound and the weight gathers would dominate
    return ShardingRules.for_mesh(mesh, fsdp=False)


def vision_requests(key, n: int) -> list:
    """``n`` seeded image requests alternating semseg/depth (4 distinct
    images, reused round-robin)."""
    from repro.configs import m3vit as MV

    imgs = np.asarray(jax.random.normal(
        key, (4, MV.IMAGE_H, MV.IMAGE_W, 3)), np.float32)
    return [Request(rid=i, task_id=i % len(MV.TASKS),
                    prompt=imgs[i % imgs.shape[0]])
            for i in range(n)]


def vision_scheduler(cfg, params, *, batch: int,
                     resident_fraction: float = 0.5, expert_budget_bytes=None,
                     rules=None, async_paging: bool = False, factor=None,
                     placement="static") -> Scheduler:
    """The vision serving stack: ``VisionBackend`` (paged MoE layers) under
    the task-bucketed ``Scheduler`` with ``batch`` slots in all."""
    from repro.configs import m3vit as MV
    from repro.serve.vision import VisionBackend

    # factorization happens per MoE layer inside the backend (after the
    # per-layer slice: the stacked tree's ndim-4 expert leaves are not
    # factorable, and each layer gets its own basis); quantized expert
    # leaves re-factor there too — factorize accepts QTensor input
    backend = VisionBackend(cfg, params,
                            resident_fraction=resident_fraction,
                            expert_budget_bytes=expert_budget_bytes,
                            rules=rules, async_paging=async_paging,
                            factor=factor, placement=placement)
    return Scheduler(backend, total_slots=batch, quantum=1,
                     num_tasks=len(MV.TASKS))


def _serve_scheduler_vision(cfg, args, rules=None) -> int:
    from repro.models import vit as V

    key = jax.random.PRNGKey(args.seed)
    k_params, k_data = jax.random.split(key)
    params = V.init_params(k_params, cfg)
    if args.quant:
        from repro.quant import quantize_tree
        params = quantize_tree(params, bits=8 if args.quant == "int8" else 4)
    sched = vision_scheduler(
        cfg, params, batch=args.batch,
        resident_fraction=args.resident_fraction,
        expert_budget_bytes=args.expert_budget_bytes or None, rules=rules,
        async_paging=args.async_paging,
        factor=_factor_spec(args) if args.factor else None,
        placement=args.placement)
    done = sched.run(vision_requests(k_data, args.requests))
    m = sched.metrics()
    cache = m.get("expert_cache", {})
    print(f"[serve] arch={cfg.name} scheduler served {len(done)} "
          f"semseg/depth requests: {m['items_per_s']:.1f} img/s, "
          f"p50 {m['latency_p50_s']*1e3:.0f}ms; expert cache: "
          f"hit_rate {cache.get('hit_rate', 1.0):.2f} at "
          f"resident_fraction {cache.get('resident_fraction', 1.0):.2f}")
    if args.async_paging:
        print(f"[serve] async paging: "
              f"stall {cache.get('stall_s', 0.0)*1e3:.1f}ms, "
              f"hidden {cache.get('hidden_s', 0.0)*1e3:.1f}ms, "
              f"overlap_ratio {cache.get('overlap_ratio', 1.0):.2f}")
    pl = m.get("placement")
    if pl is not None:
        load = ", ".join(f"{v:.0f}" for v in (m.get("shard_load") or []))
        print(f"[serve] placement {pl['policy']}: "
              f"generation {pl['generation']}, "
              f"plan_swaps {pl['plan_swaps']}, "
              f"migrations {pl['migrations']}, "
              f"replications {pl['replications']}, "
              f"shard_load [{load}] "
              f"(imbalance {m.get('shard_load_imbalance', 0.0):.2f})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--task-id", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill size (0 = one-shot)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a sequence at this token (-1 = never)")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve a mixed-task workload through the "
                         "continuous-batching scheduler")
    ap.add_argument("--requests", type=int, default=16,
                    help="scheduler mode: number of requests")
    ap.add_argument("--tasks", type=int, default=2,
                    help="scheduler mode: number of gating tasks")
    ap.add_argument("--slo", action="store_true",
                    help="scheduler mode: SLO-aware tiered admission — "
                         "interactive-first, batch-slot preemption with "
                         "KV park/restore, chunked-prefill interleave")
    ap.add_argument("--trace", default=None, choices=["bursty", "steady"],
                    help="scheduler mode: drive arrivals from a seeded "
                         "multi-tenant traffic trace instead of the "
                         "synthetic uniform workload")
    ap.add_argument("--prefix-cache", type=int, default=0,
                    help="scheduler mode: cache up to N prompt prefill "
                         "states in a radix trie; admissions skip their "
                         "longest cached prefix (attention archs only)")
    ap.add_argument("--resident-fraction", type=float, default=0.5,
                    help="vision scheduler: fraction of experts resident")
    ap.add_argument("--async-paging", action="store_true",
                    help="vision scheduler: page expert weights "
                         "asynchronously (router-lookahead prefetch + "
                         "double-buffered waves; bit-exact with sync "
                         "paging, reports stall_s/overlap_ratio)")
    ap.add_argument("--mesh", default=None,
                    help="DxM mesh (data x model), e.g. 2x2: serve state "
                         "sharded over data, tensor/expert parallelism "
                         "over model, on the first D*M visible devices")
    ap.add_argument("--placement", default="static",
                    choices=["static", "lru", "budget", "elastic"],
                    help="vision scheduler: expert placement policy — "
                         "'static' is the fixed modulo partition; "
                         "'elastic' replicates usage-hot experts across "
                         "mesh shards and migrates cold ownership live "
                         "(bit-exact; needs --mesh with model > 1)")
    ap.add_argument("--expert-budget-bytes", type=int, default=0,
                    help="vision scheduler: per-device expert-weight byte "
                         "budget (0 = use --resident-fraction); each mesh "
                         "model-shard holds its own budget's worth")
    ap.add_argument("--policy", default=None,
                    choices=["xla", "blocked", "pallas", "ref", "xla_int8",
                             "xla_factored"],
                    help="compute policy for every serving step (default: "
                         "the arch config's policy)")
    ap.add_argument("--quant", default=None, choices=["int8", "int4"],
                    help="quantize the weight tree (QTensor leaves), store "
                         "the KV cache int8, and serve under the xla_int8 "
                         "policy unless --policy overrides it")
    ap.add_argument("--factor", default=None, metavar="KIND",
                    help="factor per-expert FFN weights into a shared basis "
                         "+ per-expert delta ('rank:R' or 'butterfly') and "
                         "serve the MoE GEMM under the xla_factored impl; "
                         "the paged cache pins the basis and pages only the "
                         "deltas.  Composes with --quant: deltas quantize "
                         "at the same precision, the basis stays fp")
    ap.add_argument("--dispatch-report", action="store_true",
                    help="print ops.dispatch_report() after serving")
    args = ap.parse_args()

    from repro.ops import dispatch_report, policy_named

    enable_compile_cache()
    rules = None
    if args.mesh:
        rules = mesh_rules(args.mesh)
        print(f"[serve] mesh {args.mesh} (data x model) over "
              f"{rules.mesh.devices.size} {jax.devices()[0].platform} "
              f"devices")

    cfg = configs.get(args.arch, smoke=args.smoke)
    policy = policy_named(args.policy) if args.policy else None
    kv_quant = None
    if args.quant:
        # quantized serving: int8 KV caches + the int8 compute policy, so
        # the quantized impls are dispatch HITS (check --dispatch-report)
        policy = policy or policy_named("xla_int8")
        kv_quant = "int8"
    if args.factor:
        # factored experts: the MoE GEMM must run the xla_factored impl on
        # top of whatever quantization picked (dense blocks keep their
        # policy; only moe_grouped_gemm is overridden)
        policy = (policy or policy_named("xla_factored")).with_impls(
            moe_grouped_gemm="xla_factored")
    scfg = ServeConfig(max_len=args.max_len, temperature=args.temperature,
                       eos_id=args.eos_id, seed=args.seed,
                       prefill_chunk=args.prefill_chunk, policy=policy,
                       kv_quant=kv_quant, async_paging=args.async_paging,
                       prefix_cache=args.prefix_cache)

    if args.scheduler and cfg.family == "vit-moe":
        if policy is not None:
            from dataclasses import replace
            cfg = replace(cfg, policy=policy)
        rc = _serve_scheduler_vision(cfg, args, rules=rules)
        if args.dispatch_report:
            print("[serve] dispatch report:", dispatch_report())
        return rc

    key = jax.random.PRNGKey(args.seed)
    k_params, k_prompts = jax.random.split(key)   # independent init/data
    params = M.init_params(k_params, cfg)
    if args.factor:
        params = _factorize_params(params, args)
    if args.quant:
        from repro.quant import quantize_tree
        params = quantize_tree(params, bits=8 if args.quant == "int8" else 4)

    if args.scheduler:
        if scfg.temperature > 0:
            from dataclasses import replace
            scfg = replace(scfg, temperature=0.0)
            print("[serve] scheduler decodes greedily; ignoring temperature")
        rc = _serve_scheduler_lm(cfg, params, scfg, args, k_prompts,
                                 rules=rules)
        if args.dispatch_report:
            print("[serve] dispatch report:", dispatch_report())
        return rc

    engine = ServingEngine(cfg, params, scfg, rules=rules)
    if cfg.embed_input == "tokens":
        prompts = jax.random.randint(
            k_prompts, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    else:
        prompts = jax.random.normal(
            k_prompts, (args.batch, args.prompt_len, cfg.d_model),
            dtype=cfg.activation_dtype)
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.tokens, task_id=args.task_id)
    dt = time.perf_counter() - t0
    print(f"[serve] arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({args.batch*args.tokens/dt:.1f} tok/s)")
    print(out[: min(2, out.shape[0])])
    if args.dispatch_report:
        print("[serve] dispatch report:", dispatch_report())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
