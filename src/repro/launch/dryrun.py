import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every assigned (architecture × input shape) cell, on BOTH production
meshes (single-pod 16×16 and multi-pod 2×16×16), this script:

  1. builds the jitted step (full train step — loss + grad + AdamW — for
     train shapes; prefill / decode serve steps for inference shapes),
  2. ``.lower()``s it on ``jax.ShapeDtypeStruct`` stand-ins (zero device
     allocation) with explicit in_shardings from the rules tables,
  3. ``.compile()``s the lowered module — a sharding mismatch, unsupported
     collective, or non-divisible layout fails HERE, which is the point,
  4. records ``memory_analysis()`` (per-device bytes: proves it fits),
     ``cost_analysis()`` (FLOPs/bytes → §Roofline), and the parsed
     per-collective byte counts from the optimized HLO.

The lines above MUST run before any jax import: jax locks the device
count at first init, and the production meshes need 512 host placeholder
devices (forced host devices exist only on the CPU platform).  This
flag is set ONLY here — tests/benches see 1 device.

Usage:
  python -m repro.launch.dryrun --arch llama3_2_1b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--out results/dryrun]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES, ArchConfig, Shape
from repro.dist.sharding import (
    ShardingRules,
    batch_sharding,
    opt_state_shardings,
    param_sharding_rules,
)
from repro.launch.mesh import HW, make_production_mesh
from repro.models import model as M
from repro.optim import OptConfig, adamw_init
from repro.roofline.analysis import analyze_compiled
from repro.train.step import TrainConfig, make_train_step, make_serve_step

# grad-accumulation per train cell: microbatch = global_batch / accum must
# stay divisible by the batch axes (pod*data = 32 on the multi-pod mesh)
TRAIN_ACCUM = 8


def opt_config_for(cfg: ArchConfig) -> OptConfig:
    big = cfg.param_count() > 3e10
    # >30B params: bf16 momentum + factored second moment (DESIGN.md §5)
    return OptConfig(momentum_dtype="bfloat16" if big else "float32",
                     factored=big)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: dict | None = None):
    """Returns (lowered, mesh, cfg, shape).  Raises on sharding errors.

    ``variant`` carries §Perf hillclimb overrides:
      fsdp (bool), seq_shard (bool), moe_impl (str), accum (int),
      attn_block_k (int).
    """
    variant = variant or {}
    cfg = configs.get(arch)
    if variant.get("moe_impl") and cfg.moe is not None:
        from dataclasses import replace as _rp
        cfg = _rp(cfg, moe=_rp(cfg.moe, impl=variant["moe_impl"]))
    if variant.get("attn_block_k"):
        from dataclasses import replace as _rp

        from repro.ops.policy import ComputePolicy
        pol = (cfg.policy or ComputePolicy()).with_tiles(
            "attention", block_k=variant["attn_block_k"])
        cfg = _rp(cfg, policy=pol)
    if variant.get("no_remat"):
        from dataclasses import replace as _rp
        cfg = _rp(cfg, remat=False)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ShardingRules.for_mesh(
        mesh, seq_shard=variant.get("seq_shard", False),
        fsdp=variant.get("fsdp", True))

    params_shapes = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    params_sh = param_sharding_rules(params_shapes, rules)

    if shape.kind == "train":
        ocfg = opt_config_for(cfg)
        tcfg = TrainConfig(opt=ocfg,
                           accum_steps=variant.get("accum", TRAIN_ACCUM))
        opt_shapes = jax.eval_shape(
            lambda: adamw_init(params_shapes, ocfg))
        opt_sh = opt_state_shardings(opt_shapes, params_shapes, rules)
        batch_shapes = M.input_specs(cfg, shape)
        batch_sh = batch_sharding(batch_shapes, rules)
        step = make_train_step(cfg, tcfg, rules=rules, jit=False)
        lowered = jax.jit(
            step, donate_argnums=(0, 1),
            in_shardings=(params_sh, opt_sh, batch_sh),
        ).lower(params_shapes, opt_shapes, batch_shapes)
        return lowered, mesh, cfg, shape

    prefill_fn, decode_fn = make_serve_step(cfg, rules=rules, jit=False)
    b, s = shape.global_batch, shape.seq_len
    state_shapes = jax.eval_shape(lambda: M.init_state(cfg, b, s))
    state_sh = batch_sharding(state_shapes, rules)

    if shape.kind == "prefill":
        in_shapes = M.input_specs(cfg, shape)["inputs"]
        in_sh = batch_sharding(in_shapes, rules)
        lowered = jax.jit(
            prefill_fn, donate_argnums=(2,),
            in_shardings=(params_sh, in_sh, state_sh),
        ).lower(params_shapes, in_shapes, state_shapes)
        return lowered, mesh, cfg, shape

    # decode: one new token against a seq_len cache
    if cfg.embed_input == "tokens":
        tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    else:
        tok = jax.ShapeDtypeStruct((b, 1, cfg.d_model), cfg.activation_dtype)
    tok_sh = batch_sharding(tok, rules)
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    idx_sh = batch_sharding(idx, rules)
    lowered = jax.jit(
        decode_fn, donate_argnums=(2,),
        in_shardings=(params_sh, tok_sh, state_sh, idx_sh),
    ).lower(params_shapes, tok, state_shapes, idx)
    return lowered, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, verbose: bool = True,
             variant: dict | None = None, tag: str = "") -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    lowered, mesh, cfg, shape = build_cell(arch, shape_name, multi_pod,
                                           variant)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        flops = cost.get("flops") if hasattr(cost, "get") else None
        print(f"  cost_analysis flops={flops}")

    report = analyze_compiled(compiled, cfg, shape, mesh)
    rec = report.to_dict()
    from repro.roofline.analysis import kernel_adjusted_terms
    rec["kernel_adjusted"] = kernel_adjusted_terms(rec, cfg, shape)
    rec.update(
        arch=arch, shape=shape_name, mesh=mesh_name, variant=tag or "baseline",
        lower_s=t_lower, compile_s=t_compile,
        memory_analysis=str(mem),
        argument_bytes=float(getattr(mem, "argument_size_in_bytes", 0) or 0),
        output_bytes=float(getattr(mem, "output_size_in_bytes", 0) or 0),
        temp_bytes=float(getattr(mem, "temp_size_in_bytes", 0) or 0),
        ok=True,
    )
    if verbose:
        print(f"  roofline: compute {report.t_compute*1e3:.2f}ms  "
              f"memory {report.t_memory*1e3:.2f}ms  "
              f"collective {report.t_collective*1e3:.2f}ms  "
              f"-> {report.bottleneck}-bound  "
              f"useful={report.useful_ratio:.2f} "
              f"roofline_frac={report.roofline_fraction:.3f}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = os.path.join(out_dir,
                          f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
        if verbose:
            print(f"  -> {fn}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="architecture id (see configs)")
    ap.add_argument("--shape", help="shape id: train_4k | prefill_32k | "
                                    "decode_32k | long_500k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell on both meshes")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--list", action="store_true")
    # §Perf hillclimb variant flags
    ap.add_argument("--tag", default="", help="variant tag for the output file")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="pure TP weights (no FSDP over data)")
    ap.add_argument("--fsdp-moe-only", action="store_true",
                    help="FSDP only the MoE expert weights; dense TP-only")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-shard activations (SP)")
    ap.add_argument("--moe-impl", default=None,
                    choices=["onehot", "grouped", "ep_local"])
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--attn-block-k", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true",
                    help="disable per-block activation checkpointing")
    args = ap.parse_args()
    variant = {}
    if args.no_fsdp:
        variant["fsdp"] = False
    if args.fsdp_moe_only:
        variant["fsdp"] = "moe_only"
    if args.seq_shard:
        variant["seq_shard"] = True
    if args.moe_impl:
        variant["moe_impl"] = args.moe_impl
    if args.accum:
        variant["accum"] = args.accum
    if args.attn_block_k:
        variant["attn_block_k"] = args.attn_block_k
    if args.no_remat:
        variant["no_remat"] = True

    if args.list:
        for a, s, runnable in configs.cells(include_skipped=True):
            print(f"{a:28s} {s:12s} {'runnable' if runnable else 'SKIP (full attention @500k)'}")
        return 0

    if args.all:
        failures = []
        for a, s, runnable in configs.cells():
            if not runnable:
                continue
            for mp in (False, True):
                try:
                    run_cell(a, s, mp, out_dir=args.out)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((a, s, mp, repr(e)))
        if failures:
            print(f"FAILURES: {failures}")
            return 1
        print("all cells OK")
        return 0

    run_cell(args.arch, args.shape, args.multi_pod, out_dir=args.out,
             variant=variant, tag=args.tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())
