"""Distributed serving benchmark: mesh sweep + skewed placement sweep.

Two trajectories, one ``bench/serve_dist.json`` artifact:

  * **mesh sweep** (``serve_throughput.run_mesh_sweep``) — paged M³ViT
    serving at mesh 1/2/4/8 with a fixed per-device expert budget:
    expert parallelism must raise aggregate tok/s and hit rate.
  * **skew sweep** (this module) — the placement subsystem's trajectory:
    zipf-skewed routing (``--skew zipf:a``) concentrates the hot experts
    inside ONE shard's static block, so the static partition serializes
    on that shard's slot bank while its siblings idle.  The elastic
    policy (hot-expert replication + cold-expert migration, live plan
    swaps between forwards) must recover the lost parallelism:

      - bit-exact per token with dense ``apply_moe`` in EVERY mode
        (``accept_skew_parity`` — placement moves weights, never values);
      - ≥ 1.5× aggregate tok/s over static at mesh 4 under the 80/20
        skew (``accept_elastic_tok_per_s_1p5x``);
      - migration page-ins ride the async transfer engine behind compute
        (``accept_migration_overlap`` — the ``migrate`` tag's
        overlap_ratio > 0 in the per-tag ledger);
      - per-shard routed-token utilization flattens vs static
        (``accept_shard_util``).

Each mesh size runs in a subprocess (forced host devices must be set
before jax initializes); each child computes the dense reference
in-process, so parity is self-contained per configuration.

Usage:
  PYTHONPATH=src python -m benchmarks.serve_dist [--quick]
      [--skew zipf:a] [--skew-only]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

from benchmarks.serve_throughput import (DIST_JSON_PATH, refuse_on_tpu,
                                         run_mesh_sweep)

_SKEW_CHILD = textwrap.dedent("""
    import os, sys
    n = int(sys.argv[1]); iters = int(sys.argv[2])
    zipf_a = float(sys.argv[3]); mode = sys.argv[4]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import moe as moe_lib
    from repro.dist import make_mesh
    from repro.serve.expert_cache import PagedMoE
    from repro.serve.placement import ElasticPolicy
    from repro.serve.transfer import TransferEngine

    E = 64
    # capacity_factor 32: even the hottest expert's full token load fits
    # in capacity, so routing stats see the true skew (a tight capacity
    # clips dropped tokens out of the EMA and flattens the signal the
    # elastic policy thresholds on) and the dense reference is exact
    # d_ff 2048: heavy experts make the per-wave GEMM dominate the fixed
    # per-forward overhead (dispatch einsums, all-to-all), so the
    # static-vs-elastic wave-count gap shows up in the timing instead of
    # washing out; it also keeps the routed token count small (the knob
    # that widens the sampled expert tail and re-introduces paging)
    cfg = moe_lib.MoEConfig(d_model=32, d_ff=2048, num_experts=E, top_k=2,
                            num_tasks=1, capacity_factor=32.0,
                            group_size=64, impl="grouped",
                            expert_kind="swiglu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                              dtype=jnp.float32)
    # zipf:a gate-logit bias.  The 0.4 factor calibrates the bias to the
    # benchmark trunk's per-token gate-logit spread (~0.5 std) so the
    # REALIZED top-k frequencies follow ~1/(e+1)^a rather than collapsing
    # onto the top expert; a=1.2 lands in the 80/20 regime.  The hot
    # experts are the LOW ids — all inside shard 0's static block at
    # every mesh size (the adversarial case for the static partition)
    bias = -0.4 * zipf_a * np.log(np.arange(E, dtype=np.float64) + 1.0)
    params = dict(params,
                  gate_bias=jnp.asarray(bias[None, :], jnp.float32))
    xs = [(jax.random.normal(jax.random.PRNGKey(11 + i), (2, 64, 32))
           * 0.5).astype(jnp.float32) for i in range(4)]
    refs = [np.asarray(moe_lib.apply_moe(params, cfg, x, task_id=0)[0])
            for x in xs]

    mesh = make_mesh((1, n), ("data", "model"))
    engine = TransferEngine(workers=2) if mode == "elastic_async" else None
    placement = "static" if mode == "static" else ElasticPolicy(
        rebalance_every=2, replicate_factor=2.0)
    # resident_fraction 0.5: under a BALANCED plan the skew's working
    # set fits total residency (steady state pages nothing), while the
    # static partition still crams every hot expert through one shard's
    # bank — extra sequential waves plus per-forward thrash
    paged = PagedMoE(params, cfg, resident_fraction=0.5, mesh=mesh,
                     placement=placement, transfer_engine=engine)

    # settle: compile, warm the usage EMA, let the elastic plan converge
    # (live swaps happen HERE — and parity must hold through every one)
    parity_ok = True
    for r in range(6):
        for i, x in enumerate(xs):
            y, _ = paged(x, task_id=0)
            if r < 3:
                parity_ok = parity_ok and bool(
                    (np.asarray(y) == refs[i]).all())
    # migration transfers fire during the settle phase's plan swaps;
    # read their ledger entry BEFORE the stats reset below
    s0 = paged.cache.stats()
    migrate_tags = (s0.get("transfer_tags") or {}).get("migrate")

    paged.reset_stats()
    rounds = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for x in xs:
            paged(x, task_id=0)
        rounds.append(time.perf_counter() - t0)
    # steady state is bit-exact too (plan swaps settled, but check)
    for i, x in enumerate(xs):
        y, _ = paged(x, task_id=0)
        parity_ok = parity_ok and bool((np.asarray(y) == refs[i]).all())
    # second-smallest round: robust to one unlucky sample on shared CPUs
    best = sorted(rounds)[1] if len(rounds) > 1 else rounds[0]
    toks_per_round = sum(int(np.prod(x.shape[:-1])) for x in xs)

    s = paged.cache.stats()
    tot = paged.usage.totals.sum(axis=0).astype(float)
    hot = np.sort(tot)[::-1]
    k20 = max(1, int(round(0.2 * E)))
    result = {
        "mesh": n, "mode": mode, "zipf_a": zipf_a,
        "tok_per_s": toks_per_round / best,
        "round_seconds": rounds,
        "parity_ok": parity_ok,
        "top20_share": float(hot[:k20].sum() / max(hot.sum(), 1e-9)),
        "waves_per_forward": paged.waves / paged.forwards,
        "hit_rate": s["hit_rate"],
        "bytes_paged": s["bytes_paged"],
        "shard_load": s["shard_load"],
        "shard_load_imbalance": s["shard_load_imbalance"],
        "placement": s["placement"],
    }
    if migrate_tags is not None:
        result["migrate_transfers"] = migrate_tags
    print("RESULT " + json.dumps(result))
""")


def _parse_skew(spec: str) -> float:
    """``zipf:a`` -> the zipf exponent ``a`` (the only supported family)."""
    kind, _, val = spec.partition(":")
    if kind != "zipf" or not val:
        raise ValueError(f"unsupported --skew {spec!r}; expected zipf:a")
    a = float(val)
    if a <= 0:
        raise ValueError(f"zipf exponent must be > 0, got {a}")
    return a


def _child(repo: str, mesh: int, iters: int, zipf_a: float,
           mode: str) -> dict:
    r = subprocess.run(
        [sys.executable, "-c", _SKEW_CHILD, str(mesh), str(iters),
         str(zipf_a), mode],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=repo)
    if r.returncode != 0:
        raise RuntimeError(
            f"skew child mesh={mesh} mode={mode} failed: "
            f"{r.stderr[-2000:]}")
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    p = out["placement"]
    print(f"[serve_dist] skew mesh {mesh} {mode}: "
          f"{out['tok_per_s']:.0f} tok/s, "
          f"waves/fwd {out['waves_per_forward']:.2f}, "
          f"imbalance {out['shard_load_imbalance']:.2f}, "
          f"swaps {p['plan_swaps']}, repl {p['replications']}")
    return out


def run_skew_sweep(quick: bool = False, skew: str = "zipf:1.2"):
    """Skewed static-vs-elastic placement sweep; merges a ``skew``
    section (with its acceptance flags) into ``bench/serve_dist.json``."""
    refuse_on_tpu("serve_dist skew sweep")
    zipf_a = _parse_skew(skew)
    meshes = (4,) if quick else (2, 4)
    iters = 3 if quick else 6
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sweep: dict[int, dict[str, dict]] = {}
    for m in meshes:
        sweep[m] = {mode: _child(repo, m, iters, zipf_a, mode)
                    for mode in ("static", "elastic")}
    # the async elastic run proves migrations ride the transfer engine
    # behind compute; the 1.5x acceptance stays sync-vs-sync
    async_res = _child(repo, max(meshes), iters, zipf_a, "elastic_async")

    top = max(meshes)
    ratio = (sweep[top]["elastic"]["tok_per_s"]
             / sweep[top]["static"]["tok_per_s"])
    migrate = async_res.get("migrate_transfers") or {}
    skew_out = {
        "skew": skew,
        "quick": bool(quick),
        "meshes": {str(m): sweep[m] for m in meshes},
        "elastic_async": async_res,
        "top20_share": sweep[top]["static"]["top20_share"],
        "elastic_vs_static_tok_per_s": ratio,
        "accept_skew_parity": all(
            r["parity_ok"] for per in sweep.values() for r in per.values())
        and async_res["parity_ok"],
        "accept_elastic_tok_per_s_1p5x": ratio >= 1.5,
        "accept_migration_overlap": (
            migrate.get("fenced", 0) >= 1
            and migrate.get("overlap_ratio", 0.0) > 0.0),
        "accept_shard_util": all(
            per["elastic"]["shard_load_imbalance"]
            < per["static"]["shard_load_imbalance"]
            for per in sweep.values()),
    }
    # merge into the mesh sweep's artifact (either order of the two
    # sweeps converges to the same file contents)
    out = {}
    if os.path.exists(DIST_JSON_PATH):
        with open(DIST_JSON_PATH) as f:
            out = json.load(f)
    out["skew"] = skew_out
    os.makedirs(os.path.dirname(DIST_JSON_PATH), exist_ok=True)
    with open(DIST_JSON_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[serve_dist] skew({skew}) mesh{top} elastic/static "
          f"{ratio:.2f}x, top-20% share "
          f"{skew_out['top20_share']:.2f}, migrate overlap "
          f"{migrate.get('overlap_ratio', 0.0):.2f}")
    if not (skew_out["accept_skew_parity"]
            and skew_out["accept_elastic_tok_per_s_1p5x"]
            and skew_out["accept_migration_overlap"]
            and skew_out["accept_shard_util"]):
        raise RuntimeError(f"serve_dist skew acceptance failed: {skew_out}")
    return [(f"serve_dist_skew_{mode}_mesh{top}",
             1e6 / max(sweep[top][mode]["tok_per_s"], 1e-9),
             f"tok_per_s={sweep[top][mode]['tok_per_s']:.0f};"
             f"imbalance={sweep[top][mode]['shard_load_imbalance']:.2f}")
            for mode in ("static", "elastic")]


def run(quick: bool = False, skew: str = "zipf:1.2",
        skew_only: bool = False):
    rows = [] if skew_only else run_mesh_sweep(quick=quick)
    rows += run_skew_sweep(quick=quick, skew=skew)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="fewer meshes / reps")
    ap.add_argument("--skew", default="zipf:1.2",
                    help="skew family for the placement sweep (zipf:a)")
    ap.add_argument("--skew-only", action="store_true",
                    help="skip the mesh sweep; run only the skewed "
                         "static-vs-elastic placement sweep")
    args = ap.parse_args()
    for name, us, derived in run(quick=args.quick, skew=args.skew,
                                 skew_only=args.skew_only):
        print(f"{name},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
