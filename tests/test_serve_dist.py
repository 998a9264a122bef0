"""Distributed serving parity: sharded == single-device, bit for bit.

The tentpole guarantee of the mesh serving path: moving experts onto
per-shard slot banks (expert parallelism) and sharding serve state over
the data axis must never change a single value —

  * expert-parallel ``PagedMoE`` forward (fp32/bf16 + the int8/int4
    quantized expert paths from the quant subsystem) is BIT-EXACT with
    single-device ``apply_moe`` at equal capacity on mesh sizes 2 and 4;
  * greedy decode through the mesh-sharded ``ServingEngine`` is
    token-identical to the single-device engine at mesh sizes 1/2/4.

Multi-device cases run in subprocesses with forced host devices
(``--xla_force_host_platform_device_count=8``) so the main test session
keeps seeing 1 device — the same pattern as tests/test_moe_ep.py.
"""

import subprocess
import sys
import textwrap

import jax
import numpy as np

import pytest

from repro.dist import make_mesh

REPO = __file__.rsplit("/tests/", 1)[0]


def _run(script: str, timeout: int = 600) -> str:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                       cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return r.stdout


HEADER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import make_mesh
""")


PAGED_PARITY = HEADER + textwrap.dedent("""
    from repro.core import moe as moe_lib
    from repro.serve.expert_cache import PagedMoE

    x32 = None
    for m in (2, 4):
        mesh = make_mesh((1, m), ("data", "model"))
        for kind in ("gelu", "swiglu"):
            for dtype in (jnp.float32, jnp.bfloat16):
                cfg = moe_lib.MoEConfig(
                    d_model=32, d_ff=64, num_experts=8, top_k=2,
                    num_tasks=2, capacity_factor=2.0, group_size=64,
                    impl="grouped", expert_kind=kind)
                params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                                          dtype=dtype)
                x = (jax.random.normal(jax.random.PRNGKey(1),
                                       (2, 50, 32)) * 0.5).astype(dtype)
                for task in (0, 1):
                    ref, aref = moe_lib.apply_moe(params, cfg, x,
                                                  task_id=task)
                    paged = PagedMoE(params, cfg, resident_fraction=0.5,
                                     mesh=mesh)
                    y, aux = paged(x, task_id=task)
                    np.testing.assert_array_equal(
                        np.asarray(y, np.float32),
                        np.asarray(ref, np.float32),
                        err_msg=f"mesh={m} {kind} {dtype} task={task}")
                    assert abs(float(aux) - float(aref)) < 1e-6
                    # per-shard banks: aggregate residency covers every
                    # shard, never exceeds the per-shard bound
                    s = paged.cache.stats()
                    assert s["num_shards"] == m
                    assert s["max_resident"] <= cfg.num_experts // m
    print("PAGED_PARITY_OK")
""")


PAGED_QUANT_PARITY = HEADER + textwrap.dedent("""
    from repro.core import moe as moe_lib
    from repro.ops import policy_named, use_policy
    from repro.quant import quantize_tree
    from repro.serve.expert_cache import PagedMoE

    cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                            num_tasks=2, capacity_factor=2.0, group_size=64,
                            impl="grouped", expert_kind="swiglu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32))
         * 0.5).astype(jnp.float32)
    for bits in (8, 4):
        qparams = quantize_tree(dict(params), bits=bits)
        with use_policy(policy_named("xla_int8")):
            ref, _ = moe_lib.apply_moe(qparams, cfg, x, task_id=0)
            y1, _ = PagedMoE(qparams, cfg,
                             resident_fraction=0.5)(x, task_id=0)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(ref),
                                      err_msg=f"int{bits} single-device")
        for m in (2, 4):
            mesh = make_mesh((1, m), ("data", "model"))
            with use_policy(policy_named("xla_int8")):
                ym, _ = PagedMoE(qparams, cfg, resident_fraction=0.5,
                                 mesh=mesh)(x, task_id=0)
            np.testing.assert_array_equal(
                np.asarray(ym), np.asarray(ref),
                err_msg=f"int{bits} mesh={m}")
    print("PAGED_QUANT_PARITY_OK")
""")


BUDGET_SCALING = HEADER + textwrap.dedent("""
    # fixed PER-DEVICE byte budget: resident experts scale linearly with
    # the model-axis shard count, and the steady-state demand hit rate
    # rises once the working set fits the aggregate residency
    from repro.core import moe as moe_lib
    from repro.serve.expert_cache import PagedMoE

    cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                            capacity_factor=2.0, group_size=64,
                            impl="grouped", expert_kind="gelu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32))
         * 0.5).astype(jnp.float32)
    per_expert = sum(int(np.asarray(params[n])[0].nbytes)
                     for n in ("w1", "b1", "w2", "b2"))
    budget = 2 * per_expert          # 2 slots per device
    rates, residents = {}, {}
    for m in (1, 4):
        mesh = make_mesh((1, m), ("data", "model")) if m > 1 else None
        paged = PagedMoE(params, cfg, budget_bytes=budget, mesh=mesh)
        for _ in range(3):
            paged(x, task_id=0)      # warm: every expert is routed to
        if m > 1:
            paged.cache.reset_stats()
        else:
            c = paged.cache
            c.hits = c.misses = c.evictions = c.bytes_paged = 0
        paged(x, task_id=0)
        rates[m] = paged.cache.hit_rate
        residents[m] = (paged.cache.total_slots if m > 1
                        else paged.cache.max_resident)
    assert residents[4] == 4 * residents[1], (residents, rates)
    assert rates[4] > rates[1], (residents, rates)
    assert rates[4] == 1.0, rates   # all 8 experts fit 4 shards x 2 slots
    print("BUDGET_SCALING_OK", residents, rates)
""")


DECODE_PARITY = HEADER + textwrap.dedent("""
    # fp32 activations: GSPMD partitioning may re-tile bf16 matmuls (a
    # legitimate ulp-level reduction reorder on the CPU backend); fp32
    # logits keep greedy argmax bit-stable, which is what "token-
    # identical" asserts
    from dataclasses import replace
    from repro import configs
    from repro.dist.sharding import ShardingRules
    from repro.models import model as M
    from repro.serve import ServeConfig, ServingEngine

    for arch in ("llama3_2_1b", "kimi_k2_1t_a32b"):
        cfg = replace(configs.get(arch, smoke=True), dtype="float32")
        params = M.init_params(jax.random.PRNGKey(1), cfg)
        prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                     cfg.vocab_size)
        scfg = ServeConfig(max_len=32)
        ref = ServingEngine(cfg, params, scfg).generate(prompts, 6)
        for shape in ((1, 1), (2, 1), (2, 2), (1, 4)):
            mesh = make_mesh(shape, ("data", "model"))
            rules = ShardingRules.for_mesh(mesh, fsdp=False)
            eng = ServingEngine(cfg, params, scfg, rules=rules)
            out = eng.generate(prompts, 6)
            assert (np.asarray(out) == np.asarray(ref)).all(), (
                arch, shape, np.asarray(out), np.asarray(ref))
        print(f"DECODE_PARITY_OK {arch}")
""")


SCHEDULER_PARITY = HEADER + textwrap.dedent("""
    # mixed-task continuous batching under a 2x2 mesh: every request's
    # greedy token stream identical to the single-device scheduler
    from dataclasses import replace
    from repro import configs
    from repro.dist.sharding import ShardingRules
    from repro.models import model as M
    from repro.serve import LMBackend, Request, Scheduler, ServeConfig

    cfg = replace(configs.get("kimi_k2_1t_a32b", smoke=True),
                  dtype="float32")   # fp32: see DECODE_PARITY
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (6, 8), dtype=np.int32)

    def serve(rules):
        backend = LMBackend(cfg, params, ServeConfig(max_len=48),
                            rules=rules)
        sched = Scheduler(backend, total_slots=4, quantum=3,
                          num_tasks=backend.num_tasks)
        reqs = [Request(rid=i, task_id=i % 2, prompt=prompts[i],
                        max_new_tokens=5 + (i % 3))
                for i in range(6)]
        done = sched.run(reqs)
        return {r.rid: list(r.tokens) for r in done}

    ref = serve(None)
    mesh = make_mesh((2, 2), ("data", "model"))
    out = serve(ShardingRules.for_mesh(mesh, fsdp=False))
    assert ref == out, (ref, out)
    print("SCHEDULER_PARITY_OK")
""")


VISION_PARITY = HEADER + textwrap.dedent("""
    # expert-parallel M3ViT serving over 4 model shards, two placements:
    #   * ep_mesh (hybrid: dense trunk replicated, ONLY experts sharded —
    #     the M3ViT/UbiMoE co-design placement): BIT-exact, because the
    #     sharded PagedMoE forward is bit-exact and nothing else moved;
    #   * full rules (trunk tensor-parallel too): fp32-close — TP psums
    #     over the sharded MLP hidden legitimately reorder reductions
    from dataclasses import replace
    from repro import configs
    from repro.dist.sharding import ShardingRules
    from repro.models import vit as V
    from repro.serve.vision import M3ViTServer

    cfg = replace(configs.get("m3vit", smoke=True), dtype="float32")
    params = V.init_params(jax.random.PRNGKey(0), cfg)
    imgs = np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (2, 128, 256, 3)), np.float32)
    ref = M3ViTServer(cfg, params, resident_fraction=0.5)
    mesh = make_mesh((1, 4), ("data", "model"))
    hybrid = M3ViTServer(cfg, params, resident_fraction=0.5, ep_mesh=mesh)
    full = M3ViTServer(cfg, params, resident_fraction=0.5,
                       rules=ShardingRules.for_mesh(mesh, fsdp=False))
    for task in ("semseg", "depth"):
        a = ref.infer(imgs, task)
        np.testing.assert_array_equal(a, hybrid.infer(imgs, task),
                                      err_msg=f"{task} ep_mesh")
        np.testing.assert_allclose(a, full.infer(imgs, task),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"{task} full rules")
    print("VISION_PARITY_OK")
""")


ASYNC_SHARDED_PARITY = HEADER + textwrap.dedent("""
    # mesh-2 async paging == PR-5 synchronous paging, token for token:
    # per-shard page-ins overlap the all-to-all dispatch (two-phase
    # submit/fence across shard books) on BOTH the real worker-pool
    # transport and an adversarial virtual-clock schedule, fp32 + int8
    from repro.core import moe as moe_lib
    from repro.ops import policy_named, use_policy
    from repro.quant import quantize_tree
    from repro.serve.expert_cache import PagedMoE
    from repro.serve.transfer import FakeTransferEngine, TransferEngine

    mesh = make_mesh((1, 2), ("data", "model"))
    cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                            num_tasks=2, capacity_factor=2.0, group_size=64,
                            impl="grouped", expert_kind="swiglu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32))
         * 0.5).astype(jnp.float32)
    # staggered per-shard latencies: shard1's copies land LATE relative
    # to shard0's, so fences interleave adversarially across books.
    # Transfer keys carry GLOBAL expert ids (shard s owns [4s, 4s+4)
    # under the static plan)
    sched = {(f"shard{s}", 4 * s + e): 0.25 + 2.0 * s + 0.5 * e
             for s in (0, 1) for e in range(4)}
    for task in (0, 1):
        ref, _ = PagedMoE(params, cfg, resident_fraction=0.5,
                          mesh=mesh)(x, task_id=task)
        for eng in (TransferEngine(timeout_s=60.0),
                    FakeTransferEngine(schedule=sched, wave_s=1.0,
                                       timeout_s=1e9)):
            paged = PagedMoE(params, cfg, resident_fraction=0.5,
                             mesh=mesh, transfer_engine=eng)
            y, _ = paged(x, task_id=task)
            np.testing.assert_array_equal(
                np.asarray(y), np.asarray(ref),
                err_msg=f"task={task} {type(eng).__name__}")
            s = paged.cache.stats()
            assert "stall_s" in s and "overlap_ratio" in s, s
            assert 0.0 <= s["overlap_ratio"] <= 1.0, s
    for bits in (8, 4):
        qparams = quantize_tree(dict(params), bits=bits)
        with use_policy(policy_named("xla_int8")):
            qref, _ = PagedMoE(qparams, cfg, resident_fraction=0.5,
                               mesh=mesh)(x, task_id=0)
            qy, _ = PagedMoE(qparams, cfg, resident_fraction=0.5,
                             mesh=mesh,
                             transfer_engine=FakeTransferEngine(
                                 schedule=sched, wave_s=1.0,
                                 timeout_s=1e9))(x, task_id=0)
        np.testing.assert_array_equal(np.asarray(qy), np.asarray(qref),
                                      err_msg=f"int{bits} async mesh=2")
    print("ASYNC_SHARDED_PARITY_OK")
""")


SHARD_HANG = HEADER + textwrap.dedent("""
    # a shard whose transfer link hangs must raise a LOUD TransferTimeout
    # from the two-phase ensure — never deadlock the serving loop.  The
    # healthy shard's copy still lands (submitted before the hung fence).
    import numpy as _np
    from repro.serve.expert_cache import ShardedExpertCache
    from repro.serve.transfer import FakeTransferEngine, TransferTimeout

    mesh = make_mesh((1, 2), ("data", "model"))
    rng = _np.random.default_rng(0)
    host = {"w": rng.standard_normal((8, 4, 4)).astype(_np.float32)}
    eng = FakeTransferEngine(latency_s=0.1, timeout_s=5.0,
                             schedule={("shard1", 4): None})   # hung link
    cache = ShardedExpertCache(host, 2, mesh, transfer_engine=eng)
    try:
        cache.ensure([0, 4])     # shard0's expert 0 (fine), shard1's 4 (hung)
    except TransferTimeout as e:
        assert "shard1" in str(e) and "hung" in str(e), str(e)
    else:
        raise AssertionError("hung shard did not raise TransferTimeout")
    assert eng.stats.timeouts == 1
    # the healthy shard committed its expert before the hang surfaced
    assert 0 in cache.resident, cache.resident
    print("SHARD_HANG_OK")
""")


def test_paged_moe_sharded_bit_exact():
    """Expert-parallel PagedMoE == apply_moe at mesh 2 and 4 (fp32+bf16)."""
    assert "PAGED_PARITY_OK" in _run(PAGED_PARITY)


def test_async_sharded_token_identical():
    """Mesh-2 async paging (real + adversarial fake transport, fp32/int8/
    int4) emits exactly the synchronous path's values."""
    assert "ASYNC_SHARDED_PARITY_OK" in _run(ASYNC_SHARDED_PARITY)


def test_hung_shard_raises_loud_timeout():
    """A hung shard transfer raises TransferTimeout, not a deadlock."""
    assert "SHARD_HANG_OK" in _run(SHARD_HANG)


def test_paged_moe_sharded_quantized_bit_exact():
    """int8/int4 quantized expert paging stays bit-exact when sharded."""
    assert "PAGED_QUANT_PARITY_OK" in _run(PAGED_QUANT_PARITY)


def test_budget_scales_residency_with_shards():
    """Fixed per-device budget_bytes -> linear resident scaling + higher
    demand hit rate at mesh 4 than mesh 1."""
    assert "BUDGET_SCALING_OK" in _run(BUDGET_SCALING)


def test_greedy_decode_token_identical_across_meshes():
    """ServingEngine under mesh 1/2/4 emits the single-device tokens."""
    out = _run(DECODE_PARITY)
    assert "DECODE_PARITY_OK llama3_2_1b" in out
    assert "DECODE_PARITY_OK kimi_k2_1t_a32b" in out


def test_scheduler_token_identical_at_mesh():
    """Continuous batching at 2x2: per-request streams match 1 device."""
    assert "SCHEDULER_PARITY_OK" in _run(SCHEDULER_PARITY)


def test_vision_server_sharded_matches():
    """M3ViT expert-parallel serving matches the single-device server."""
    assert "VISION_PARITY_OK" in _run(VISION_PARITY)


def test_engine_sharded_noop_mesh_in_process():
    """A (1, 1) mesh in the main process: rules plumb through the engine
    (param placement, state sharding) without changing a token."""
    from repro import configs
    from repro.dist.sharding import ShardingRules
    from repro.models import model as M
    from repro.serve import ServeConfig, ServingEngine

    cfg = configs.get("llama3_2_1b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab_size)
    scfg = ServeConfig(max_len=32)
    ref = ServingEngine(cfg, params, scfg).generate(prompts, 4)
    mesh = make_mesh((1, 1), ("data", "model"))
    out = ServingEngine(cfg, params, scfg,
                        rules=ShardingRules.for_mesh(mesh, fsdp=False)
                        ).generate(prompts, 4)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_stub_embed_table_is_host_side():
    """The feedback embed table caches HOST (numpy) values — an lru_cache
    over device arrays would pin first-call placement and go stale once a
    mesh is active."""
    from repro.serve.engine import _stub_embed_table

    t = _stub_embed_table(64, 16, "float32")
    assert isinstance(t, np.ndarray), type(t)
    assert t.shape == (64, 16)
    # deterministic across calls (same cache entry)
    t2 = _stub_embed_table(64, 16, "float32")
    assert t is t2
