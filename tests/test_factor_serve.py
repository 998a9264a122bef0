"""Factored-expert serving parity: paged == direct, bit for bit.

The contract the whole factored-memory story leans on: pinning the shared
basis and paging only the per-expert delta factors must never change a
single output value —

  * ``PagedMoE`` over factored experts (rank / butterfly, fp32 and int8 /
    int4 delta factors, gelu and swiglu FFNs) is BIT-EXACT with the
    all-resident direct ``apply_moe`` at any residency fraction;
  * the byte budget sizes residency on the PAGED (delta) bytes only — the
    pinned basis is subtracted from the budget, not divided into it — so
    the same ``budget_bytes`` holds several times more factored experts
    resident than dense ones;
  * the guarantee survives expert parallelism: factored paging on a
    2-shard mesh (per-shard delta banks + replicated pinned basis) stays
    bit-exact, run in a subprocess with forced host devices (the same
    pattern as tests/test_serve_dist.py).
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import moe as moe_lib
from repro.factor import factorize_tree
from repro.ops import policy_named, use_policy
from repro.serve.expert_cache import PagedMoE

REPO = __file__.rsplit("/tests/", 1)[0]


def _cfg(expert_kind="gelu", num_experts=8):
    return moe_lib.MoEConfig(
        d_model=32, d_ff=64, num_experts=num_experts, top_k=2, num_tasks=2,
        capacity_factor=2.0, group_size=64, impl="grouped",
        expert_kind=expert_kind)


def _setup(expert_kind, kind, delta_bits, num_experts=8):
    cfg = _cfg(expert_kind, num_experts)
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                              dtype=jnp.float32)
    fparams = factorize_tree(dict(params), kind=kind, rank=4,
                             delta_bits=delta_bits)
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32))
         * 0.5).astype(jnp.float32)
    return cfg, fparams, x


class TestPagedFactoredParity:
    @pytest.mark.parametrize("kind,delta_bits", [
        ("rank", None), ("rank", 8), ("rank", 4),
        ("butterfly", None), ("butterfly", 8)])
    @pytest.mark.parametrize("expert_kind", ["gelu", "swiglu"])
    def test_paged_bitexact_with_direct(self, expert_kind, kind,
                                        delta_bits):
        cfg, fparams, x = _setup(expert_kind, kind, delta_bits)
        with use_policy(policy_named("xla_factored")):
            for task in (0, 1):
                ref, aref = moe_lib.apply_moe(fparams, cfg, x,
                                              task_id=task)
                for frac in (0.25, 1.0):
                    paged = PagedMoE(fparams, cfg,
                                     resident_fraction=frac)
                    y, aux = paged(x, task_id=task)
                    np.testing.assert_array_equal(
                        np.asarray(y), np.asarray(ref),
                        err_msg=f"{expert_kind} {kind} bits={delta_bits} "
                                f"task={task} frac={frac}")
                    assert abs(float(aux) - float(aref)) < 1e-6

    def test_basis_is_pinned_not_paged(self):
        cfg, fparams, x = _setup("gelu", "rank", None)
        paged = PagedMoE(fparams, cfg, resident_fraction=0.25)
        s = paged.cache.stats()
        assert s["pinned_bytes"] > 0
        # the paged unit is the delta, an order smaller than the dense
        # (d_model*d_ff + d_ff*d_model) fp32 expert
        dense = PagedMoE(
            moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                             dtype=jnp.float32),
            cfg, resident_fraction=0.25)
        d = dense.cache.stats()
        assert d["pinned_bytes"] == 0
        assert s["paged_expert_bytes"] < d["paged_expert_bytes"] / 3
        # paging bytes move only deltas: after a forced fill, the bytes
        # paged per expert match the paged (not pinned+paged) unit
        with use_policy(policy_named("xla_factored")):
            paged(x, task_id=0)
        st = paged.cache.stats()
        assert st["bytes_paged"] % s["paged_expert_bytes"] == 0


class TestFactoredBudgetSizing:
    def test_budget_counts_paged_bytes_only(self):
        cfg, fparams, _ = _setup("gelu", "rank", None)
        probe = PagedMoE(fparams, cfg, resident_fraction=1.0)
        per = probe.cache.stats()["paged_expert_bytes"]
        pinned = probe.cache.stats()["pinned_bytes"]
        for n in (3, 5):
            paged = PagedMoE(fparams, cfg,
                             budget_bytes=pinned + n * per)
            assert paged.cache.max_resident == n
        # budget below the pinned floor: clamps to top_k, never crashes
        tiny = PagedMoE(fparams, cfg, budget_bytes=max(0, pinned - 1))
        assert tiny.cache.max_resident == cfg.top_k

    def test_equal_budget_holds_4x_more_factored_experts(self):
        # the satellite acceptance bar, at test scale: same budget_bytes,
        # ≥4× the resident experts once deltas are rank-4 int8
        cfg = _cfg("gelu", num_experts=32)
        params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                                  dtype=jnp.float32)
        fparams = factorize_tree(dict(params), rank=4, delta_bits=8)
        dense_probe = PagedMoE(params, cfg, resident_fraction=1.0)
        dense_per = dense_probe.cache.stats()["paged_expert_bytes"]
        budget = 4 * dense_per
        dense = PagedMoE(params, cfg, budget_bytes=budget)
        fact = PagedMoE(fparams, cfg, budget_bytes=budget)
        assert dense.cache.max_resident == 4
        assert fact.cache.max_resident >= 4 * dense.cache.max_resident


HEADER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import make_mesh
""")


FACTORED_DIST_PARITY = HEADER + textwrap.dedent("""
    from repro.core import moe as moe_lib
    from repro.factor import factorize_tree
    from repro.ops import policy_named, use_policy
    from repro.serve.expert_cache import PagedMoE

    cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                            num_tasks=2, capacity_factor=2.0, group_size=64,
                            impl="grouped", expert_kind="gelu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg,
                              dtype=jnp.float32)
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32))
         * 0.5).astype(jnp.float32)
    for kind, bits in (("rank", None), ("rank", 8), ("butterfly", None)):
        fparams = factorize_tree(dict(params), kind=kind, rank=4,
                                 delta_bits=bits)
        with use_policy(policy_named("xla_factored")):
            ref, _ = moe_lib.apply_moe(fparams, cfg, x, task_id=0)
            y1, _ = PagedMoE(fparams, cfg,
                             resident_fraction=0.5)(x, task_id=0)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(ref),
                                      err_msg=f"{kind} bits={bits} single")
        for m in (2,):
            mesh = make_mesh((1, m), ("data", "model"))
            paged = PagedMoE(fparams, cfg, resident_fraction=0.5,
                             mesh=mesh)
            with use_policy(policy_named("xla_factored")):
                ym, _ = paged(x, task_id=0)
            np.testing.assert_array_equal(
                np.asarray(ym), np.asarray(ref),
                err_msg=f"{kind} bits={bits} mesh={m}")
            s = paged.cache.stats()
            assert s["num_shards"] == m
            assert s["pinned_bytes"] > 0   # basis replicated per device
    print("FACTORED_DIST_PARITY_OK")
""")


def _run(script: str, timeout: int = 600) -> str:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                       cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return r.stdout


class TestFactoredDistributed:
    def test_mesh_parity_bitexact(self):
        assert "FACTORED_DIST_PARITY_OK" in _run(FACTORED_DIST_PARITY)
