"""Expert-parallel (shard_map) MoE path: ep_local == grouped, single- and
multi-device.  The multi-device case runs in a subprocess with 8 forced
host devices so the main test session keeps seeing 1 device."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.core import moe as M
from repro.dist import make_mesh
from repro.dist.sharding import ShardingRules, use_rules


def test_ep_local_equals_grouped_single_device(rng):
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = M.MoEConfig(d_model=32, d_ff=64, num_experts=4, top_k=2,
                      capacity_factor=4.0, group_size=64, impl="ep_local",
                      expert_kind="gelu")
    params = M.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 32, 32)), jnp.float32)
    with use_rules(ShardingRules.for_mesh(mesh)):
        y1, a1 = jax.jit(lambda p, x: M.apply_moe(p, cfg, x))(params, x)
    y2, a2 = M.apply_moe(params, replace(cfg, impl="grouped"), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)
    assert float(a1) == pytest.approx(float(a2), rel=1e-5)


def test_ep_local_no_mesh_falls_back(rng):
    cfg = M.MoEConfig(d_model=16, d_ff=32, num_experts=4, top_k=1,
                      capacity_factor=4.0, impl="ep_local",
                      expert_kind="gelu")
    params = M.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 8, 16)), jnp.float32)
    y, _ = M.apply_moe(params, cfg, x)          # no rules context
    y2, _ = M.apply_moe(params, replace(cfg, impl="grouped"), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-6)


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import make_mesh
    from dataclasses import replace
    from repro.core import moe as M
    from repro.dist.sharding import ShardingRules, use_rules

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    cfg = M.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                      capacity_factor=4.0, group_size=64, impl="ep_local",
                      expert_kind="swiglu")
    params = M.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 32, 32)), jnp.float32)
    with use_rules(ShardingRules.for_mesh(mesh)):
        y1, a1 = jax.jit(lambda p, x: M.apply_moe(p, cfg, x))(params, x)
    y2, a2 = M.apply_moe(params, replace(cfg, impl="grouped"), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)
    assert abs(float(a1) - float(a2)) < 1e-5
    # gradients flow through the shard_map path
    with use_rules(ShardingRules.for_mesh(mesh)):
        g = jax.jit(jax.grad(
            lambda p, x: M.apply_moe(p, cfg, x)[0].sum()))(params, x)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
    assert float(jnp.abs(g["wg"]).max()) > 0
    print("MULTI_DEVICE_EP_OK")
""")


def test_ep_local_multi_device_subprocess():
    """2×4 mesh (8 forced host devices): ep_local == grouped, grads flow."""
    r = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                       cwd=__file__.rsplit("/tests/", 1)[0])
    assert "MULTI_DEVICE_EP_OK" in r.stdout, r.stderr[-2000:]
