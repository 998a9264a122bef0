"""Rule edge cases the seed suite skips: divisibility trimming on a
mesh with >1-sized axes (a fake mesh — ``_trim_spec`` only reads
``mesh.shape``/``mesh.axis_names``, so no forced-host-device subprocess
is needed), ``constrain`` under nested ``use_rules`` contexts, and
``opt_state_shardings`` on non-factored (plain ``v``) state."""

import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import make_mesh
from repro.dist.sharding import (
    ShardingRules, _trim_spec, constrain, current_rules,
    opt_state_shardings, use_rules)


def fake_mesh(**sizes):
    return types.SimpleNamespace(shape=dict(sizes),
                                 axis_names=tuple(sizes))


@pytest.mark.parametrize("shape, axes", [
    ((1,), ("data",)),
    ((1, 1), ("data", "model")),
    ((1, 1, 1), ("pod", "data", "model")),
])
def test_make_mesh_axes_are_auto(shape, axes):
    """Explicit axes (jax.make_mesh's default) make the rules' sharding
    constraints and the serving gathers raise ShardingTypeError."""
    mesh = make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert dict(mesh.shape) == dict(zip(axes, shape))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * len(axes)


class TestTrimNonDivisible:
    """On the (1, 1) test mesh every dim divides; these exercise the drop
    path with real >1 axis sizes."""

    MESH = fake_mesh(data=2, model=4)

    def test_divisible_kept(self):
        assert _trim_spec((6, 8), P("data", "model"), self.MESH) \
            == P("data", "model")

    def test_non_divisible_dim_dropped(self):
        # 5 % 2 != 0: the data axis falls off; the model axis survives
        assert _trim_spec((5, 8), P("data", "model"), self.MESH) \
            == P(None, "model")
        # 8 % 2 == 0 but 9 % 4 != 0: only the model axis falls off
        assert _trim_spec((8, 9), P("data", "model"), self.MESH) \
            == P("data", None)

    def test_pad_left_then_trim(self):
        # scanned stack: leading period dim padded None, then trimming
        # still applies to the payload dims
        assert _trim_spec((3, 5, 8), P("data", "model"), self.MESH,
                          pad_left=True) == P(None, None, "model")

    def test_tuple_entry_product_divisibility(self):
        mesh = fake_mesh(pod=2, data=2, model=4)
        # 4 % (2*2) == 0: the whole batch tuple survives
        assert _trim_spec((4, 8), P(("pod", "data"), "model"), mesh) \
            == P(("pod", "data"), "model")
        # 6 % 4 != 0: the whole entry is dropped, not partially kept
        assert _trim_spec((6, 8), P(("pod", "data"), "model"), mesh) \
            == P(None, "model")

    def test_axis_missing_from_mesh_filtered(self):
        # single-pod mesh: "pod" is filtered out of the tuple entry and
        # divisibility is checked against the survivors only
        assert _trim_spec((4, 8), P(("pod", "data"), "model"), self.MESH) \
            == P(("data",), "model")


class TestNestedUseRules:
    def test_inner_context_shadows_and_restores(self, mesh):
        r1 = ShardingRules.for_mesh(mesh)
        r2 = ShardingRules.for_mesh(mesh, seq_shard=True)
        assert current_rules() is None
        with use_rules(r1):
            assert current_rules() is r1
            x = constrain(jnp.ones((2, 4, 8)), "btd")
            assert x.shape == (2, 4, 8)
            with use_rules(r2):
                assert current_rules() is r2
                y = constrain(jnp.ones((2, 4, 8)), "btd")
                assert y.shape == (2, 4, 8)
            assert current_rules() is r1
        assert current_rules() is None

    def test_nested_none_disables_constrain(self, mesh):
        with use_rules(ShardingRules.for_mesh(mesh)):
            with use_rules(None):
                x = jnp.ones((3,))
                assert constrain(x, "btd") is x
            # outer rules active again
            assert current_rules() is not None

    def test_exception_still_restores(self, mesh):
        with pytest.raises(RuntimeError):
            with use_rules(ShardingRules.for_mesh(mesh)):
                raise RuntimeError("boom")
        assert current_rules() is None


class TestOptStateNonFactored:
    def test_plain_v_follows_param(self, mesh):
        from repro.optim import OptConfig, adamw_init

        rules = ShardingRules.for_mesh(mesh)
        params = {"mlp": {"w1": jnp.zeros((256, 512), jnp.float32)},
                  "ln1": {"scale": jnp.zeros((256,), jnp.float32)}}
        cfg = OptConfig(factored=False)
        opt_shapes = jax.eval_shape(lambda: adamw_init(params, cfg))
        sh = opt_state_shardings(opt_shapes, params, rules)
        ema = sh["ema"]["mlp"]["w1"]
        assert "vr" not in ema and "vc" not in ema
        assert ema["m"].spec == P("data", "model")
        assert ema["v"].spec == P("data", "model")
        # norm scale: replicated, v mirrors it
        for s in sh["ema"]["ln1"]["scale"].values():
            assert all(ax is None for ax in s.spec)
        assert sh["step"].spec == P()

    def test_small_matrix_unfactored_even_when_factoring_on(self, mesh):
        from repro.optim import OptConfig, adamw_init

        rules = ShardingRules.for_mesh(mesh)
        params = {"mlp": {"w1": jnp.zeros((64, 64), jnp.float32)}}
        cfg = OptConfig(factored=True, factored_min_size=128)
        opt_shapes = jax.eval_shape(lambda: adamw_init(params, cfg))
        sh = opt_state_shardings(opt_shapes, params, rules)
        ema = sh["ema"]["mlp"]["w1"]
        assert "v" in ema and "vr" not in ema
        assert ema["v"].spec == P("data", "model")
