"""Technique ③ — accurate low-cost LUT activation (paper §IV-C)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import gelu as G


class TestDeltaTable:
    def test_bounded_unit_interval(self):
        """Paper: 0 <= delta(x) < 1 — only fractional bits need storing."""
        for kind in ("gelu", "silu"):
            t = np.asarray(G.build_delta_table(kind))
            assert (t >= 0.0).all() and (t < 1.0).all()

    def test_even_symmetry(self, rng):
        """Paper Eq. 5–6: delta(-x) == delta(x), so only x>=0 is stored."""
        x = np.abs(rng.normal(size=(1000,)) * 3).astype(np.float32)
        for fn, exact in ((G.lut_gelu, G.exact_gelu),
                          (G.lut_silu, G.exact_silu)):
            dpos = np.asarray(jax.nn.relu(jnp.asarray(x)) - exact(jnp.asarray(x)))
            dneg = np.asarray(jax.nn.relu(jnp.asarray(-x)) - exact(jnp.asarray(-x)))
            np.testing.assert_allclose(dpos, dneg, atol=1e-6)

    def test_truncation_beyond_range(self):
        """|x| > range ⇒ GELU rounds to ReLU, LUT returns ReLU exactly."""
        x = jnp.asarray([9.0, 20.0, -9.0, -20.0], jnp.float32)
        np.testing.assert_array_equal(G.lut_gelu(x), jax.nn.relu(x))

    def test_step_is_power_of_two(self):
        """Index computation must be a bit shift."""
        assert G.LUT_STEP_LOG2 < 0
        step = 2.0 ** G.LUT_STEP_LOG2
        assert step * (2 ** (-G.LUT_STEP_LOG2)) == 1.0


class TestAccuracy:
    @pytest.mark.parametrize("kind", ["gelu", "silu"])
    def test_max_abs_error(self, rng, kind):
        """Dense sweep: LUT error is bounded by half a table step's worth of
        delta variation — ~2e-3 absolute at step 2^-8 (paper: no accuracy
        drop end-to-end, checked in the M3ViT benchmark)."""
        x = jnp.asarray(np.linspace(-10, 10, 200001), jnp.float32)
        lut = G.lut_activation(x, kind=kind)
        exact = G.exact_gelu(x) if kind == "gelu" else G.exact_silu(x)
        err = float(jnp.max(jnp.abs(lut - exact)))
        # nearest-entry lookup at step 2^-8: worst |err| = half-step × max
        # |delta'| (~1.4 for silu) ≈ 2.7e-3; gelu is ~4x tighter
        assert err < 3e-3, err

    def test_better_than_sigmoid_approx(self):
        """Paper Table V: the LUT supersedes the sigmoid approximation
        GELU(x) ~ x*sigmoid(1.702x) because it is strictly more accurate."""
        x = jnp.asarray(np.linspace(-8, 8, 100001), jnp.float32)
        exact = G.exact_gelu(x)
        lut_err = float(jnp.max(jnp.abs(G.lut_gelu(x) - exact)))
        sig_err = float(jnp.max(jnp.abs(x * jax.nn.sigmoid(1.702 * x) - exact)))
        assert lut_err < sig_err / 5

    @settings(max_examples=30, deadline=None)
    @given(st.floats(-50, 50))
    def test_pointwise_property(self, v):
        x = jnp.float32(v)
        got = float(G.lut_gelu(x))
        want = float(G.exact_gelu(x))
        assert abs(got - want) < 2.5e-3


class TestKernelForms:
    """The gather-free, erf-free forms Pallas kernel bodies use."""

    def test_erf_rational_matches_float64_erf(self):
        import math

        x = np.linspace(-6, 6, 200001).astype(np.float32)
        want = np.array([math.erf(float(v)) for v in x])
        got = np.asarray(G.erf_rational(jnp.asarray(x)), np.float64)
        assert np.abs(got - want).max() < 1e-6
        lim = np.asarray(G.erf_rational(
            jnp.asarray([np.inf, -np.inf, np.nan], jnp.float32)))
        assert lim[0] == 1.0 and lim[1] == -1.0 and np.isnan(lim[2])

    # 2048 / 1024 entries: whole table rows; 844: a ragged last row
    @pytest.mark.parametrize("kind, rng_", [("gelu", 8.0), ("silu", 8.0),
                                            ("gelu", 4.0), ("gelu", 3.3)])
    def test_lanes_lookup_equals_gather(self, rng, kind, rng_):
        table = G._cached_table(kind, G.LUT_STEP_LOG2, rng_)
        y = rng.normal(size=(16, 256)).astype(np.float32) * 4
        y[0, :4] = [np.inf, -np.inf, np.nan, 0.0]
        y[1, :2] = [rng_, -rng_ * 2]                 # range edge, outside
        want = G.lut_correction(jnp.asarray(y), jnp.asarray(table),
                                G.LUT_STEP_LOG2)
        got = G.lut_correction_lanes(
            jnp.asarray(y), jnp.asarray(G.lut_table_lanes(table)),
            G.LUT_STEP_LOG2, table.shape[0])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestDispatch:
    def test_get_activation(self):
        x = jnp.asarray([-1.0, 0.0, 2.0], jnp.float32)
        assert G.get_activation("relu")(x)[0] == 0.0
        np.testing.assert_allclose(G.get_activation("gelu", False)(x),
                                   G.exact_gelu(x))
        np.testing.assert_allclose(G.get_activation(None)(x), x)
        with pytest.raises(ValueError):
            G.get_activation("swish7")
