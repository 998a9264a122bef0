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


class TestClosedForm:
    """``lut_activation`` evaluates the table's entry from its closed form
    instead of gathering it; the table (``lut_correction``) is the oracle."""

    @pytest.mark.parametrize("kind", ["gelu", "silu"])
    @pytest.mark.parametrize("rng_", [8.0, 4.0, 3.3])
    def test_closed_form_equals_table_entries(self, kind, rng_):
        table = G._cached_table(kind, G.LUT_STEP_LOG2, rng_)
        q = np.arange(table.shape[0], dtype=np.float32) * np.float32(
            2.0**G.LUT_STEP_LOG2)
        got = np.asarray(G.delta_closed_form(jnp.asarray(q), kind))
        assert np.abs(got - table).max() <= 2e-7

    @staticmethod
    def _edges(rng_):
        n = int(rng_ / 2.0**G.LUT_STEP_LOG2)
        top = np.float32(n * 2.0**G.LUT_STEP_LOG2)   # first |y| off the table
        past = np.nextafter(top, np.float32(np.inf))
        below = np.nextafter(top, np.float32(0))
        half = np.float32((n - 0.5) * 2.0**G.LUT_STEP_LOG2)  # clamped to n-1
        e = [0.0, -0.0, rng_, -rng_, half, -half, below, -below, top, -top,
             past, -past, 3e38, -3e38, np.inf, -np.inf, np.nan]
        return np.asarray(e, np.float32)

    @pytest.mark.parametrize("kind", ["gelu", "silu"])
    @pytest.mark.parametrize("rng_", [8.0, 3.3])
    def test_matches_table_oracle(self, rng, kind, rng_):
        table = jnp.asarray(G._cached_table(kind, G.LUT_STEP_LOG2, rng_))
        edges = self._edges(rng_)
        x = np.concatenate([
            (rng.normal(size=(1 << 16,)) * 3).astype(np.float32), edges])
        got = np.asarray(G.lut_activation(jnp.asarray(x), kind, rng=rng_))
        want = np.asarray(G.lut_correction(jnp.asarray(x), table,
                                           G.LUT_STEP_LOG2))
        fin = np.isfinite(want)
        np.testing.assert_array_equal(got[~fin], want[~fin])
        assert np.isfinite(got[fin]).all()
        diff = np.abs(got[fin] - want[fin])
        # δ agrees to ~1e-7 (above), so ReLU(y) − δ agrees to 2.5e-7; where
        # the output's own float32 step is larger (SiLU, |y| in [4, 8),
        # where δ is still ~0.07) a δ apart in its last bit can round the
        # output one step the other way
        bound = np.maximum(2.5e-7, np.spacing(np.abs(want[fin])))
        assert (diff <= bound).all(), diff.max()
        if kind == "gelu":
            assert diff.max() <= 2.5e-7, diff.max()
        # off the table: bit-exact
        off = np.abs(edges) >= rng_
        np.testing.assert_array_equal(got[-edges.size:][off],
                                      want[-edges.size:][off])

    @pytest.mark.parametrize("kind", ["gelu", "silu"])
    def test_jitted_equals_op_by_op(self, rng, kind):
        """Paged and direct MoE paths are held bit-exact with each other,
        one of them jitted and one run op by op: the activation must round
        the same way fused (where XLA may contract a multiply into the
        subtraction) and unfused."""
        y = jnp.asarray((rng.normal(size=(2, 2, 36, 64)) * 3)
                        .astype(np.float32))
        eager = np.asarray(G.lut_activation(y, kind))
        fused = np.asarray(jax.jit(lambda v: G.lut_activation(v, kind))(y))
        np.testing.assert_array_equal(fused, eager)

    @pytest.mark.parametrize("kind", ["gelu", "silu"])
    def test_lowers_without_gather(self, kind):
        """At the wave's hidden buffer (4 groups × 8 slots × 68 rows × f)."""
        x = jax.ShapeDtypeStruct((4, 8, 68, 768), jnp.float32)
        hlo = jax.jit(lambda v: G.lut_activation(v, kind)).lower(x).as_text()
        assert "gather" not in hlo

    def test_m3vit_dense_block_dispatches_lut(self):
        """The default policy's ``activation`` dispatch resolves to ``lut``
        (the closed form) in an M³ViT dense block, with no fallback."""
        from repro import configs, ops
        from repro.models import vit as V
        from repro.serve.vision import M3ViTServer

        cfg = configs.get("m3vit", smoke=True)
        assert cfg.policy is None      # the default policy
        params = V.init_params(jax.random.PRNGKey(0), cfg)
        srv = M3ViTServer(cfg, params, resident_fraction=0.5)
        dense = srv.kinds.index("attn_mlp")
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                              jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None], (2, 16))
        ops.reset_dispatch_report()
        y = srv._dense(srv.layer_params[dense], x, pos)
        rep = ops.dispatch_report()["activation"]
        assert rep["hits"].get("lut", 0) >= 1
        assert set(rep["hits"]) == {"lut"} and rep["fallbacks"] == []
        assert np.isfinite(np.asarray(y, np.float32)).all()


class TestDispatch:
    def test_get_activation(self):
        x = jnp.asarray([-1.0, 0.0, 2.0], jnp.float32)
        assert G.get_activation("relu")(x)[0] == 0.0
        np.testing.assert_allclose(G.get_activation("gelu", False)(x),
                                   G.exact_gelu(x))
        np.testing.assert_allclose(G.get_activation(None)(x), x)
        with pytest.raises(ValueError):
            G.get_activation("swish7")
