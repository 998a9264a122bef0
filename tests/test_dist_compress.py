"""Int8 gradient compression with error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import compress as C
from repro.dist import make_mesh


class TestQuantize:
    def test_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.normal(size=(5000,)) * 3, jnp.float32)
        q, scale = C.quantize_int8(x)
        y = C.dequantize_int8(q, scale, x.shape)
        # error bounded by half a quantization step per chunk
        err = np.abs(np.asarray(x - y))
        bound = np.repeat(np.asarray(scale)[:, 0] * 0.5 + 1e-9, C.CHUNK)[:5000]
        assert (err <= bound + 1e-6).all()

    def test_exact_zero(self):
        x = jnp.zeros((100,))
        q, s = C.quantize_int8(x)
        np.testing.assert_array_equal(np.asarray(C.dequantize_int8(q, s, x.shape)), 0)

    def test_payload_shrinks_4x(self, rng):
        x = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
        assert C.compressed_bytes(x) < x.size * 4 / 3.5


class TestErrorFeedback:
    def test_ef_converges_like_uncompressed(self, rng):
        """SGD on a quadratic with compressed grads + EF reaches the same
        optimum (the EF carry makes compression unbiased over time)."""
        target = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)

        def grad(w):
            return 2 * (w - target) / target.size

        def run(compressed):
            w = jnp.zeros_like(target)
            err = jnp.zeros_like(target)
            for _ in range(300):
                g = grad(w)
                if compressed:
                    gf = g + err
                    q, s = C.quantize_int8(gf)
                    deq = C.dequantize_int8(q, s, gf.shape)
                    err = gf - deq
                    g = deq
                w = w - 20.0 * g
            return float(jnp.mean((w - target) ** 2))

        l_plain = run(False)
        l_comp = run(True)
        # EF-SGD converges to a noise floor ∝ lr × quant step; demand ≥99%
        # of the initial loss (~1.0) recovered and within 100× of exact SGD
        assert l_comp < 0.01
        assert l_comp < max(l_plain * 100, 0.01)

    def test_compressed_psum_single_axis(self, rng):
        """compressed_psum inside shard_map on a 1-device mesh: identity
        reduce, EF state returned."""
        mesh = make_mesh((1,), ("data",))
        g = {"w": jnp.asarray(rng.normal(size=(2048,)), jnp.float32)}
        e = C.init_error_state(g)

        from jax.sharding import PartitionSpec as P

        def body(gg, ee):
            return C.compressed_psum(gg, ee, axes=("data",))

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()), check_vma=False)
        out_g, out_e = fn(g, e)
        # one device: psum is identity; dequantized ~= original within step
        err = float(jnp.abs(out_g["w"] - g["w"]).max())
        assert err < float(jnp.abs(g["w"]).max()) / 100
        np.testing.assert_allclose(np.asarray(out_e["w"]),
                                   np.asarray(g["w"] - out_g["w"]),
                                   atol=1e-6)


class TestStackedAllReduce:
    def test_mean_over_shards(self, rng):
        """Stacked wrapper: leading axis = DP shards (1 here), result is the
        shard mean with EF carried per shard."""
        mesh = make_mesh((1,), ("data",))
        g = {"w": jnp.asarray(rng.normal(size=(1, 512)), jnp.float32)}
        e = {"w": jnp.zeros((1, 512), jnp.float32)}
        out_g, out_e = C.compressed_allreduce_stacked(g, e, mesh)
        assert out_g["w"].shape == (1, 512)
        err = float(jnp.abs(out_g["w"] - g["w"]).max())
        assert err < 0.05
