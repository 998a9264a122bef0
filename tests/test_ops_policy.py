"""The repro.ops compute-policy API: scoping, capability-checked dispatch
with loud fallbacks, schedule resolution, and cross-impl agreement.

The allclose sweeps deliberately use *odd* shapes — prime sequence lengths,
head/feature dims that are not multiples of 128 — so every impl's padding
and masking paths are exercised, not just the MXU-aligned happy path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.core import attention as A
from repro.kernels import ref


def mkqkv(rng, b, hq, hkv, sq, skv, d, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    return q, k, v


# ================================================================== policy


class TestPolicyScoping:
    def test_default_outside_any_scope(self):
        assert ops.current_policy() == ops.DEFAULT_POLICY

    def test_enter_exit_restores_prior(self):
        p1 = ops.policy_named("xla")
        p2 = ops.policy_named("pallas")
        with ops.use_policy(p1):
            assert ops.current_policy() is p1
            with ops.use_policy(p2):
                assert ops.current_policy() is p2
            assert ops.current_policy() is p1
        assert ops.current_policy() == ops.DEFAULT_POLICY

    def test_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with ops.use_policy(ops.policy_named("ref")):
                raise RuntimeError("boom")
        assert ops.current_policy() == ops.DEFAULT_POLICY

    def test_none_is_passthrough(self):
        p = ops.policy_named("pallas")
        with ops.use_policy(p):
            with ops.use_policy(None):
                assert ops.current_policy() is p

    def test_kwargs_derive_from_current(self):
        with ops.use_policy(ops.policy_named("xla")):
            with ops.use_policy(attention="pallas"):
                cur = ops.current_policy()
                assert cur.impl_for("attention") == "pallas"
                assert cur.impl_for("linear") == "xla"  # inherited

    def test_per_op_override_beats_blanket_default(self):
        p = ops.ComputePolicy(default_impl="pallas",
                              impls=(("attention", "blocked"),))
        assert p.impl_for("attention") == "blocked"
        assert p.impl_for("linear") == "pallas"

    def test_policy_is_hashable_and_frozen(self):
        p = ops.policy_named("blocked").with_tiles("attention", block_k=64)
        hash(p)
        with pytest.raises(Exception):
            p.default_impl = "xla"

    def test_with_tiles_merges(self):
        p = ops.ComputePolicy().with_tiles("attention", block_k=64)
        p = p.with_tiles("attention", block_q=32)
        assert p.tile_for("attention") == {"block_k": 64, "block_q": 32}
        assert p.tile_for("linear") == {}


class TestScheduleTable:
    @pytest.mark.parametrize("backend", ["interpret", "tpu"])
    def test_shipped_table_covers_every_pallas_impl(self, backend):
        # each backend section stands alone (a TPU never borrows the
        # interpreter's tiles), so each must cover every kernel impl
        for op, impls in ops.capability_matrix().items():
            for impl in (n for n in impls if n.startswith("pallas")):
                blocks = ops.schedule_for(op, impl, {}, backend=backend)
                assert blocks, f"no {backend} schedule entry for {op}.{impl}"
                assert all(isinstance(v, int) for v in blocks.values())

    def test_buckets_scale_blocks_with_shape(self):
        small = ops.schedule_for("attention", "blocked", {"skv": 64},
                                 backend="interpret")
        large = ops.schedule_for("attention", "blocked", {"skv": 4096},
                                 backend="interpret")
        assert small["block_k"] < large["block_k"]

    def test_policy_tile_override_beats_table(self, rng):
        """A pinned block size must not change the math (and must win)."""
        q, k, v = mkqkv(rng, 1, 2, 2, 37, 101, 24)
        base = A.attention(q, k, v)
        with ops.use_policy(ops.ComputePolicy(
                tiles=(("attention", (("block_k", 7),)),))):
            pinned = A.attention(q, k, v)
        np.testing.assert_allclose(np.asarray(base), np.asarray(pinned),
                                   atol=3e-5, rtol=3e-5)


# ================================================== dispatch accounting


class TestDispatchReport:
    def setup_method(self):
        ops.reset_dispatch_report()

    def test_hit_recorded_for_requested_impl(self, rng):
        q, k, v = mkqkv(rng, 1, 2, 2, 16, 16, 8)
        with ops.use_policy(attention="xla"):
            A.attention(q, k, v)
        rep = ops.dispatch_report()["attention"]
        assert rep["hits"].get("xla", 0) >= 1
        assert not rep["fallbacks"]

    def test_traced_q_offset_falls_back_loudly(self, rng):
        """Chunked prefill traces the chunk offset; the kernel impl must be
        rejected with a reason, not silently ignored (old behaviour)."""
        q, k, v = mkqkv(rng, 1, 2, 2, 8, 24, 16)

        def f(q, k, v, off):
            return A.attention(q, k, v, q_offset=off)

        with ops.use_policy(attention="pallas"):
            out = jax.jit(f)(q, k, v, jnp.int32(16))
        want = ref.ref_attention(q, k, v, q_offset=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        rep = ops.dispatch_report()["attention"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "pallas"]
        assert fb, f"expected a recorded fallback, got {rep}"
        assert fb[0]["used"] == "blocked"
        assert any("q_offset" in r for r in fb[0]["reasons"])

    def test_decode_vector_cache_len_falls_back_loudly(self, rng):
        """Continuous batching decodes at per-slot positions (traced
        vector); the pallas decode impl rejects it with a reason."""
        b, hkv, smax, d = 2, 2, 32, 16
        q = jnp.asarray(rng.normal(size=(b, 4, 1, d)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)

        def f(q, kc, vc, cl):
            return A.decode_attention(q, kc, vc, cl)

        with ops.use_policy(attention_decode="pallas"):
            jax.jit(f)(q, kc, vc, jnp.asarray([5, 9], jnp.int32))
        rep = ops.dispatch_report()["attention_decode"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "pallas"]
        assert fb and fb[0]["used"] == "xla"
        assert any("traced" in r for r in fb[0]["reasons"])

    def test_moe_gemm_without_group_sizes_falls_back(self, rng):
        buf = jnp.asarray(rng.normal(size=(3, 8, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, 16, 32)), jnp.float32)
        with ops.use_policy(moe_grouped_gemm="pallas"):
            ops.dispatch("moe_grouped_gemm", buf, w, None)
        rep = ops.dispatch_report()["moe_grouped_gemm"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "pallas"]
        assert fb and fb[0]["used"] == "xla"
        assert any("group_sizes" in r for r in fb[0]["reasons"])

    def test_activation_relu_rejects_lut(self, rng):
        x = jnp.asarray(rng.normal(size=(33,)), jnp.float32)
        with ops.use_policy(activation="lut"):
            y = ops.apply_activation(x, "relu")
        np.testing.assert_allclose(np.asarray(y),
                                   np.maximum(np.asarray(x), 0.0))
        rep = ops.dispatch_report()["activation"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "lut"]
        assert fb and fb[0]["used"] == "xla"

    def test_every_request_accounted(self, rng):
        """requests == hits + fallbacks per op: nothing is dropped on the
        floor (the ledger invariant behind 'no silent fallbacks')."""
        q, k, v = mkqkv(rng, 1, 2, 2, 16, 16, 8)
        with ops.use_policy(ops.policy_named("pallas")):
            A.attention(q, k, v)
            A.attention(q, k, v, window=4)
        x = jnp.asarray(rng.normal(size=(7, 33)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(33, 19)), jnp.float32)
        with ops.use_policy(linear="pallas"):
            from repro.core.unified_linear import unified_linear

            unified_linear(x, w, activation="gelu")
        for op, entry in ops.dispatch_report().items():
            hits = sum(entry["hits"].values())
            fbs = sum(f["count"] for f in entry["fallbacks"])
            assert hits + fbs == entry["requests"], (op, entry)

    def test_unknown_op_raises(self):
        with pytest.raises(KeyError):
            ops.dispatch("conv3d", jnp.zeros((2, 2)))

    def test_unregistered_impl_name_is_reasoned_fallback(self, rng):
        """A typo'd impl (or a blanket preset naming an impl some op lacks)
        must surface as a fallback with a reason, never a silent filter."""
        q, k, v = mkqkv(rng, 1, 2, 2, 8, 8, 8)
        with ops.use_policy(attention="palas"):     # typo
            A.attention(q, k, v)
        rep = ops.dispatch_report()["attention"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "palas"]
        assert fb and fb[0]["used"] == "blocked"
        assert any("not a registered impl" in r for r in fb[0]["reasons"])

    def test_lut_range_policy_consistent_across_impls(self, rng):
        """A non-default LUT range must reach every impl's table build —
        lut, the pallas kernels, and the ref oracle agree."""
        from repro.core.unified_linear import unified_linear

        x = jnp.asarray(rng.normal(size=(16, 24)) * 2, jnp.float32)
        w = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
        narrow = ops.ComputePolicy(lut_range=4.0)
        outs = {}
        for impl in ("xla", "pallas", "ref"):   # xla's epilogue uses 'lut'
            with ops.use_policy(narrow.with_impls(linear=impl)):
                outs[impl] = np.asarray(
                    unified_linear(x, w, activation="gelu"))
        np.testing.assert_allclose(outs["xla"], outs["pallas"], atol=1e-6)
        np.testing.assert_allclose(outs["xla"], outs["ref"], atol=1e-6)
        acts = {}
        for impl in ("lut", "pallas"):
            with ops.use_policy(narrow.with_impls(activation=impl)):
                acts[impl] = np.asarray(ops.apply_activation(x, "silu"))
        np.testing.assert_allclose(acts["lut"], acts["pallas"], atol=1e-6)


# ============================================ attention parity (satellite)


class TestAttentionImplParity:
    """window + q_offset + non-causal combinations must hit the impl the
    policy names (no hidden rerouting) and agree with the ref.py oracle."""

    @pytest.mark.parametrize("impl", ["xla", "blocked", "pallas"])
    @pytest.mark.parametrize("causal,window,q_offset", [
        (True, None, 0),
        (False, None, 0),
        (True, 16, 0),
        (False, 16, 0),       # pure sliding window, no causal frontier
        (True, None, 32),     # chunked-prefill offset
        (True, 16, 32),
        (False, 16, 32),      # all three at once
    ])
    def test_vs_ref_oracle(self, rng, impl, causal, window, q_offset):
        ops.reset_dispatch_report()
        q, k, v = mkqkv(rng, 1, 4, 2, 24, 72, 32)
        with ops.use_policy(attention=impl):
            got = A.attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
        want = ref.ref_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        rep = ops.dispatch_report()["attention"]
        assert rep["hits"].get(impl, 0) >= 1, \
            f"policy named {impl} but dispatch fell back: {rep}"
        assert not rep["fallbacks"]


# ========================================== cross-impl allclose sweeps


ODD_ATTN_SHAPES = [
    (1, 4, 2, 37, 101, 24),    # prime seq lens, d % 128 != 0
    (2, 3, 3, 13, 29, 40),     # MHA, tiny primes
    (1, 8, 1, 61, 61, 48),     # MQA, prime square
]


def _fp_impls(op):
    """Registered impls that serve fp operands — the quantized impls
    require QTensor inputs and have their own parity sweep below."""
    return [n for n in ops.registered(op) if not n.startswith("xla_int")]


class TestCrossImplAgreement:
    """Property-style sweep: all registered impls of each op agree on odd
    shapes (the acceptance-criteria invariant behind the kernel matrix)."""

    @pytest.mark.parametrize("shape", ODD_ATTN_SHAPES)
    def test_attention(self, rng, shape):
        q, k, v = mkqkv(rng, *shape)
        outs = {}
        for impl in _fp_impls("attention"):
            with ops.use_policy(attention=impl):
                outs[impl] = np.asarray(A.attention(q, k, v, causal=True))
        base = outs.pop("ref")
        for impl, out in outs.items():
            np.testing.assert_allclose(out, base, atol=3e-5, rtol=3e-5,
                                       err_msg=f"attention impl {impl}")

    @pytest.mark.parametrize("window", [None, 8])
    def test_attention_decode(self, rng, window):
        b, hq, hkv, smax, d = 2, 4, 2, 37, 24
        length = 29                      # uniform => pallas-capable
        q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        cl = jnp.full((b,), length, jnp.int32)
        outs = {}
        for impl in _fp_impls("attention_decode"):
            ops.reset_dispatch_report()
            with ops.use_policy(attention_decode=impl):
                outs[impl] = np.asarray(
                    A.decode_attention(q, kc, vc, cl, window=window))
            rep = ops.dispatch_report()["attention_decode"]
            assert rep["hits"].get(impl, 0) >= 1, (impl, rep)
        base = outs.pop("ref")
        for impl, out in outs.items():
            np.testing.assert_allclose(out, base, atol=3e-5, rtol=3e-5,
                                       err_msg=f"decode impl {impl}")

    @pytest.mark.parametrize("mnk", [(7, 19, 33), (37, 41, 29),
                                     (1, 257, 13)])
    @pytest.mark.parametrize("act", [None, "gelu", "silu"])
    def test_linear(self, rng, mnk, act):
        from repro.core.unified_linear import unified_linear

        m, n, k = mnk
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        outs = {}
        for impl in _fp_impls("linear"):
            with ops.use_policy(linear=impl):
                outs[impl] = np.asarray(
                    unified_linear(x, w, b, activation=act))
        base = outs.pop("ref")
        # LUT epilogues may flip one 2^-8 bucket on reassociated sums
        tol = 3e-3 if act else 3e-5
        for impl, out in outs.items():
            np.testing.assert_allclose(out, base, atol=tol, rtol=tol,
                                       err_msg=f"linear impl {impl}")

    def test_linear_leading_dims_hit_kernel(self, rng):
        """The old silent ndim!=2 kernel bypass is gone: 3-D inputs flatten
        into the kernel and the dispatch records a pallas HIT."""
        from repro.core.unified_linear import unified_linear

        ops.reset_dispatch_report()
        x = jnp.asarray(rng.normal(size=(2, 7, 33)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(33, 19)), jnp.float32)
        with ops.use_policy(linear="pallas"):
            got = unified_linear(x, w)
        want = ref.ref_linear(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        rep = ops.dispatch_report()["linear"]
        assert rep["hits"].get("pallas", 0) == 1 and not rep["fallbacks"]

    def test_linear_accum_out_hits_kernel(self, rng):
        """accum_out no longer drops the kernel request: the GEMM runs
        through the policy impl, the weighted accumulate is an epilogue."""
        from repro.core.unified_linear import unified_linear

        ops.reset_dispatch_report()
        x = jnp.asarray(rng.normal(size=(10, 24)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
        idx = jnp.asarray([1, 3, 7], jnp.int32)
        wts = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)
        out0 = jnp.zeros((10, 16), jnp.float32)
        with ops.use_policy(linear="pallas"):
            got = unified_linear(x, w, token_index=idx, accum_out=out0,
                                 accum_weight=wts)
        rows = np.asarray(x)[np.asarray(idx)] @ np.asarray(w)
        want = np.zeros((10, 16), np.float32)
        want[np.asarray(idx)] += rows * np.asarray(wts)[:, None]
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        rep = ops.dispatch_report()["linear"]
        assert rep["hits"].get("pallas", 0) == 1 and not rep["fallbacks"]

    @pytest.mark.parametrize("ecdf", [(3, 5, 33, 41), (5, 13, 24, 19)])
    def test_moe_grouped_gemm(self, rng, ecdf):
        e, c, d, f = ecdf
        buf = jnp.asarray(rng.normal(size=(e, c, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        sizes = jnp.asarray(rng.integers(1, c + 1, size=(e,)), jnp.int32)
        outs = {}
        for impl in _fp_impls("moe_grouped_gemm"):
            with ops.use_policy(moe_grouped_gemm=impl):
                outs[impl] = np.asarray(
                    ops.dispatch("moe_grouped_gemm", buf, w, sizes))
        base = outs.pop("ref")
        for impl, out in outs.items():
            np.testing.assert_allclose(out, base, atol=3e-5, rtol=3e-5,
                                       err_msg=f"moe_grouped_gemm impl {impl}")

    @pytest.mark.parametrize("kind", ["gelu", "silu"])
    @pytest.mark.parametrize("n", [5, 127, 1009])
    def test_activation(self, rng, kind, n):
        x = jnp.asarray(rng.normal(size=(n,)) * 4, jnp.float32)
        outs = {}
        for impl in ops.registered("activation"):
            with ops.use_policy(activation=impl):
                outs[impl] = np.asarray(ops.apply_activation(x, kind))
        # lut and pallas share the table => tight; exact differs by the
        # LUT quantization bound (paper: max |err| < 2.5e-3)
        np.testing.assert_allclose(outs["pallas"], outs["lut"], atol=1e-6)
        np.testing.assert_allclose(outs["xla"], outs["lut"], atol=3e-3)


# ==================================== quantized-impl parity (satellite)


class TestQuantizedImplParity:
    """int8/int4 impls vs the ref oracles on dequantized weights, at the
    same odd/prime shapes as the fp sweeps, with dispatch-report HIT
    assertions — a silent fp fallback fails the test."""

    @pytest.mark.parametrize("mnk", [(7, 19, 33), (37, 41, 29),
                                     (1, 257, 13)])
    @pytest.mark.parametrize("bits", [8, 4])
    def test_linear(self, rng, mnk, bits):
        from repro.core.unified_linear import unified_linear
        from repro.quant import dequantize, quantize

        m, n, k = mnk
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        qw = quantize(w, bits, group_size=8)
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("xla_int8")):
            got = np.asarray(unified_linear(x, qw, b, activation="gelu"))
        rep = ops.dispatch_report()["linear"]
        assert rep["hits"].get("xla_int8", 0) >= 1 and not rep["fallbacks"]
        # the int8 epilogue dispatches the default LUT activation — give
        # the oracle the same LUT so the GEMM parity is tight
        want = np.asarray(ref.ref_linear(
            x, dequantize(qw, jnp.float32), b, activation="gelu",
            use_lut=True))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
        cos = (got * want).sum() / np.sqrt(
            (got * got).sum() * (want * want).sum())
        assert cos >= 0.999999

    @pytest.mark.parametrize("ecdf", [(3, 5, 33, 41), (5, 13, 24, 19)])
    @pytest.mark.parametrize("bits", [8, 4])
    def test_moe_grouped_gemm(self, rng, ecdf, bits):
        from repro.quant import dequantize, quantize

        e, c, d, f = ecdf
        buf = jnp.asarray(rng.normal(size=(e, c, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        qw = quantize(w, bits, group_size=8)
        sizes = jnp.asarray(rng.integers(1, c + 1, size=(e,)), jnp.int32)
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("xla_int8")):
            got = np.asarray(
                ops.dispatch("moe_grouped_gemm", buf, qw, sizes))
        rep = ops.dispatch_report()["moe_grouped_gemm"]
        assert rep["hits"].get("xla_int8", 0) >= 1 and not rep["fallbacks"]
        # the int8 impl computes all experts densely (like xla), then zeroes
        # rows past each expert's queue length — the op contract all impls
        # share with the Pallas kernel
        want = np.einsum("ecd,edf->ecf", np.asarray(buf),
                         np.asarray(dequantize(qw, jnp.float32)))
        keep = np.arange(c)[None, :, None] < np.asarray(sizes)[:, None, None]
        want = np.where(keep, want, 0.0)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("window", [None, 8])
    @pytest.mark.parametrize("vector_len", [False, True])
    def test_int8_kv_decode(self, rng, window, vector_len):
        """int8 KV decode vs the ref oracle on the dequantized cache —
        including the traced per-slot cache_len vector the pallas impl
        rejects: the int8 impl must serve it as a HIT."""
        from repro.quant import QTensor, quantize_kv

        b, hq, hkv, smax, d = 2, 4, 2, 37, 24
        q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        kq, ks = quantize_kv(kc)
        vq, vs = quantize_kv(vc)
        kt = QTensor(kq, ks, dtype="float32")
        vt = QTensor(vq, vs, dtype="float32")
        cl = (jnp.asarray([13, 29], jnp.int32) if vector_len
              else jnp.full((b,), 29, jnp.int32))
        ops.reset_dispatch_report()
        with ops.use_policy(attention_decode="xla_int8"):
            got = jax.jit(lambda *a: A.decode_attention(
                *a, window=window))(q, kt, vt, cl)
        rep = ops.dispatch_report()["attention_decode"]
        assert rep["hits"].get("xla_int8", 0) >= 1 and not rep["fallbacks"]
        with ops.use_policy(attention_decode="ref"):
            want = A.decode_attention(
                q, kq.astype(jnp.float32) * ks, vq.astype(jnp.float32) * vs,
                cl, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    def test_fp_weight_under_int8_policy_falls_back_loudly(self, rng):
        from repro.core.unified_linear import unified_linear

        x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("xla_int8")):
            unified_linear(x, w)
        rep = ops.dispatch_report()["linear"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "xla_int8"]
        assert fb and fb[0]["used"] == "xla"
        assert any("not quantized" in r for r in fb[0]["reasons"])

    def test_quantized_weight_under_fp_policy_falls_back_loudly(self, rng):
        from repro.core.unified_linear import unified_linear
        from repro.quant import quantize

        x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
        qw = quantize(jnp.asarray(rng.normal(size=(8, 8)), jnp.float32))
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("pallas")):
            unified_linear(x, qw)
        rep = ops.dispatch_report()["linear"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "pallas"]
        assert fb and fb[0]["used"] == "xla_int8"
        assert any("QTensor" in r for r in fb[0]["reasons"])

    def test_fp_kv_under_int8_policy_falls_back_loudly(self, rng):
        b, hkv, smax, d = 2, 2, 16, 8
        q = jnp.asarray(rng.normal(size=(b, 4, 1, d)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, hkv, smax, d)), jnp.float32)
        ops.reset_dispatch_report()
        with ops.use_policy(attention_decode="xla_int8"):
            A.decode_attention(q, kc, vc, jnp.full((b,), 5, jnp.int32))
        rep = ops.dispatch_report()["attention_decode"]
        fb = [f for f in rep["fallbacks"] if f["requested"] == "xla_int8"]
        assert fb and fb[0]["used"] == "xla"
        assert any("not quantized" in r for r in fb[0]["reasons"])


# ===================================================== policy-through-model


class TestPolicyThroughModel:
    def test_config_policy_scopes_forward(self, rng):
        """A config-carried policy drives every layer's dispatch; xla vs
        blocked attention policies agree end-to-end."""
        from dataclasses import replace

        from repro import configs
        from repro.models import model as M

        cfg = replace(configs.get("m3vit", smoke=True), dtype="float32")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
        ops.reset_dispatch_report()
        y1, _, _ = M.forward(params, x, replace(
            cfg, policy=ops.policy_named("xla")))
        rep = ops.dispatch_report()
        assert rep["attention"]["hits"].get("xla", 0) >= 1
        y2, _, _ = M.forward(params, x, replace(
            cfg, policy=ops.policy_named("xla").with_impls(
                attention="blocked")))
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=2e-4, rtol=2e-4)
