"""Factored-expert suite: FactoredTensor, the SVD-seeded converters, the
tree walkers, and the xla_factored registry impls.

Property obligations (checked with `hypothesis`): reconstruction error is monotone non-increasing in
rank and exactly zero at full rank; rank-0 reconstructs the broadcast
basis bit-exactly; butterfly seeding is exact on Monarch-structured
residuals; non-finite inputs are rejected loudly; the factored dispatch
path is numerically the factored math, with every fp/int8 impl bouncing
factored operands with a reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import ops
from repro.core.unified_linear import unified_linear
from repro.factor import (FACTOR_PARAM_NAMES, FactoredTensor, factorize,
                          factorize_tree, is_factored, reconstruct,
                          reconstruct_tree, split_dim)
from repro.quant import is_qtensor, quantize, quantize_tree


def _experts(seed: int, e: int, k: int, n: int, true_rank=None,
             scale: float = 1.0):
    """Stacked expert weights; with ``true_rank`` they are basis + rank-r
    delta (the structure the converter models), else plain gaussian."""
    rng = np.random.default_rng(seed)
    if true_rank is None:
        return jnp.asarray(rng.normal(size=(e, k, n)) * scale, jnp.float32)
    basis = rng.normal(size=(k, n))
    u = rng.normal(size=(e, k, true_rank))
    v = rng.normal(size=(e, true_rank, n))
    w = basis[None] + 0.1 * np.einsum("ekr,ern->ekn", u, v)
    return jnp.asarray(w * scale, jnp.float32)


def _rel_err(ft, w) -> float:
    r = np.asarray(reconstruct(ft), np.float64)
    w = np.asarray(w, np.float64)
    return float(np.linalg.norm(r - w) / max(np.linalg.norm(w), 1e-30))


# ============================================================ FactoredTensor


class TestFactoredTensor:
    def test_pytree_roundtrip_and_properties(self):
        w = _experts(0, 4, 8, 12)
        ft = factorize(w, "rank", rank=3)
        leaves, treedef = jax.tree_util.tree_flatten(ft)
        ft2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert is_factored(ft2)
        assert ft2.kind == "rank" and ft2.dtype == "float32"
        assert ft2.experts == 4 and ft2.rank == 3
        assert ft2.shape == (4, 8, 12) and ft2.ndim == 3
        assert ft2.nbytes == ft2.basis_nbytes + ft2.delta_nbytes
        np.testing.assert_array_equal(np.asarray(reconstruct(ft)),
                                      np.asarray(reconstruct(ft2)))

    def test_key_paths_name_children(self):
        ft = factorize(_experts(0, 2, 4, 6), "rank", rank=1)
        paths = {jax.tree_util.keystr(p)
                 for p, _ in jax.tree_util.tree_flatten_with_path(ft)[0]}
        assert paths == {".basis", ".u", ".v"}

    def test_nested_qtensor_key_paths(self):
        ft = factorize(_experts(0, 2, 4, 6), "rank", rank=1, delta_bits=8)
        paths = {jax.tree_util.keystr(p)
                 for p, _ in jax.tree_util.tree_flatten_with_path(ft)[0]}
        assert paths == {".basis", ".u.q", ".u.scale", ".v.q", ".v.scale"}

    def test_jit_closure(self):
        w = _experts(1, 3, 8, 8)
        ft = factorize(w, "rank", rank=2)
        y = jax.jit(lambda f: reconstruct(f))(ft)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(reconstruct(ft)), atol=1e-6)

    def test_single_weight_has_no_expert_axis(self):
        w = _experts(2, 4, 8, 12)
        ft = factorize(np.asarray(w)[0], "rank", rank=2,
                       basis=np.asarray(w).mean(axis=0))
        assert ft.experts is None and ft.shape == (8, 12) and ft.ndim == 2


class TestSplitDim:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(min_value=1, max_value=512))
    def test_factors_multiply_back(self, n):
        a, b = split_dim(n)
        assert a * b == n and 1 <= a <= b

    def test_square_and_prime(self):
        assert split_dim(64) == (8, 8)
        assert split_dim(13) == (1, 13)

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            split_dim(0)


# ================================================================ factorize


class TestFactorizeRank:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(min_value=1, max_value=6),
           st.integers(min_value=2, max_value=12),
           st.integers(min_value=2, max_value=12))
    def test_error_monotone_in_rank_and_exact_at_full(self, e, k, n):
        w = _experts(e * 100 + k * 10 + n, e, k, n)
        errs = [_rel_err(factorize(w, "rank", rank=r), w)
                for r in range(min(k, n) + 1)]
        for lo, hi in zip(errs[1:], errs[:-1]):
            assert lo <= hi + 1e-6
        assert errs[-1] <= 1e-5          # full rank: SVD is exact

    def test_rank0_is_broadcast_basis_bitexact(self):
        w = _experts(3, 4, 8, 8)
        ft = factorize(w, "rank", rank=0)
        assert ft.rank == 0
        assert ft.u.shape == (4, 8, 0) and ft.v.shape == (4, 0, 8)
        exp = np.broadcast_to(np.asarray(w, np.float32).mean(axis=0),
                              (4, 8, 8))
        np.testing.assert_array_equal(np.asarray(reconstruct(ft)), exp)

    def test_structured_weights_recovered(self):
        # experts = basis + rank-2 delta: the residual against the mean
        # basis carries the rank-2 delta plus the (possibly higher-rank)
        # delta mean, so rank 4 absorbs most — not all — of it
        w = _experts(4, 6, 16, 24, true_rank=2)
        e4 = _rel_err(factorize(w, "rank", rank=4), w)
        assert e4 < 0.05 and e4 < _rel_err(factorize(w, "rank", rank=0), w) / 2
        # explicit true basis: residual is exactly rank 2 -> exact at r=2
        rng = np.random.default_rng(7)
        basis = rng.normal(size=(16, 24)).astype(np.float32)
        u = rng.normal(size=(6, 16, 2)).astype(np.float32)
        v = rng.normal(size=(6, 2, 24)).astype(np.float32)
        w2 = basis[None] + np.einsum("ekr,ern->ekn", u, v)
        ft = factorize(w2, "rank", rank=2, basis=basis)
        assert _rel_err(ft, w2) < 1e-5

    def test_rank_clipped_to_dims(self):
        ft = factorize(_experts(5, 2, 4, 6), "rank", rank=100)
        assert ft.rank == 4

    def test_qtensor_input(self):
        w = _experts(6, 3, 8, 8)
        qt = quantize(w, 8)
        ft = factorize(qt, "rank", rank=8)
        # factorizing the QTensor == factorizing its dequantized values
        r = np.asarray(reconstruct(ft), np.float64)
        dq = np.asarray(jnp.asarray(qt.q, jnp.float32) * qt.scale,
                        np.float64)
        assert np.linalg.norm(r - dq) / np.linalg.norm(dq) < 1e-5


class TestFactorizeButterfly:
    def test_exact_on_monarch_residuals(self):
        rng = np.random.default_rng(0)
        e, k, n = 3, 16, 36
        k1, k2 = split_dim(k)
        n1, n2 = split_dim(n)
        basis = rng.normal(size=(k, n)).astype(np.float32)
        l_fac = rng.normal(size=(e, k1, k2, n2)).astype(np.float32)
        r_fac = rng.normal(size=(e, n2, k1, n1)).astype(np.float32)
        delta = np.einsum("eakn,enab->eakbn", l_fac, r_fac).reshape(e, k, n)
        w = basis[None] + delta
        ft = factorize(w, "butterfly", basis=basis)
        assert ft.kind == "butterfly" and ft.experts == e
        assert _rel_err(ft, w) < 1e-5

    def test_compresses_vs_dense(self):
        w = _experts(0, 8, 64, 64)
        ft = factorize(w, "butterfly")
        assert ft.delta_nbytes < np.asarray(w).nbytes / 2


class TestFactorizeDeltaBits:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantized_deltas_shrink_and_stay_close(self, bits):
        w = _experts(1, 4, 16, 24, true_rank=2)
        fp = factorize(w, "rank", rank=4)
        q = factorize(w, "rank", rank=4, delta_bits=bits)
        assert is_qtensor(q.u) and is_qtensor(q.v)
        assert q.delta_nbytes < fp.delta_nbytes
        # quantizing the (small) deltas perturbs the reconstruction only
        # slightly beyond the fp factorization's own error
        assert _rel_err(q, w) < _rel_err(fp, w) + 0.05

    def test_rank0_skips_quantization(self):
        q = factorize(_experts(2, 3, 8, 8), "rank", rank=0, delta_bits=8)
        assert not is_qtensor(q.u) and q.u.size == 0


class TestFactorizeRejections:
    def test_bad_kind(self):
        with pytest.raises(ValueError, match="kind"):
            factorize(_experts(0, 2, 4, 4), "tucker")

    def test_negative_rank(self):
        with pytest.raises(ValueError, match="rank"):
            factorize(_experts(0, 2, 4, 4), "rank", rank=-1)

    def test_bad_ndim(self):
        with pytest.raises(ValueError, match="stacked experts"):
            factorize(jnp.zeros((4,)))
        with pytest.raises(ValueError, match="stacked experts"):
            factorize(jnp.zeros((2, 2, 4, 4)))

    def test_single_weight_without_basis(self):
        with pytest.raises(ValueError, match="basis"):
            factorize(jnp.ones((4, 4)))

    def test_basis_shape_mismatch(self):
        with pytest.raises(ValueError, match="basis shape"):
            factorize(_experts(0, 2, 4, 4), basis=np.ones((3, 4)))

    def test_bad_delta_bits(self):
        with pytest.raises(ValueError, match="delta_bits"):
            factorize(_experts(0, 2, 4, 4), delta_bits=2)

    @pytest.mark.parametrize("bad", [np.nan, np.inf])
    def test_nonfinite_weights_rejected(self, bad):
        w = np.array(_experts(0, 2, 4, 4))
        w[1, 2, 3] = bad
        with pytest.raises(ValueError, match="NaN/Inf"):
            factorize(w)

    def test_nonfinite_basis_rejected(self):
        b = np.ones((4, 4), np.float32)
        b[0, 0] = np.nan
        with pytest.raises(ValueError, match="NaN/Inf"):
            factorize(_experts(0, 2, 4, 4), basis=b)


# ============================================================ tree walkers


def _moe_dict(seed=0, e=4, k=8, n=12):
    return {"gate": jnp.zeros((k, e)),
            "w1": _experts(seed, e, k, n),
            "b1": jnp.zeros((e, n)),
            "w2": _experts(seed + 1, e, n, k),
            "b2": jnp.zeros((e, k))}


class TestFactorizeTree:
    def test_factors_expert_leaves_next_to_gate(self):
        t = factorize_tree({"moe": _moe_dict()}, rank=2)
        assert is_factored(t["moe"]["w1"]) and is_factored(t["moe"]["w2"])
        assert not is_factored(t["moe"]["gate"])
        assert not is_factored(t["moe"]["b1"])

    def test_skips_layer_stacked_dense_mlp(self):
        # a scanned dense block's (L, K, N) w1 has the same name/ndim as an
        # expert stack but NO gate sibling — it must pass through (slicing
        # a wrongly-factored leaf per layer would shred the basis)
        t = factorize_tree({"mlp": {"w1": _experts(0, 2, 8, 12),
                                    "b1": jnp.zeros((2, 12))}})
        assert not is_factored(t["mlp"]["w1"])

    def test_skips_scanned_expert_stacks(self):
        # scanned MoE layers stack a leading layer axis (ndim 4): not
        # factorable as-is — per-layer factorization happens after slicing
        t = factorize_tree({"moe": {"gate": jnp.zeros((2, 8, 4)),
                                    "w1": jnp.zeros((2, 4, 8, 12))}})
        assert not is_factored(t["moe"]["w1"])

    def test_accepts_qtensor_leaves(self):
        qt = quantize_tree({"moe": _moe_dict()})
        t = factorize_tree(qt, rank=2)
        assert is_factored(t["moe"]["w1"])

    def test_idempotent(self):
        t = factorize_tree({"moe": _moe_dict()}, rank=2)
        t2 = factorize_tree(t, rank=2)
        assert t2["moe"]["w1"] is t["moe"]["w1"]

    def test_respects_names(self):
        t = factorize_tree({"moe": _moe_dict()}, rank=2, names={"w1"})
        assert is_factored(t["moe"]["w1"])
        assert not is_factored(t["moe"]["w2"])

    def test_reconstruct_tree_inverts(self):
        src = {"moe": _moe_dict(3)}
        t = reconstruct_tree(factorize_tree(src, rank=8))
        assert not any(is_factored(x) for x in jax.tree.leaves(
            t, is_leaf=is_factored))
        r = np.asarray(t["moe"]["w1"])
        w = np.asarray(src["moe"]["w1"])
        assert np.linalg.norm(r - w) / np.linalg.norm(w) < 1e-4

    def test_quantize_tree_passes_factored_through(self):
        t = factorize_tree({"moe": _moe_dict()}, rank=2)
        q = quantize_tree(t)
        assert is_factored(q["moe"]["w1"])
        assert not is_qtensor(q["moe"]["w1"])


# ======================================================= dispatch / impls


class TestFactoredDispatch:
    def _moe_operands(self, delta_bits=None, kind="rank"):
        w = _experts(0, 4, 16, 24, true_rank=2)
        ft = factorize(w, kind, rank=4, delta_bits=delta_bits)
        buf = jnp.asarray(
            np.random.default_rng(1).normal(size=(4, 6, 16)), jnp.float32)
        return buf, w, ft

    @pytest.mark.parametrize("delta_bits", [None, 8, 4])
    @pytest.mark.parametrize("kind", ["rank", "butterfly"])
    def test_moe_gemm_close_to_dense_reference(self, delta_bits, kind):
        buf, w, ft = self._moe_operands(delta_bits, kind)
        from repro.ops.registry import dispatch
        with ops.use_policy(ops.policy_named("xla_factored")):
            y = dispatch("moe_grouped_gemm", buf, ft, None)
        ref = jnp.einsum("ecd,edf->ecf", buf,
                         reconstruct(ft).astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-4)

    def test_moe_gemm_is_recorded_hit(self):
        buf, _, ft = self._moe_operands()
        from repro.ops.registry import dispatch
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("xla_factored")):
            dispatch("moe_grouped_gemm", buf, ft, None)
        rep = ops.dispatch_report()["moe_grouped_gemm"]
        assert rep["hits"] == {"xla_factored": 1} and not rep["fallbacks"]

    def test_default_policy_falls_back_to_factored(self):
        # no policy: the fp impls bounce the factored operand with a
        # reason and the chain lands on xla_factored — same numbers
        buf, _, ft = self._moe_operands()
        from repro.ops.registry import dispatch
        ops.reset_dispatch_report()
        y_fb = dispatch("moe_grouped_gemm", buf, ft, None)
        rep = ops.dispatch_report()["moe_grouped_gemm"]
        assert rep["fallbacks"], "expected a recorded fallback"
        fb = rep["fallbacks"][0]
        assert fb["used"] == "xla_factored"
        assert any("factored" in r for r in fb["reasons"])
        with ops.use_policy(ops.policy_named("xla_factored")):
            y_hit = dispatch("moe_grouped_gemm", buf, ft, None)
        np.testing.assert_array_equal(np.asarray(y_fb), np.asarray(y_hit))

    def test_linear_serves_single_factored_weight(self):
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.normal(size=(3, 16, 8)), jnp.float32)
        basis = jnp.asarray(np.asarray(w).mean(axis=0))
        ft = factorize(np.asarray(w)[0], rank=8, basis=basis)
        x = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
        y = unified_linear(x, ft)
        ref = x @ reconstruct(ft)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-4)

    def test_linear_rejects_expert_stacked_factored(self):
        from repro.ops.registry import DispatchError, dispatch
        _, _, ft = self._moe_operands()
        x = jnp.ones((5, 16), jnp.float32)
        with pytest.raises(DispatchError):
            dispatch("linear", x, ft, None)

    def test_int8_impl_bounces_factored_with_reason(self):
        from repro.ops.registry import registered
        buf, _, ft = self._moe_operands()
        impl = registered("moe_grouped_gemm")["xla_int8"]
        why = impl.requires(ops.current_policy(), buf, ft, None)
        assert why and "factored" in why
