"""The fused Pallas MoE megakernel + fused decode attention, end to end.

Covers the single-pass MoE layer (dispatch gather + expert GEMMs +
activation + weighted combine in ONE ``pallas_call`` — the ``(E, C, d)``
buffer never exists), the single-pass decode attention that consumes the
softmax normalizer inside the PV loop, and the kernel-layer bugfix sweep
that rode along: interpret-mode observability, the single-source GELU
delta table with exact-limit non-finite handling, and the grouped-GEMM
zeroed-tail output contract.

The parity sweeps deliberately use odd/prime token counts and queue
lengths so padding, empty-expert skip, and masking paths are exercised —
and every sweep asserts the dispatch report recorded a HIT, so a silent
fallback to a staged impl fails loudly rather than passing on the wrong
code path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.core import attention as A
from repro.core import moe as M
from repro.core import routing as R
from repro.core.gelu import (_cached_table, build_delta_table, exact_gelu,
                             lut_activation)
from repro.core.online_softmax import merge_stats, online_max_sum
from repro.kernels import ref
from repro.kernels.runtime import default_interpret, resolve_interpret

# fused keeps f32 in VMEM end to end; in f32 it is bit-compatible with the
# staged path up to dot reassociation
F32_TOL = dict(atol=2e-5, rtol=2e-5)


def _cfg(kind="gelu", e=8, d=32, f=64, k=2, group=64, cf=2.0):
    return M.MoEConfig(d_model=d, d_ff=f, num_experts=e, top_k=k,
                       expert_kind=kind, capacity_factor=cf, group_size=group)


def _routed(rng, cfg, t, logits=None):
    """Random routing for t tokens; returns (x, routing, group_sizes, cap)."""
    cap = cfg.capacity(t)
    if logits is None:
        logits = jnp.asarray(rng.normal(size=(t, cfg.num_experts)),
                             jnp.float32)
    r = R.route(logits, cfg.top_k, cap)
    sizes = R.dispatch_counts(r, cfg.num_experts)
    x = jnp.asarray(rng.normal(size=(t, cfg.d_model)), jnp.float32)
    return x, r, sizes, cap


def _moe_report():
    return ops.dispatch_report()["moe_ffn"]


# =============================================================== fused MoE


class TestFusedMoEParity:
    """apply_moe under the pallas_fused policy vs the staged seed default
    ("blocked" — same LUT activations), at odd token counts."""

    @pytest.mark.parametrize("kind", ["gelu", "swiglu"])
    @pytest.mark.parametrize("t", [37, 67, 128])
    def test_matches_staged_lut_path(self, rng, kind, t):
        cfg = _cfg(kind)
        params = M.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(t, cfg.d_model)), jnp.float32)
        with ops.use_policy(ops.policy_named("blocked")):
            want, aux_want = M.apply_moe(params, cfg, x)
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("pallas_fused")):
            got, aux_got = M.apply_moe(params, cfg, x)
        rep = _moe_report()
        assert rep["hits"].get("pallas_fused", 0) >= 1, rep
        assert not rep["fallbacks"], rep
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **F32_TOL)
        np.testing.assert_allclose(float(aux_got), float(aux_want),
                                   rtol=1e-5)

    @pytest.mark.parametrize("kind", ["gelu", "swiglu"])
    def test_bf16_model_dtype_one_ulp_of_ref(self, rng, kind):
        # bf16 params: fused (f32 in VMEM) and staged (bf16 casts between
        # projections) are each within one bf16 ulp of the exact oracle
        cfg = _cfg(kind)
        params = M.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
        x = jnp.asarray(rng.normal(size=(64, cfg.d_model)), jnp.bfloat16)
        with ops.use_policy(ops.policy_named("ref")):
            want, _ = M.apply_moe(params, cfg, x)
        with ops.use_policy(ops.policy_named("pallas_fused")):
            got, _ = M.apply_moe(params, cfg, x)
        dev = np.max(np.abs(np.asarray(got, np.float32)
                            - np.asarray(want, np.float32)))
        assert dev <= 2 * np.spacing(np.float32(
            np.max(np.abs(np.asarray(want, np.float32))))) * 2**16


class TestFusedMoEDirect:
    """Direct moe_ffn dispatches with crafted routing vs the exact ref
    oracle (custom policy without LUT so both sides use exact acts)."""

    def _fused_exact(self):
        # activation pinned to the exact impl so lut_activations is False —
        # the kernel then computes erf-GELU / sigmoid-SiLU in VMEM and the
        # comparison against the exact ref oracle is tight
        return ops.ComputePolicy(impls=(("moe_ffn", "pallas_fused"),
                                        ("activation", "xla")))

    @pytest.mark.parametrize("kind", ["gelu", "swiglu"])
    def test_empty_expert_queues(self, rng, kind):
        # rig logits so only experts 1 and 5 ever win: six queues are empty
        # and the metaqueue skip must not read their weights' garbage
        cfg = _cfg(kind, k=2)
        t = 29
        logits = jnp.full((t, cfg.num_experts), -1e9, jnp.float32)
        logits = logits.at[:, 1].set(1.0).at[:, 5].set(0.5)
        x, r, sizes, cap = _routed(rng, cfg, t, logits=logits)
        assert int((R.dispatch_counts(r, cfg.num_experts) == 0).sum()) >= 6
        params = M.init_moe(jax.random.PRNGKey(1), cfg, dtype=jnp.float32)
        eparams = {k_: params[k_] for k_ in M.expert_param_names(cfg)}
        want = ref.ref_moe_ffn(x, eparams, r, cfg=cfg)
        ops.reset_dispatch_report()
        with ops.use_policy(self._fused_exact()):
            got = ops.dispatch("moe_ffn", x, eparams, r, sizes,
                               cfg=cfg, capacity=cap)
        rep = _moe_report()
        assert rep["hits"].get("pallas_fused", 0) >= 1 and not rep["fallbacks"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **F32_TOL)

    @pytest.mark.parametrize("top_k", [1, 3])
    def test_topk_combine_weights(self, rng, top_k):
        # each token accumulates k gate-weighted expert outputs across the
        # expert sweep's grid steps — prime t so the queue tails are ragged
        cfg = _cfg("gelu", k=top_k)
        x, r, sizes, cap = _routed(rng, cfg, 31)
        params = M.init_moe(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
        eparams = {k_: params[k_] for k_ in M.expert_param_names(cfg)}
        want = ref.ref_moe_ffn(x, eparams, r, cfg=cfg)
        with ops.use_policy(self._fused_exact()):
            got = ops.dispatch("moe_ffn", x, eparams, r, sizes,
                               cfg=cfg, capacity=cap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **F32_TOL)

    def test_capacity_overflow_drops_match_oracle(self, rng):
        # overload one expert past capacity: invalid slots must contribute
        # zero on both sides (fused: gate 0 + tok=-1 annihilate the row)
        cfg = _cfg("gelu", e=4, k=1, cf=0.5)
        t = 48
        logits = jnp.zeros((t, 4), jnp.float32).at[:, 2].set(5.0)
        x, r, sizes, cap = _routed(rng, cfg, t, logits=logits)
        assert not bool(r.valid.all())
        params = M.init_moe(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
        eparams = {k_: params[k_] for k_ in M.expert_param_names(cfg)}
        want = ref.ref_moe_ffn(x, eparams, r, cfg=cfg)
        with ops.use_policy(self._fused_exact()):
            got = ops.dispatch("moe_ffn", x, eparams, r, sizes,
                               cfg=cfg, capacity=cap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **F32_TOL)


class TestFusedMoEBounces:
    """Packed operands and compiled-only policies bounce with a reason —
    recorded fallbacks, never wrong-path silence."""

    def _dispatch(self, params_xform=None, policy=None):
        rng = np.random.default_rng(0)
        cfg = _cfg("gelu", e=4, d=16, f=24)
        x, r, sizes, cap = _routed(rng, cfg, 16)
        params = M.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        eparams = {k_: params[k_] for k_ in M.expert_param_names(cfg)}
        if params_xform:
            eparams = params_xform(eparams)
        ops.reset_dispatch_report()
        with ops.use_policy(policy or ops.policy_named("pallas_fused")):
            ops.dispatch("moe_ffn", x, eparams, r, sizes,
                         cfg=cfg, capacity=cap)
        return _moe_report()

    def test_int8_weights_bounce_to_staged(self):
        from repro.quant import quantize

        def q(ep):
            ep["w1"] = quantize(ep["w1"], 8, group_size=8)
            ep["w2"] = quantize(ep["w2"], 8, group_size=8)
            return ep

        rep = self._dispatch(params_xform=q)
        fb = rep["fallbacks"][0]
        assert fb["used"] == "xla"
        assert any("quantized" in r for r in fb["reasons"])

    def test_factored_weights_bounce_to_staged(self):
        from repro.factor import factorize

        def fx(ep):
            ep["w1"] = factorize(ep["w1"], "rank", rank=4)
            return ep

        rep = self._dispatch(params_xform=fx)
        fb = rep["fallbacks"][0]
        assert fb["used"] == "xla"
        assert any("factored" in r for r in fb["reasons"])

    @pytest.mark.skipif(not default_interpret(),
                        reason="compiled kernels available on this backend")
    def test_compiled_only_policy_bounces_off_tpu(self):
        p = dataclasses.replace(ops.policy_named("pallas_fused"),
                                interpret=False)
        rep = self._dispatch(policy=p)
        fb = rep["fallbacks"][0]
        assert fb["used"] == "xla"
        assert any("interpret" in r or "compiled" in r
                   for r in fb["reasons"])


# ============================================================ fused decode


class TestFusedDecode:
    def _qkv(self, rng, b=2, hq=4, hkv=4, s=96, d=64, dtype=jnp.float32):
        q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), dtype)
        k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
        v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
        return q, k, v

    def _fused(self, q, k, v, cl, **kw):
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("pallas_fused")):
            out = A.decode_attention(q, k, v, cl, **kw)
        rep = ops.dispatch_report()["attention_decode"]
        assert rep["hits"].get("pallas_fused", 0) >= 1, rep
        assert not rep["fallbacks"], rep
        return np.asarray(out, np.float32)

    @pytest.mark.parametrize("window", [None, 17])
    def test_matches_ref_nonuniform_lengths(self, rng, window):
        q, k, v = self._qkv(rng)
        cl = jnp.asarray([77, 31], jnp.int32)
        got = self._fused(q, k, v, cl, window=window)
        for i in range(2):
            want = ref.ref_attention(
                q[i:i + 1], k[i:i + 1, :, :int(cl[i])],
                v[i:i + 1, :, :int(cl[i])], causal=False, window=None)
            if window is not None:
                lo = max(0, int(cl[i]) - window)
                want = ref.ref_attention(
                    q[i:i + 1], k[i:i + 1, :, lo:int(cl[i])],
                    v[i:i + 1, :, lo:int(cl[i])], causal=False)
            np.testing.assert_allclose(got[i:i + 1], np.asarray(want),
                                       atol=2e-6, rtol=2e-5)

    def test_traced_cache_len_under_jit(self, rng):
        # the plain pallas decode impl rejects traced/vector cache_len; the
        # fused kernel reads it via scalar prefetch at run time — same jit
        q, k, v = self._qkv(rng)

        @jax.jit
        def step(cl):
            with ops.use_policy(ops.policy_named("pallas_fused")):
                return A.decode_attention(q, k, v, cl)

        ops.reset_dispatch_report()
        a = np.asarray(step(jnp.asarray([5, 90], jnp.int32)))
        b = np.asarray(step(jnp.asarray([60, 1], jnp.int32)))
        rep = ops.dispatch_report()["attention_decode"]
        assert rep["hits"].get("pallas_fused", 0) >= 1 and not rep["fallbacks"]
        for out, cls in ((a, (5, 90)), (b, (60, 1))):
            for i, c in enumerate(cls):
                want = ref.ref_attention(q[i:i + 1], k[i:i + 1, :, :c],
                                         v[i:i + 1, :, :c], causal=False)
                np.testing.assert_allclose(out[i:i + 1], np.asarray(want),
                                           atol=2e-6, rtol=2e-5)

    def test_gqa_grouped_heads(self, rng):
        q, k, v = self._qkv(rng, hq=8, hkv=2)
        cl = jnp.asarray([50, 96], jnp.int32)
        got = self._fused(q, k, v, cl)
        with ops.use_policy(ops.policy_named("xla")):
            want = np.asarray(A.decode_attention(q, k, v, cl), np.float32)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-5)

    def test_zero_length_rows_are_exact_zero(self, rng):
        q, k, v = self._qkv(rng)
        got = self._fused(q, k, v, jnp.asarray([0, 42], jnp.int32))
        assert np.all(got[0] == 0.0)
        assert np.any(got[1] != 0.0)


class TestOnlineSoftmaxCarry:
    """The (m, s) carry algebra the fused decode reuses from
    core/online_softmax.py — including the all-masked degenerate rows."""

    def test_blockwise_merge_matches_oracle(self, rng):
        x = jnp.asarray(rng.normal(size=(5, 384)) * 4, jnp.float32)
        m, s = online_max_sum(x[:, :128])
        for lo in (128, 256):
            mb, sb = online_max_sum(x[:, lo:lo + 128])
            m, s = merge_stats(m, s, mb, sb)
        mo, so = online_max_sum(x)
        np.testing.assert_allclose(np.asarray(m), np.asarray(mo))
        np.testing.assert_allclose(np.asarray(s), np.asarray(so), rtol=1e-6)

    def test_all_masked_merge_is_identity(self):
        ninf = jnp.float32(-jnp.inf)
        m, s = merge_stats(ninf, jnp.float32(0.0), ninf, jnp.float32(0.0))
        assert float(m) == -np.inf and float(s) == 0.0

    def test_all_masked_rows_finite_sentinel(self):
        # the kernels mask with a finite -1e30 (never feed -inf to exp):
        # the carry stays finite and the PV product underflows to the exact
        # zero the fused decode returns for cache_len == 0 rows
        x = jnp.full((3, 256), -1e30, jnp.float32)
        m, s = online_max_sum(x)
        assert np.all(np.isfinite(np.asarray(m)))
        acc = jnp.zeros((3, 8), jnp.float32)  # sum of p·V with p == exp(0)·0
        out = acc / jnp.maximum(s[:, None] * 0.0, 1e-37)
        assert np.all(np.asarray(out) == 0.0)


# ===================================================== kernel bugfix sweep


class TestGeluTableSingleSource:
    def test_build_delta_table_equals_cached(self):
        for kind in ("gelu", "silu"):
            a = np.asarray(build_delta_table(kind))
            b = _cached_table(kind, -8, 8.0)
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
    def test_nonfinite_propagates_like_exact(self, bad):
        x = jnp.asarray([bad, 1.0, -2.5], jnp.float32)
        got = np.asarray(lut_activation(x, "gelu"))
        want = np.asarray(exact_gelu(x))
        # element 0: same limit as the exact activation
        assert np.isnan(got[0]) == np.isnan(want[0])
        if not np.isnan(want[0]):
            assert got[0] == want[0]
        # finite elements still go through the LUT (within table step)
        np.testing.assert_allclose(got[1:], want[1:], atol=2e-3)

    def test_huge_finite_is_relu_not_garbage_gather(self):
        x = jnp.asarray([3e38, -3e38, 8.0, -8.0], jnp.float32)
        got = np.asarray(lut_activation(x, "gelu"))
        np.testing.assert_array_equal(
            got[:2], np.asarray([3e38, 0.0], np.float32))
        assert np.all(np.isfinite(got))


class TestMoEGemmZeroedTails:
    @pytest.mark.parametrize("sizes", [(5, 0, 128, 37), (1, 127, 3, 65)])
    def test_kernel_rows_past_queue_length_are_zero(self, rng, sizes):
        from repro.kernels.moe_gemm import moe_gemm_call

        e, c, d, f = 4, 128, 64, 64
        # garbage in the padded tails — the bug this regression pins down:
        # the kernel used to multiply it into the output
        buf = jnp.asarray(rng.normal(size=(e, c, d)) * 1e3, jnp.float32)
        w = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        gs = jnp.asarray(sizes, jnp.int32)
        out = np.asarray(moe_gemm_call(buf, w, gs, block_c=64, block_f=64,
                                       block_k=64))
        want = np.asarray(ref.ref_moe_gemm(buf, w, gs))
        np.testing.assert_allclose(out, want, atol=1e-2, rtol=1e-5)
        for i, s in enumerate(sizes):
            assert np.all(out[i, s:] == 0.0), f"expert {i} tail not zeroed"

    def test_xla_impl_shares_the_contract(self, rng):
        buf = jnp.asarray(rng.normal(size=(3, 7, 8)) * 1e3, jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, 8, 5)), jnp.float32)
        gs = jnp.asarray([2, 0, 7], jnp.int32)
        with ops.use_policy(ops.policy_named("xla")):
            out = np.asarray(ops.dispatch("moe_grouped_gemm", buf, w, gs))
        assert np.all(out[0, 2:] == 0.0) and np.all(out[1] == 0.0)
        assert np.any(out[2] != 0.0)


class TestInterpretModeReporting:
    def test_report_shows_which_mode_ran(self, rng):
        x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("pallas")):
            ops.apply_activation(x, "gelu")
        rep = ops.dispatch_report()["activation"]
        mode = "interpret" if default_interpret() else "compiled"
        assert rep["modes"]["pallas"][mode] >= 1

    def test_non_kernel_impls_record_no_mode(self, rng):
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        ops.reset_dispatch_report()
        with ops.use_policy(ops.policy_named("xla")):
            ops.apply_activation(x, "gelu")
        rep = ops.dispatch_report()["activation"]
        assert "xla" not in rep.get("modes", {})

    @pytest.mark.skipif(not default_interpret(),
                        reason="compiled kernels available on this backend")
    def test_interpret_false_off_tpu_is_reasoned_fallback(self, rng):
        x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
        p = dataclasses.replace(ops.policy_named("pallas"), interpret=False)
        ops.reset_dispatch_report()
        with ops.use_policy(p):
            ops.apply_activation(x, "gelu")
        rep = ops.dispatch_report()["activation"]
        assert not rep["hits"].get("pallas")
        assert rep["fallbacks"] and any(
            "compiled" in r or "interpret" in r
            for r in rep["fallbacks"][0]["reasons"])

    @pytest.mark.parametrize("explicit", [None, True, False])
    def test_resolve_interpret_honours_explicit_choice(self, explicit):
        # only None asks the backend; False means compiled on any backend
        # (a compile for a described TPU needs exactly that off-TPU)
        want = default_interpret() if explicit is None else explicit
        assert resolve_interpret(explicit) is want


class TestModeledTraffic:
    def test_m3vit_fused_moves_at_least_2x_fewer_bytes(self):
        from repro.roofline import moe_traffic_report

        rep = moe_traffic_report(tokens=128, d_model=192, d_ff=768,
                                 num_experts=16, capacity=68, kind="gelu")
        assert rep["ratio_staged_over_fused"] >= 2.0
        for side in ("staged", "fused"):
            assert rep[f"{side}_bytes"] == sum(rep[f"{side}_items"].values())

    def test_dtype_awareness_changes_the_model(self):
        from repro.roofline import staged_moe_bytes

        bf16 = staged_moe_bytes(tokens=128, d_model=192, d_ff=768,
                                num_experts=16, capacity=68)
        f32 = staged_moe_bytes(tokens=128, d_model=192, d_ff=768,
                               num_experts=16, capacity=68,
                               param_dtype="float32", act_dtype="float32")
        assert f32["total"] > bf16["total"]
