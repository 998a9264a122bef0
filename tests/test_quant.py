"""Property-based quantization suite: the dist.compress int8 chunks, the
repro.quant QTensor paths, int8 KV serving, quantized expert paging.

Edge cases the `hypothesis` properties must cover: all-zero rows, single-element
channels, extreme magnitudes, NaN rejection — with scale>0 and elementwise
reconstruction-error bounds (half a quantization step).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import ops
from repro.dist import compress as C
from repro.quant import (QTensor, dequantize, dequantize_tree, is_qtensor,
                         quantize, quantize_kv, quantize_tree, tree_bytes)


# ======================================================== dist.compress


class TestCompressRoundtrip:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.floats(min_value=-1e6, max_value=1e6),
                    min_size=1, max_size=600))
    def test_error_bounded_by_half_step(self, vals):
        x = jnp.asarray(np.asarray(vals, np.float32))
        q, s = C.quantize_int8(x)
        deq = np.asarray(C.dequantize_int8(q, s, x.shape))
        s_np = np.asarray(s)
        assert np.isfinite(s_np).all() and (s_np >= 0).all()
        # elementwise: |x - deq| <= scale/2 for that element's chunk
        flat = np.asarray(x).reshape(-1)
        pad = (-flat.size) % C.CHUNK
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
        err = np.abs(flat - np.concatenate(
            [deq.reshape(-1), np.zeros(pad, np.float32)]))
        bound = np.repeat(s_np.reshape(-1), C.CHUNK) / 2 + 1e-6
        assert (err <= bound).all()

    def test_all_zero_chunk_exact(self):
        x = jnp.zeros((2 * C.CHUNK + 3,), jnp.float32)
        q, s = C.quantize_int8(x)
        assert (np.asarray(q) == 0).all()
        np.testing.assert_array_equal(
            np.asarray(C.dequantize_int8(q, s, x.shape)), 0.0)

    def test_single_element(self):
        x = jnp.asarray([-3.7], jnp.float32)
        q, s = C.quantize_int8(x)
        deq = np.asarray(C.dequantize_int8(q, s, x.shape))
        assert abs(deq[0] + 3.7) <= float(np.asarray(s)[0, 0]) / 2 + 1e-6

    def test_extreme_magnitudes_stay_finite(self):
        x = jnp.asarray([3e37, -3e37, 1e-30, 0.0], jnp.float32)
        q, s = C.quantize_int8(x)
        assert np.isfinite(np.asarray(s)).all()
        deq = np.asarray(C.dequantize_int8(q, s, x.shape))
        assert np.isfinite(deq).all()
        np.testing.assert_allclose(deq[:2], np.asarray(x[:2]), rtol=0.01)


# ============================================================ QTensor


def _example_weight(seed: int, rows: int, cols: int, scale: float = 1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, cols)) * scale, jnp.float32)


class TestQTensorInt8:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=1, max_value=67),
           st.integers(min_value=1, max_value=23),
           st.floats(min_value=-12.0, max_value=12.0))
    def test_roundtrip_bound_and_positive_scale(self, rows, cols, log_mag):
        w = _example_weight(rows * 31 + cols, rows, cols,
                            scale=10.0 ** log_mag)
        qt = quantize(w, 8)
        assert qt.bits == 8 and qt.shape == w.shape
        s = np.asarray(qt.scale)
        assert (s > 0).all()                       # scale strictly positive
        err = np.abs(np.asarray(dequantize(qt, jnp.float32) - w))
        assert (err <= s / 2 + 1e-7 * s).all()     # half a step per channel

    def test_all_zero_channel_exact(self):
        w = _example_weight(0, 16, 8).at[:, 3].set(0.0)
        qt = quantize(w, 8)
        assert (np.asarray(qt.scale) > 0).all()
        deq = np.asarray(dequantize(qt, jnp.float32))
        np.testing.assert_array_equal(deq[:, 3], 0.0)

    def test_single_element_channel(self):
        w = jnp.asarray([[2.5, -0.25, 0.0]], jnp.float32)   # K = 1
        qt = quantize(w, 8)
        deq = np.asarray(dequantize(qt, jnp.float32))
        np.testing.assert_allclose(deq, np.asarray(w), rtol=0.01, atol=1e-9)

    def test_nan_and_inf_rejected(self):
        w = _example_weight(1, 8, 8)
        with pytest.raises(ValueError):
            quantize(w.at[2, 2].set(jnp.nan))
        with pytest.raises(ValueError):
            quantize(w.at[0, 0].set(jnp.inf), 4)

    def test_bad_args_rejected(self):
        with pytest.raises(ValueError):
            quantize(jnp.zeros((8,)), 8)           # ndim < 2
        with pytest.raises(ValueError):
            quantize(jnp.zeros((8, 8)), 5)         # unsupported width

    def test_moe_shaped_scale_per_expert_channel(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(5, 24, 16)), jnp.float32)
        qt = quantize(w, 8)
        assert qt.scale.shape == (5, 1, 16)
        err = np.abs(np.asarray(dequantize(qt, jnp.float32) - w))
        assert (err <= np.asarray(qt.scale) / 2 + 1e-7).all()


class TestQTensorInt4:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=1, max_value=49),
           st.integers(min_value=1, max_value=17),
           st.integers(min_value=2, max_value=32))
    def test_roundtrip_bound(self, rows, cols, group):
        w = _example_weight(rows * 7 + cols, rows, cols)
        qt = quantize(w, 4, group_size=group)
        assert qt.bits == 4 and qt.shape == w.shape
        s = np.asarray(qt.scale)
        assert (s > 0).all()
        deq = np.asarray(dequantize(qt, jnp.float32))
        # elementwise bound: half a step of the element's own group scale
        # (the padded K is 2× the packed rows; groups tile it evenly)
        ng = s.shape[-2]
        g = 2 * qt.q.shape[-2] // ng
        bound = np.repeat(s, g, axis=-2)[:rows] / 2 + 1e-7
        assert (np.abs(deq - np.asarray(w)) <= bound).all()

    def test_packing_halves_payload(self):
        w = _example_weight(3, 64, 32)
        q8, q4 = quantize(w, 8), quantize(w, 4)
        assert q4.q.dtype == jnp.uint8
        assert q4.q.shape[-2] == q8.q.shape[-2] // 2

    def test_odd_rows_pad_and_slice(self):
        w = _example_weight(4, 37, 8)              # odd K
        qt = quantize(w, 4, group_size=8)
        assert qt.shape == (37, 8)
        assert dequantize(qt, jnp.float32).shape == (37, 8)


class TestKVQuant:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=1, max_value=64))
    def test_per_row_bound(self, d):
        rng = np.random.default_rng(d)
        x = jnp.asarray(rng.normal(size=(2, 3, 5, d)) * 4, jnp.float32)
        q, s = quantize_kv(x)
        assert q.shape == x.shape and s.shape == x.shape[:-1] + (1,)
        assert (np.asarray(s) > 0).all()
        err = np.abs(np.asarray(q, np.float32) * np.asarray(s)
                     - np.asarray(x))
        assert (err <= np.asarray(s) / 2 + 1e-7).all()

    def test_zero_row_exact_and_jit_safe(self):
        x = jnp.zeros((1, 1, 2, 8), jnp.float32)
        q, s = jax.jit(quantize_kv)(x)
        np.testing.assert_array_equal(np.asarray(q, np.float32)
                                      * np.asarray(s), 0.0)


# ========================================================== tree conversion


class TestQuantizeTree:
    def test_only_matmul_weights_convert(self):
        rng = np.random.default_rng(0)
        tree = {
            "attn": {"wq": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32),
                     "bq": jnp.zeros((8,), jnp.float32)},
            "moe": {"w1": jnp.asarray(rng.normal(size=(4, 8, 8)), jnp.float32),
                    "b1": jnp.zeros((4, 8), jnp.float32),
                    "gate": jnp.asarray(rng.normal(size=(2, 8, 4)),
                                        jnp.float32)},
            "embed": {"tokens": jnp.zeros((16, 8), jnp.float32)},
            "rest": [{"w": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}],
        }
        qt = quantize_tree(tree)
        assert is_qtensor(qt["attn"]["wq"]) and is_qtensor(qt["moe"]["w1"])
        assert is_qtensor(qt["rest"][0]["w"])
        assert not is_qtensor(qt["attn"]["bq"])
        assert not is_qtensor(qt["moe"]["gate"])     # routing stays fp
        assert not is_qtensor(qt["embed"]["tokens"])  # consumed by take()
        deq = dequantize_tree(qt)
        assert deq["attn"]["wq"].shape == (8, 8)
        assert deq["attn"]["wq"].dtype == jnp.float32

    def test_idempotent(self):
        tree = {"w": jnp.ones((4, 4), jnp.float32)}
        once = quantize_tree(tree)
        twice = quantize_tree(once)
        assert twice["w"] is once["w"]


# ============================================= acceptance-criteria mirrors


class TestM3ViTAcceptance:
    """The benchmarks/quant_memory.py acceptance bars, enforced as tests:
    ≥3.5× expert-weight bytes at int8 and cosine ≥0.999 vs the fp32
    forward, with the quantized impls served as dispatch HITS."""

    @pytest.fixture(scope="class")
    def setup(self):
        from dataclasses import replace

        from repro import configs
        from repro.models import vit as V

        cfg = replace(configs.get("m3vit", smoke=True), dtype="float32")
        params = V.init_params(jax.random.PRNGKey(0), cfg)
        return cfg, params, V

    def test_expert_bytes_reduction(self, setup):
        cfg, params, V = setup
        moe = params["layers"]["b1"]["moe"]
        fp = {k: moe[k] for k in ("w1", "w2")}
        q8 = quantize_tree(fp)
        assert tree_bytes(fp) / tree_bytes(q8) >= 3.5
        q4 = quantize_tree(fp, bits=4)
        assert tree_bytes(fp) / tree_bytes(q4) >= 6.0

    def test_forward_cosine_and_hits(self, setup):
        from dataclasses import replace

        cfg, params, V = setup
        img = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 256, 3))
        ref = np.asarray(V.forward(params, img, cfg, "semseg")[0],
                         np.float64).reshape(-1)
        qparams = quantize_tree(params)
        qcfg = replace(cfg, policy=ops.policy_named("xla_int8"))
        ops.reset_dispatch_report()
        out = np.asarray(V.forward(qparams, img, qcfg, "semseg")[0],
                         np.float64).reshape(-1)
        rep = ops.dispatch_report()
        for op in ("linear", "moe_grouped_gemm"):
            assert rep[op]["hits"].get("xla_int8", 0) >= 1, (op, rep[op])
            assert not rep[op]["fallbacks"], (op, rep[op])
        cos = ref @ out / (np.linalg.norm(ref) * np.linalg.norm(out))
        assert cos >= 0.999, cos


# ============================================== serving integration


class TestInt8KVServing:
    def test_engine_generates_with_int8_kv_hits(self):
        from dataclasses import replace

        from repro import configs
        from repro.models import model as M
        from repro.serve import ServeConfig, ServingEngine

        cfg = replace(configs.get("llama3_2_1b", smoke=True),
                      dtype="float32")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                     cfg.vocab_size)
        fp = ServingEngine(cfg, params, ServeConfig(max_len=32))
        out_fp = np.asarray(fp.generate(prompts, 6))
        ops.reset_dispatch_report()
        q = ServingEngine(cfg, params, ServeConfig(
            max_len=32, kv_quant="int8",
            policy=ops.policy_named("xla_int8")))
        out_q = np.asarray(q.generate(prompts, 6))
        rep = ops.dispatch_report()["attention_decode"]
        assert rep["hits"].get("xla_int8", 0) >= 1 and not rep["fallbacks"]
        # int8 KV error is far below the argmax decision margin here
        np.testing.assert_array_equal(out_fp, out_q)

    def test_chunked_prefill_through_quantized_cache(self):
        from dataclasses import replace

        from repro import configs
        from repro.models import model as M
        from repro.serve import ServeConfig, ServingEngine

        cfg = replace(configs.get("llama3_2_1b", smoke=True),
                      dtype="float32")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(jax.random.PRNGKey(2), (1, 11), 0,
                                     cfg.vocab_size)
        base = ServingEngine(cfg, params, ServeConfig(
            max_len=32, kv_quant="int8",
            policy=ops.policy_named("xla_int8")))
        chunked = ServingEngine(cfg, params, ServeConfig(
            max_len=32, kv_quant="int8", prefill_chunk=4,
            policy=ops.policy_named("xla_int8")))
        np.testing.assert_array_equal(
            np.asarray(base.generate(prompts, 5)),
            np.asarray(chunked.generate(prompts, 5)))


class TestQuantizedExpertPaging:
    def _moe(self):
        from repro.core.moe import MoEConfig, init_moe

        cfg = MoEConfig(d_model=32, d_ff=48, num_experts=8, top_k=2,
                        num_tasks=2, expert_kind="gelu",
                        capacity_factor=2.0, group_size=64, impl="grouped")
        params = init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        return cfg, params

    @pytest.mark.parametrize("bits", [8, 4])
    def test_paged_bitexact_with_apply_moe(self, bits):
        from repro.core.moe import apply_moe
        from repro.serve.expert_cache import PagedMoE

        cfg, params = self._moe()
        qparams = quantize_tree(params, bits=bits)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32),
                              jnp.float32)
        with ops.use_policy(ops.policy_named("xla_int8")):
            y_full, aux_full = apply_moe(qparams, cfg, x, task_id=1)
            paged = PagedMoE(qparams, cfg, resident_fraction=0.5)
            y_paged, aux_paged = paged(x, task_id=1)
        np.testing.assert_array_equal(np.asarray(y_full),
                                      np.asarray(y_paged))
        assert float(aux_full) == float(aux_paged)
        assert paged.cache.misses > 0           # it really paged

    def test_budget_holds_more_quantized_experts(self):
        from repro.serve.expert_cache import PagedMoE

        cfg, params = self._moe()
        fp = PagedMoE(params, cfg, resident_fraction=0.25)
        budget = fp.cache.max_resident * fp.cache._expert_bytes
        q8 = PagedMoE(quantize_tree(params), cfg, budget_bytes=budget)
        q4 = PagedMoE(quantize_tree(params, bits=4), cfg,
                      budget_bytes=budget)
        assert q8.cache.max_resident >= 3 * fp.cache.max_resident
        assert q4.cache.max_resident >= q8.cache.max_resident
