"""Technique ⑤ — expert-by-expert reordering: queues, metaqueue, combine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import routing as R


class TestRouteTopK:
    def test_topk_selects_highest(self, rng):
        logits = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
        expert, gate, probs = R.route_topk(logits, k=2)
        want = np.argsort(-np.asarray(probs), axis=-1)[:, :2]
        np.testing.assert_array_equal(np.sort(expert, -1), np.sort(want, -1))

    def test_renormalized_gates_sum_to_one(self, rng):
        logits = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
        _, gate, _ = R.route_topk(logits, k=3, renormalize=True)
        np.testing.assert_allclose(np.asarray(gate).sum(-1), 1.0, rtol=1e-5)

    def test_uses_online_softmax(self, rng):
        logits = jnp.asarray(rng.normal(size=(4, 6)) * 40, jnp.float32)
        _, _, probs = R.route_topk(logits, k=1)
        np.testing.assert_allclose(np.asarray(probs),
                                   np.asarray(jax.nn.softmax(logits, -1)),
                                   atol=1e-6)


class TestQueues:
    """build_dispatch constructs the paper's per-expert token queues."""

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 6), st.integers(1, 3), st.integers(4, 40),
           st.integers(0, 1000))
    def test_positions_are_arrival_order_queues(self, e, k, t, seed):
        rng = np.random.default_rng(seed)
        expert = jnp.asarray(rng.integers(0, e, size=(t, k)), jnp.int32)
        position, valid = R.build_dispatch(expert, e, capacity=t * k)
        pos = np.asarray(position)
        exp = np.asarray(expert)
        assert np.asarray(valid).all()           # capacity == all fit
        # property: within each expert, positions are 0..len-1, unique, and
        # increase in token order (the arrival-order queue)
        for ee in range(e):
            ps = pos.reshape(-1)[exp.reshape(-1) == ee]
            assert sorted(ps.tolist()) == list(range(len(ps)))
            assert (np.diff(ps) > 0).all()       # arrival order preserved

    def test_capacity_drops_overflow(self):
        expert = jnp.zeros((10, 1), jnp.int32)     # all to expert 0
        position, valid = R.build_dispatch(expert, 4, capacity=6)
        assert int(valid.sum()) == 6
        assert bool(valid[:6].all()) and not bool(valid[6:].any())


class TestDispatchCombine:
    def test_grouped_equals_onehot(self, rng):
        t, d, e, k, cap = 32, 16, 4, 2, 32
        x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
        logits = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
        r = R.route(logits, k, cap)
        b1 = R.dispatch(x, r, e, cap)
        b2 = R.dispatch_onehot(x, r, e, cap)
        np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), atol=1e-6)
        out = jnp.tanh(b1)
        y1 = R.combine(out, r)
        y2 = R.combine_onehot(out, r)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)

    def test_identity_experts_reconstruct_input(self, rng):
        """If every expert is the identity and gates sum to 1, combine ∘
        dispatch == identity — the queues lose no tokens."""
        t, d, e, k = 16, 8, 4, 2
        x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
        logits = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
        r = R.route(logits, k, capacity=t * k)
        buf = R.dispatch(x, r, e, t * k)
        y = R.combine(buf, r)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)

    def test_dropped_tokens_get_zero(self, rng):
        t, d, e = 8, 4, 2
        x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
        expert = jnp.zeros((t, 1), jnp.int32)
        gate = jnp.ones((t, 1), jnp.float32)
        position, valid = R.build_dispatch(expert, e, capacity=4)
        r = R.Routing(expert=expert, gate=gate, position=position,
                      valid=valid, probs=jnp.ones((t, e)) / e)
        buf = R.dispatch(x, r, e, 4)
        y = R.combine(buf, r)
        np.testing.assert_allclose(np.asarray(y[:4]), np.asarray(x[:4]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(y[4:]), 0.0, atol=1e-6)


class TestMetaqueue:
    def test_empty_expert_skipped(self, rng):
        """Metaqueue: experts with empty queues contribute nothing and the
        grouped-GEMM kernel skips them (group size 0)."""
        t, e = 12, 4
        logits = jnp.where(
            jnp.arange(e)[None, :] == 2, -1e9,
            jnp.asarray(rng.normal(size=(t, e)), jnp.float32))
        r = R.route(logits, 1, capacity=t)
        sizes = np.zeros(e, np.int64)
        for ee in np.asarray(r.expert).reshape(-1):
            sizes[ee] += 1
        assert sizes[2] == 0                     # never selected


class TestLoadBalance:
    def test_uniform_is_minimal(self):
        t, e = 64, 8
        probs = jnp.ones((t, e)) / e
        expert = jnp.asarray(np.arange(t) % e, jnp.int32)[:, None]
        uniform = float(R.load_balance_loss(probs, expert, e))
        skew = jnp.zeros((t, 1), jnp.int32)
        probs_skew = jnp.zeros((t, e)).at[:, 0].set(1.0)
        skewed = float(R.load_balance_loss(probs_skew, skew, e))
        assert abs(uniform - 1.0) < 1e-5
        assert skewed > uniform * 2

    def test_matches_f64_sum(self, rng):
        """The aux loss is an f32-exact sum: within a few f32 ulps of the
        same statistics summed in float64, masked rows excluded."""
        t, e, k = 96, 16, 4
        logits = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
        expert, _, probs = R.route_topk(logits, k)
        mask = jnp.asarray(rng.random(t) > 0.25)
        got = float(R.load_balance_loss(probs, expert, e, mask=mask))
        p64 = np.asarray(probs, np.float64)
        m64 = np.asarray(mask, np.float64)
        counts = np.zeros(e)
        np.add.at(counts, np.asarray(expert).reshape(-1), np.repeat(m64, k))
        denom = m64.sum()
        want = e * np.sum(counts / (denom * k) * (m64 @ p64 / denom))
        np.testing.assert_allclose(got, want, rtol=1e-6)


class TestCombineRows:
    def test_matches_f64_weighted_sum(self, rng):
        t, k, d = 24, 4, 32
        r = R.route(jnp.asarray(rng.normal(size=(t, 8)), jnp.float32), k,
                    capacity=t * k)
        rows = jnp.asarray(rng.normal(size=(t * k, d)), jnp.float32)
        got = np.asarray(R.combine_rows(rows, r))
        w = np.asarray(r.gate, np.float64) * np.asarray(r.valid)
        want = np.einsum("tk,tkd->td", w,
                         np.asarray(rows, np.float64).reshape(t, k, d))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_eager_equals_jit(self, rng):
        t, k, d = 24, 4, 32
        r = R.route(jnp.asarray(rng.normal(size=(t, 8)), jnp.float32), k,
                    capacity=t * k)
        rows = jnp.asarray(rng.normal(size=(t * k, d)), jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(R.combine_rows(rows, r), np.float32),
            np.asarray(jax.jit(R.combine_rows)(rows, r), np.float32))
