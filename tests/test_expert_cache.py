"""Expert-weight paging (serve/expert_cache.py).

The ISSUE-2 acceptance bit: the paged-expert forward pass is BIT-EXACT
with the all-resident ``core.moe.apply_moe`` forward, at any residency
fraction (waves of at most R experts accumulate into disjoint rows of the
combine buffer, so fp summation order never changes).  Plus LRU eviction
bookkeeping, demand hit/miss accounting, and usage-EMA prefetch.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import moe as moe_lib
from repro.serve.expert_cache import ExpertCache, ExpertUsage, PagedMoE


def _cfg(**kw):
    base = dict(d_model=32, d_ff=64, num_experts=8, top_k=2, num_tasks=2,
                capacity_factor=2.0, group_size=64, impl="grouped",
                expert_kind="gelu")
    base.update(kw)
    return moe_lib.MoEConfig(**base)


def _setup(cfg, dtype=jnp.bfloat16, seed=0, shape=(2, 50)):
    params = moe_lib.init_moe(jax.random.PRNGKey(seed), cfg, dtype=dtype)
    x = (jax.random.normal(jax.random.PRNGKey(seed + 1),
                           shape + (cfg.d_model,)) * 0.5).astype(dtype)
    return params, x


class TestPagedBitExact:
    @pytest.mark.parametrize("frac", [0.25, 0.5, 1.0])
    @pytest.mark.parametrize("kind", ["gelu", "swiglu"])
    def test_paged_equals_resident(self, frac, kind):
        cfg = _cfg(expert_kind=kind)
        params, x = _setup(cfg)
        for task in (0, 1):
            ref, aux_ref = moe_lib.apply_moe(params, cfg, x, task_id=task)
            paged = PagedMoE(params, cfg, resident_fraction=frac)
            y, aux = paged(x, task_id=task)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))
            np.testing.assert_allclose(float(aux), float(aux_ref),
                                       rtol=1e-6)

    def test_paged_with_shared_experts(self):
        cfg = _cfg(expert_kind="swiglu", num_shared_experts=1)
        params, x = _setup(cfg)
        ref, _ = moe_lib.apply_moe(params, cfg, x, task_id=1)
        y, _ = PagedMoE(params, cfg, resident_fraction=0.5)(x, task_id=1)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))

    def test_paged_nondivisible_token_count(self):
        """Group padding inside the paged path mirrors apply_moe."""
        cfg = _cfg(group_size=16)
        params, x = _setup(cfg, shape=(1, 23))   # 23 tokens, groups of 16
        ref, _ = moe_lib.apply_moe(params, cfg, x)
        y, _ = PagedMoE(params, cfg, resident_fraction=0.5)(x, task_id=0)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))

    def test_residency_stays_bounded(self):
        cfg = _cfg()
        params, x = _setup(cfg)
        paged = PagedMoE(params, cfg, resident_fraction=0.25)
        paged(x, task_id=0)
        paged(x, task_id=1)
        assert paged.cache.max_resident == 2
        assert len(paged.cache.resident) <= 2
        s = paged.cache.stats()
        assert s["resident_fraction"] == pytest.approx(0.25)
        assert s["bytes_paged"] > 0


class TestExpertCacheLRU:
    def _host(self, e=6):
        rng = np.random.default_rng(0)
        return {"w": rng.standard_normal((e, 4, 4)).astype(np.float32)}

    def test_demand_paging_and_eviction(self):
        cache = ExpertCache(self._host(), max_resident=3)
        cache.ensure([0, 1, 2])
        assert cache.misses == 3 and cache.hits == 0
        assert sorted(cache.resident) == [0, 1, 2]
        cache.ensure([1, 3])           # 1 hits; 3 evicts the LRU (0)
        assert cache.hits == 1 and cache.misses == 4
        assert cache.evictions == 1
        assert 0 not in cache.resident and 3 in cache.resident

    def test_slots_hold_correct_weights(self):
        host = self._host()
        cache = ExpertCache(host, max_resident=2)
        cache.ensure([4, 1])
        remap = cache.remap()
        slots = np.asarray(cache.slots["w"])
        for e in (4, 1):
            np.testing.assert_array_equal(slots[remap[e]], host["w"][e])

    def test_ensure_rejects_oversized_working_set(self):
        cache = ExpertCache(self._host(), max_resident=2)
        with pytest.raises(ValueError):
            cache.ensure([0, 1, 2])

    def test_prefetch_converts_misses_to_hits(self):
        cache = ExpertCache(self._host(), max_resident=3)
        cache.prefetch([0, 1, 2])      # not counted as demand traffic
        assert cache.hits == 0 and cache.misses == 0
        cache.ensure([0, 1, 2])
        assert cache.hits == 3 and cache.misses == 0

    def test_remap_sentinel_for_nonresident(self):
        """Non-resident experts map to -1, never to a live slot: mapping
        them to 0 silently aliased whatever expert occupied slot 0 for any
        caller that forgot to mask (the old behaviour)."""
        cache = ExpertCache(self._host(e=6), max_resident=2)
        cache.ensure([4, 1])
        remap = cache.remap()
        assert remap[4] >= 0 and remap[1] >= 0
        for e in (0, 2, 3, 5):
            assert remap[e] == -1, f"non-resident {e} must map to -1"
        # an evicted expert goes back to the sentinel
        cache.ensure([5, 1])           # 5 evicts the LRU (4)
        remap = cache.remap()
        assert remap[4] == -1 and remap[5] >= 0

    def test_prefetch_truncation_recorded(self):
        """A warm-up list longer than the slot count keeps the head and
        RECORDS the dropped tail (count + ids) instead of silently
        truncating."""
        cache = ExpertCache(self._host(e=6), max_resident=3)
        cache.prefetch([5, 0, 1, 2, 4])
        assert sorted(cache.resident) == [0, 1, 5]
        s = cache.stats()
        assert s["prefetch_truncated"] == 2
        assert s["prefetch_dropped"] == [2, 4]
        cache.prefetch([0, 1])         # within budget: no new accounting
        assert cache.stats()["prefetch_truncated"] == 2


class TestEvictedExpertRegression:
    def test_route_to_evicted_expert_stays_exact(self):
        """Regression for the remap slot-0 alias: route a batch to experts
        that were all EVICTED by the previous batch.  Before the -1
        sentinel, ``remap()`` sent non-resident ids to slot 0, so any
        unmasked dereference silently computed with whichever expert held
        slot 0; the paged forward must stay bit-exact with ``apply_moe``
        through the eviction."""
        cfg = _cfg(top_k=2)
        params, x = _setup(cfg, dtype=jnp.float32)
        # disjoint per-task working sets so task 1 fully evicts task 0's
        bias = np.full((2, cfg.num_experts), -30.0, np.float32)
        bias[0, :4] = 0.0
        bias[1, 4:] = 0.0
        params = dict(params, gate_bias=jnp.asarray(bias))
        paged = PagedMoE(params, cfg, resident_fraction=0.25)   # R = 2
        paged(x, task_id=0)             # resident ⊂ {0..3}
        paged(x, task_id=1)             # evicts them: resident ⊂ {4..7}
        remap = paged.cache.remap()
        assert all(remap[e] == -1 for e in range(4)), \
            "task-0 experts must be non-resident (sentinel) after eviction"
        ref, _ = moe_lib.apply_moe(params, cfg, x, task_id=0)
        y, _ = paged(x, task_id=0)      # routes to the evicted experts
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


class TestShardedCacheBookkeeping:
    """ShardedExpertCache on a 1-shard mesh: same bookkeeping contract as
    the single-device cache (the multi-shard paths run in the forced-
    host-device subprocess suite, tests/test_serve_dist.py)."""

    def _host(self, e=6):
        rng = np.random.default_rng(0)
        return {"w": rng.standard_normal((e, 4, 4)).astype(np.float32)}

    def _mesh(self):
        from repro.dist import make_mesh
        return make_mesh((1, 1), ("data", "model"))

    def test_single_shard_matches_expert_cache(self):
        from repro.serve.expert_cache import ShardedExpertCache

        host = self._host()
        cache = ShardedExpertCache(host, 3, self._mesh())
        assert cache.num_shards == 1 and cache.total_slots == 3
        cache.ensure([0, 1, 2])
        assert cache.misses == 3 and cache.hits == 0
        cache.ensure([1, 3])
        assert cache.hits == 1 and cache.evictions == 1
        remap = cache.remap()
        assert remap[0] == -1 and remap[3] >= 0
        slots = np.asarray(cache.slots["w"]).reshape(-1, 4, 4)
        for e in (1, 2, 3):
            np.testing.assert_array_equal(slots[remap[e]], host["w"][e])
        cache.prefetch([0, 1, 2, 4, 5])
        assert cache.stats()["prefetch_truncated"] == 2
        cache.reset_stats()
        assert cache.hits == 0 and cache.stats()["prefetch_truncated"] == 0


class TestExpertUsage:
    def test_ema_and_hot(self):
        u = ExpertUsage(num_experts=4, num_tasks=2, decay=0.5)
        u.update([10, 0, 0, 1], task_id=0)
        u.update([0, 8, 2, 0], task_id=1)
        assert u.hot(2, task_id=0) == [0, 3]
        assert u.hot(1, task_id=1) == [1]
        over = u.task_overlap()
        assert 0.0 <= over < 0.2        # near-disjoint usage

    def test_prefetch_drives_hit_rate(self):
        """Task-sparse routing + usage prefetch: after warmup, alternating
        tasks hit the cache instead of thrashing it."""
        cfg = _cfg(top_k=2)
        params, x = _setup(cfg, dtype=jnp.float32)
        # disjoint per-task expert subsets via the gate_bias hook
        bias = np.full((2, cfg.num_experts), -30.0, np.float32)
        bias[0, :4] = 0.0
        bias[1, 4:] = 0.0
        params = dict(params, gate_bias=jnp.asarray(bias))
        paged = PagedMoE(params, cfg, resident_fraction=0.5)
        for task in (0, 1, 0, 1):       # warm usage EMA + caches
            paged.prefetch(task)
            paged(x, task_id=task)
        c = paged.cache
        c.hits = c.misses = 0
        for task in (0, 1, 0, 1):
            paged.prefetch(task)
            paged(x, task_id=task)
        assert paged.cache.hit_rate == 1.0
        # and routing really was task-disjoint
        assert paged.usage.task_overlap() < 0.05


class TestPinnedAccounting:
    """Heterogeneous residency accounting (factored experts split every
    layer into a pinned shared basis + paged per-expert deltas): stats()
    report the two pools separately, paging traffic counts only the paged
    unit, and the byte budget sizes residency on paged bytes alone."""

    def _host(self, e=6):
        rng = np.random.default_rng(0)
        return {"w": rng.standard_normal((e, 4, 4)).astype(np.float32)}

    def test_stats_split_pinned_from_paged(self):
        pinned = {"w.basis": np.ones((4, 4), np.float32)}
        cache = ExpertCache(self._host(), max_resident=3, pinned=pinned)
        s = cache.stats()
        assert s["pinned_bytes"] == 64
        assert s["paged_expert_bytes"] == 64    # one (4,4) f32 per expert
        cache.ensure([0, 1])
        assert cache.stats()["bytes_paged"] == 2 * 64   # deltas only

    def test_pinned_leaves_live_on_device_untouched(self):
        host = self._host()
        basis = np.arange(16, dtype=np.float32).reshape(4, 4)
        cache = ExpertCache(host, max_resident=2,
                            pinned={"w.basis": basis})
        cache.ensure([0, 5])
        cache.ensure([3, 2])            # evictions never touch pinned
        np.testing.assert_array_equal(np.asarray(cache.pinned["w.basis"]),
                                      basis)

    def test_pinned_paged_name_clash_rejected(self):
        with pytest.raises(ValueError, match="pinned and paged"):
            ExpertCache(self._host(), max_resident=2,
                        pinned={"w": np.ones((4, 4), np.float32)})

    def test_budget_sizing_with_mixed_size_leaves(self):
        """Regression: the per-expert unit is the SUM across weight leaves
        of different sizes (w1/b1/w2/b2 in a gelu FFN) — sizing on any
        single leaf over- or under-counts residency."""
        cfg = _cfg(expert_kind="gelu")
        params, _ = _setup(cfg, dtype=jnp.float32)
        probe = PagedMoE(params, cfg, resident_fraction=1.0)
        per = probe.cache.stats()["paged_expert_bytes"]
        d, f = cfg.d_model, cfg.d_ff
        assert per == 4 * (d * f + f + f * d + d)   # f32 w1+b1+w2+b2
        for n in (2, 5):
            paged = PagedMoE(params, cfg, budget_bytes=n * per)
            assert paged.cache.max_resident == n
        # one byte short of n experts floors to n-1
        paged = PagedMoE(params, cfg, budget_bytes=3 * per - 1)
        assert paged.cache.max_resident == max(cfg.top_k, 2)
