"""Placement subsystem: plan/policy unit contracts + elastic end-to-end.

Three layers of guarantee:

  * **plan/policy units** (in-process) — ``PlacementPlan`` immutability,
    static-map bit-compatibility, validation; the policy registry; the
    extracted budget/fraction slot arithmetic; ``ExpertUsage.hot``'s
    deterministic tie-break; the ``reset_stats`` contract
    (``prefetch_dropped`` clears on both cache classes); ``drop``'s
    placement-not-eviction bookkeeping; ``ElasticPolicy.update`` as a
    pure host function (spread, replication, stability, no-op cases).
  * **skewed static serving** (subprocess, mesh 2/4) — 80/20-skewed
    routing through the refactored ``ShardedExpertCache`` stays
    BIT-EXACT with ``apply_moe``, and the new ``shard_load`` ledger
    exposes the imbalance the elastic policy exists to fix.
  * **elastic serving** (subprocess, mesh 2/4) — under the same skew the
    elastic policy swaps plans live (generations advance, migrations and
    replications fire, hot experts hold >1 replica) while every forward
    stays bit-exact with the dense reference; migration page-ins ride
    the transfer engine under the ``migrate`` tag.

Multi-device cases run in subprocesses with forced host devices, the
tests/test_serve_dist.py pattern.
"""

import subprocess
import sys
import textwrap

import numpy as np

import pytest

from repro.dist import make_mesh
from repro.serve.expert_cache import ExpertCache, ExpertUsage
from repro.serve.placement import (BudgetPolicy, ElasticPolicy, LRUPolicy,
                                   PlacementPlan, PlacementPolicy,
                                   StaticPolicy, budget_slots,
                                   fraction_slots, get_policy)

REPO = __file__.rsplit("/tests/", 1)[0]


def _run(script: str, timeout: int = 600) -> str:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                       cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return r.stdout


HEADER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import make_mesh
""")


# ---------------------------------------------------------------- plan units


def test_static_plan_is_the_modulo_partition():
    """``PlacementPlan.static`` reproduces ``owner(e) = e // (E/m)``
    bit-for-bit — the refactor's anchor invariant."""
    for E, m in ((8, 1), (8, 2), (8, 4), (16, 4)):
        plan = PlacementPlan.static(E, m)
        e_local = E // m
        for e in range(E):
            assert plan.owner(e) == e // e_local
            assert plan.shards_of(e) == (e // e_local,)
        assert plan.generation == 0
        assert plan.max_replicas == 1
        np.testing.assert_array_equal(plan.shard_expert_counts(),
                                      np.full(m, e_local))


def test_plan_validation_is_loud():
    with pytest.raises(ValueError, match="does not divide"):
        PlacementPlan.static(8, 3)
    with pytest.raises(ValueError, match="lists 2 experts"):
        PlacementPlan(3, 2, ((0,), (1,)))
    with pytest.raises(ValueError, match="no shard"):
        PlacementPlan(2, 2, ((0,), ()))
    with pytest.raises(ValueError, match="twice"):
        PlacementPlan(2, 2, ((0,), (1, 1)))
    with pytest.raises(ValueError, match="outside"):
        PlacementPlan(2, 2, ((0,), (2,)))


def test_plan_immutable_and_evolve_bumps_generation():
    plan = PlacementPlan.static(4, 2)
    with pytest.raises(AttributeError, match="immutable"):
        plan.generation = 7
    with pytest.raises(AttributeError, match="immutable"):
        plan.replicas = ()
    nxt = plan.evolve(((0, 1), (0,), (1,), (1,)))
    assert nxt.generation == plan.generation + 1
    assert nxt.max_replicas == 2
    assert nxt.shards_of(0) == (0, 1)
    # layout comparison ignores the generation (rebalance no-op check)
    again = nxt.evolve(nxt.replicas)
    assert again.generation == nxt.generation + 1
    assert again.same_layout(nxt) and not again.same_layout(plan)


# -------------------------------------------------------------- policy units


def test_policy_registry():
    assert isinstance(get_policy("static"), StaticPolicy)
    assert isinstance(get_policy("lru"), LRUPolicy)
    assert isinstance(get_policy("budget"), BudgetPolicy)
    assert isinstance(get_policy("elastic"), ElasticPolicy)
    assert isinstance(get_policy(None), StaticPolicy)
    inst = ElasticPolicy(rebalance_every=2)
    assert get_policy(inst) is inst       # instances pass through
    with pytest.raises(ValueError, match="unknown placement policy"):
        get_policy("round-robin")


def test_slot_sizing_arithmetic():
    """The extracted byte-budget / fraction slot math, including the
    pinned-leaves-first accounting of the factored path."""
    # 10 expert-slots' worth of budget, no pinned overhead
    assert budget_slots(1000, 100, 0, floor=1) == 10
    # pinned basis is paid FIRST: 400 pinned leaves 600 => 6 slots
    assert budget_slots(1000, 100, 400, floor=1) == 6
    # budget smaller than the pinned store still yields the floor
    assert budget_slots(300, 100, 400, floor=2) == 2
    assert fraction_slots(0.5, 8, floor=1) == 4
    assert fraction_slots(0.1, 8, floor=1) == 1      # ceil, then floor
    assert fraction_slots(0.0, 8, floor=2) == 2
    # the policy object routes budget-vs-fraction the same way
    kw = dict(per_expert_bytes=100, pinned_bytes=0, experts_per_shard=8,
              resident_fraction=0.5, floor=1)
    assert StaticPolicy().slots(**kw) == 4
    assert get_policy("budget", budget_bytes=1000).slots(**kw) == 10
    with pytest.raises(ValueError, match="needs a byte budget"):
        BudgetPolicy().slots(**kw)


def test_usage_hot_deterministic_tie_break():
    """Equal-EMA experts rank by ascending id, explicitly — prefetch and
    elastic placement both require platform-independent order."""
    u = ExpertUsage(6, num_tasks=1, decay=0.0)
    u.update([5, 5, 9, 5, 9, 5])
    assert u.hot(6) == [2, 4, 0, 1, 3, 5]
    assert u.hot(3) == [2, 4, 0]
    # all-zero EMA (no routing yet): pure id order
    assert ExpertUsage(4).hot(4) == [0, 1, 2, 3]
    # per-task view ties break the same way
    u2 = ExpertUsage(4, num_tasks=2, decay=0.0)
    u2.update([1, 1, 0, 0], task_id=1)
    assert u2.hot(4, task_id=1) == [0, 1, 2, 3]


def test_elastic_policy_victim_and_ranking_inherit_base():
    """Elastic changes OWNERSHIP only — victim selection and prefetch
    ranking stay the extracted LRU/usage-hot behaviour."""
    from collections import OrderedDict
    pol = ElasticPolicy()
    lru = OrderedDict([(3, 0), (1, 1), (5, 2)])
    assert pol.victim(lru, pinned={3}) == 1
    assert pol.victim(lru, pinned=set()) == 3
    u = ExpertUsage(4, decay=0.0)
    u.update([0, 7, 0, 7])
    assert pol.prefetch_ranking(u, 2) == [1, 3]


# --------------------------------------------------------- cache bookkeeping


def _toy_host(E=8, d=4):
    rng = np.random.default_rng(0)
    return {"w": rng.standard_normal((E, d, d)).astype(np.float32)}


def test_reset_stats_clears_prefetch_dropped():
    """Satellite contract: ``reset_stats`` clears the truncation evidence
    (count AND the dropped-id deque) so per-interval serving reports
    never carry a previous interval's drops."""
    cache = ExpertCache(_toy_host(), max_resident=2)
    cache.prefetch(range(8))            # 6 ids over the 2-slot bank
    assert cache.prefetch_truncated == 6
    assert list(cache.prefetch_dropped) == [2, 3, 4, 5, 6, 7]
    assert cache.stats()["prefetch_dropped"] == [2, 3, 4, 5, 6, 7]
    cache.reset_stats()
    assert cache.prefetch_truncated == 0
    assert list(cache.prefetch_dropped) == []
    assert cache.stats()["prefetch_dropped"] == []
    # dropped ids accumulate again after the reset (deque survives)
    cache.prefetch([7, 6, 5])
    assert list(cache.prefetch_dropped) == [5]


def test_sharded_reset_stats_clears_books_and_load():
    """The sharded form resets every shard book (incl. dropped ids) and
    the per-interval load ledger; placement history is cumulative."""
    import jax
    from repro.serve.expert_cache import ShardedExpertCache
    mesh = make_mesh((1, 1), ("data", "model"))
    cache = ShardedExpertCache(_toy_host(), 2, mesh)
    cache.prefetch(range(8))
    assert cache.prefetch_truncated == 6
    cache.record_load([4, 0, 0, 0, 0, 0, 0, 0])
    assert cache.shard_load_imbalance() == 1.0      # m=1: trivially even
    cache.reset_stats()
    assert cache.prefetch_truncated == 0
    assert all(not b.prefetch_dropped for b in cache.books)
    assert cache.shard_load.sum() == 0.0
    assert cache.shard_load_imbalance() == 0.0


def test_drop_is_placement_not_eviction():
    cache = ExpertCache(_toy_host(), max_resident=4)
    cache.ensure([0, 1, 2])
    assert sorted(cache.resident) == [0, 1, 2]
    assert cache.drop(1) is True
    assert sorted(cache.resident) == [0, 2]
    assert cache.evictions == 0          # a move, not a capacity eviction
    assert cache.drop(1) is False        # already gone
    assert cache.drop(7) is False        # never resident
    cache.ensure([0, 2], record=False)   # survivors still hit
    assert cache.misses == 3             # only the original page-ins


def test_single_device_replica_table_degenerates():
    cache = ExpertCache(_toy_host(), max_resident=3)
    cache.ensure([2, 5])
    table, counts = cache.replica_table()
    assert table.shape == (8, 1)
    np.testing.assert_array_equal(counts, (cache.remap() >= 0))
    np.testing.assert_array_equal(table[:, 0], cache.remap())


# ------------------------------------------------------ elastic policy logic


def _usage_with(ema_row):
    u = ExpertUsage(len(ema_row), num_tasks=1, decay=0.0)
    u.update(ema_row)
    return u


def test_elastic_update_spreads_hot_block():
    """The adversarial skew: every active expert lives on shard 0 under
    the static map.  The proposal deals them across all shards."""
    plan = PlacementPlan.static(8, 4)
    pol = ElasticPolicy(replicate_factor=100.0)      # replication off
    usage = _usage_with([40, 30, 0, 0, 0, 0, 0, 0])  # both on shard 0
    new = pol.update(plan, usage, np.zeros(4), slots_per_shard=2)
    assert new is not None and new.generation == 1
    # hottest-first greedy LPT: the two actives land on different shards
    assert new.owner(0) != new.owner(1)
    # inactive experts keep their static homes (no churn)
    for e in range(2, 8):
        assert new.shards_of(e) == plan.shards_of(e)
    # stability: the same evidence against the new plan is a no-op
    assert pol.update(new, usage, np.zeros(4), slots_per_shard=2) is None


def test_elastic_update_replicates_dominant_expert():
    plan = PlacementPlan.static(8, 4)
    pol = ElasticPolicy(replicate_factor=2.0)
    usage = _usage_with([97, 1, 1, 1, 0, 0, 0, 0])
    new = pol.update(plan, usage, np.zeros(4), slots_per_shard=2)
    assert new is not None
    assert len(new.shards_of(0)) == 4        # hot: replicated everywhere
    for e in (1, 2, 3):
        assert len(new.shards_of(e)) == 1    # warm: single home
    assert new.max_replicas == 4
    # deterministic: identical evidence proposes the identical layout
    again = pol.update(plan, usage, np.zeros(4), slots_per_shard=2)
    assert again.replicas == new.replicas


def test_elastic_update_no_op_cases():
    pol = ElasticPolicy()
    # single shard: nothing to balance
    assert pol.update(PlacementPlan.static(8, 1), _usage_with([9] * 8),
                      np.zeros(1), slots_per_shard=8) is None
    # no routing evidence yet
    assert pol.update(PlacementPlan.static(8, 4), _usage_with([0] * 8),
                      np.zeros(4), slots_per_shard=2) is None


def test_elastic_respects_bank_capacity():
    """More active experts than one shard's bank: the greedy deal never
    overfills a bank (each shard gets at most ``slots_per_shard``)."""
    plan = PlacementPlan.static(8, 2)
    pol = ElasticPolicy(replicate_factor=100.0)
    usage = _usage_with([8, 7, 6, 5, 4, 3, 2, 1])    # all active
    new = pol.update(plan, usage, np.zeros(2), slots_per_shard=4)
    counts = new.shard_expert_counts() if new is not None \
        else plan.shard_expert_counts()
    assert counts.max() <= 4


# ----------------------------------------------------- subprocess: skew e2e


SKEWED_STATIC = HEADER + textwrap.dedent("""
    # satellite: 80/20-skewed routing through the refactored sharded
    # cache — static placement must stay BIT-EXACT with apply_moe, and
    # the new shard_load ledger must expose the imbalance (the hot
    # experts all live in shard 0's static block)
    import json
    from repro.core import moe as moe_lib
    from repro.serve.expert_cache import PagedMoE

    cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                            num_tasks=1, capacity_factor=2.0, group_size=64,
                            impl="grouped", expert_kind="swiglu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    # gate bias drives ~all routing mass onto experts {0, 1} (shard 0 at
    # every mesh size) — the adversarial case for the static partition
    bias = np.full((1, 8), -40.0, np.float32)
    bias[0, :2] = 0.0
    params = dict(params, gate_bias=jnp.asarray(bias))
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32))
         * 0.5).astype(jnp.float32)
    ref, aref = moe_lib.apply_moe(params, cfg, x, task_id=0)
    out = {}
    for m in (2, 4):
        mesh = make_mesh((1, m), ("data", "model"))
        paged = PagedMoE(params, cfg, resident_fraction=0.5, mesh=mesh,
                         placement="static")
        y, aux = paged(x, task_id=0)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref),
                                      err_msg=f"mesh={m} skewed static")
        assert abs(float(aux) - float(aref)) < 1e-6
        s = paged.cache.stats()
        assert s["placement"]["policy"] == "static"
        assert s["placement"]["generation"] == 0
        assert s["placement"]["plan_swaps"] == 0
        load = np.asarray(s["shard_load"])
        assert load.shape == (m,)
        # the skew concentrates the routed tokens on shard 0
        assert load[0] > 0.9 * load.sum(), load
        assert s["shard_load_imbalance"] > 0.9 * m
        out[m] = s["shard_load_imbalance"]
    print("SKEWED_STATIC_OK", json.dumps(out))
""")


ELASTIC_SKEW = HEADER + textwrap.dedent("""
    # the tentpole end-to-end: elastic placement under 80/20 skew at mesh
    # 2 and 4.  Live plan swaps (migration + replication) must keep every
    # forward bit-exact with the dense reference while spreading the
    # recorded shard load
    from repro.core import moe as moe_lib
    from repro.serve.expert_cache import PagedMoE
    from repro.serve.placement import ElasticPolicy

    # capacity_factor 4.0: the dominant expert's full token load fits in
    # capacity, so the usage EMA sees the true 2:1:1 skew (a tight
    # capacity CLIPS the dropped tokens out of the routing stats and
    # flattens the very signal the elastic policy thresholds on)
    cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, num_experts=8, top_k=2,
                            num_tasks=1, capacity_factor=4.0, group_size=64,
                            impl="grouped", expert_kind="swiglu")
    params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    # expert 0 dominates (every token's first slot); experts 1 and 2
    # split the second slot; the rest are cold.  v0 ~ 2x the mean active
    # load, so it crosses the replication threshold at every mesh size
    bias = np.full((1, 8), -40.0, np.float32)
    bias[0, 0] = 0.0
    bias[0, 1:3] = -2.0
    params = dict(params, gate_bias=jnp.asarray(bias))
    xs = [(jax.random.normal(jax.random.PRNGKey(7 + i), (2, 50, 32))
           * 0.5).astype(jnp.float32) for i in range(6)]
    refs = [moe_lib.apply_moe(params, cfg, x, task_id=0)[0] for x in xs]
    for m in (2, 4):
        mesh = make_mesh((1, m), ("data", "model"))
        pol = ElasticPolicy(rebalance_every=2, replicate_factor=1.2)
        paged = PagedMoE(params, cfg, resident_fraction=0.5, mesh=mesh,
                         placement=pol)
        for i, x in enumerate(xs):
            y, _ = paged(x, task_id=0)
            np.testing.assert_array_equal(
                np.asarray(y), np.asarray(refs[i]),
                err_msg=f"mesh={m} forward={i} (gen="
                        f"{paged.cache.plan.generation})")
        s = paged.cache.stats()
        p = s["placement"]
        assert p["policy"] == "elastic"
        # the plan really moved: generations advanced, residency migrated
        assert p["plan_swaps"] >= 1, p
        assert p["generation"] >= 1, p
        assert p["migrations"] >= 1, p
        # the dominant experts replicated across shards
        assert p["max_replicas"] >= 2, p
        assert p["replications"] >= 1, p
        assert p["table_width"] == m
        # replica load-splitting spreads the recorded shard load: far
        # from the all-on-one-shard static imbalance (~m)
        assert s["shard_load_imbalance"] < 0.75 * m, s
        print(f"mesh={m} gen={p['generation']} swaps={p['plan_swaps']} "
              f"migr={p['migrations']} repl={p['replications']} "
              f"imb={s['shard_load_imbalance']:.2f}")
    print("ELASTIC_SKEW_OK")
""")


MIGRATE_TAG = HEADER + textwrap.dedent("""
    # plan swaps ride the double-buffered transfer machinery: set_plan
    # submits the new homes' page-ins tagged 'migrate' (non-blocking),
    # and the per-tag ledger accounts them separately from demand paging
    import numpy as _np
    from repro.serve.expert_cache import ShardedExpertCache
    from repro.serve.placement import ElasticPolicy, PlacementPlan
    from repro.serve.transfer import FakeTransferEngine

    mesh = make_mesh((1, 2), ("data", "model"))
    rng = _np.random.default_rng(0)
    host = {"w": rng.standard_normal((8, 4, 4)).astype(_np.float32)}
    eng = FakeTransferEngine(latency_s=0.05, timeout_s=5.0)
    # an elastic policy widens the replica table to m (a static cache
    # rejects replicating plans by construction — table_width 1)
    cache = ShardedExpertCache(host, 8, mesh, transfer_engine=eng,
                               policy=ElasticPolicy(),
                               plan=PlacementPlan.static(8, 2))
    cache.ensure(range(8))
    assert sorted(cache.resident) == list(range(8))
    before = eng.stats.tags_dict()
    assert "migrate" not in before and before["demand"]["submitted"] == 8

    # swap: expert 0 replicates onto shard 1, expert 7 migrates to shard 0
    reps = [(0, 1)] + [(0,) if e < 4 else (1,) for e in range(1, 8)]
    reps[7] = (0,)
    cache.set_plan(cache.plan.evolve(tuple(reps)))
    assert cache.plan.generation == 1
    assert cache.migrations == 2          # 0->shard1, 7->shard0
    assert cache.migration_drops == 1     # 7 left shard 1
    assert cache.replications == 1        # expert 0 grew a replica
    tags = eng.stats.tags_dict()
    assert tags["migrate"]["submitted"] == 2, tags
    assert tags["migrate"]["fenced"] == 0         # still in flight
    # the next ensure fences the migrated copies at their point of use
    cache.ensure(range(8))
    tags = eng.stats.tags_dict()
    assert tags["migrate"]["fenced"] == 2, tags
    table, counts = cache.replica_table()
    assert counts[0] == 2 and counts[7] == 1
    assert (counts[1:7] == 1).all()
    print("MIGRATE_TAG_OK")
""")


def test_skewed_static_bit_exact_and_load_visible():
    assert "SKEWED_STATIC_OK" in _run(SKEWED_STATIC)


def test_elastic_skew_bit_exact_with_live_rebalancing():
    assert "ELASTIC_SKEW_OK" in _run(ELASTIC_SKEW)


def test_migration_rides_transfer_engine_with_tag():
    assert "MIGRATE_TAG_OK" in _run(MIGRATE_TAG)
