"""Expert-weight paging: bounded device residency for MoE expert weights.

The software analogue of Edge-MoE's DDR expert streaming (§IV-D): device
memory holds only a bounded set of expert weights (a configurable fraction
of E); the rest live in host memory and are paged in on demand.  Three
pieces:

  * ``ExpertUsage``   — per-task EMA of the router's per-expert dispatch
    counts (exported by ``core/moe.py`` via ``return_stats`` /
    ``routing.dispatch_counts``).  This is the prediction signal: the
    paper's task-level sparsity means each task concentrates its routing
    mass on a stable expert subset, so usage history predicts the next
    batch's working set.
  * ``ExpertCache``   — the residency manager: fixed device slot arrays
    (R stacked weight tensors per projection), LRU eviction, demand paging
    with hit/miss/byte accounting, and usage-driven prefetch.
  * ``PagedMoE``      — a serve-time MoE layer that routes on device, pages
    the needed experts, and runs the expert FFN in *waves* of at most R
    resident experts.  Wave outputs land in a per-(token, slot) row buffer
    (disjoint across waves) and the final gate-weighted combine sums the
    rows in exactly the same order as ``core.moe.apply_moe`` — the paged
    forward is **bit-exact** with the all-resident forward (tested).
  * ``ShardedExpertCache`` — the expert-parallel form: experts are
    partitioned over a mesh axis (``model``), each shard owns a bounded
    slot bank for ITS experts only, and the device store is one stacked
    ``(shards, R, ...)`` array sharded over that axis.  A fixed per-device
    slot budget therefore scales total resident experts linearly with the
    shard count — the distributed inversion of the paper's "load each
    expert once": experts stay put and the ``(E, C, d)`` dispatch buffers
    move through the all-to-all that GSPMD derives from the one-hot
    dispatch einsums.  ``PagedMoE(mesh=...)`` switches to this path; it
    stays bit-exact with the single-device forward (tested at mesh 2/4).
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import routing as R
from repro.core.moe import (MoEConfig, _expert_ffn, expert_param_names,
                            group_shape)
from repro.core.unified_linear import unified_linear
from repro.dist.sharding import ep_dispatch_sharding
from repro.factor import FactoredTensor, is_factored
from repro.quant import QTensor, is_qtensor
from repro.serve.placement import PlacementPlan, PlacementPolicy, get_policy
from repro.serve.transfer import Transfer

__all__ = ["ExpertUsage", "ExpertCache", "ShardedExpertCache", "PagedMoE"]

# how many truncation-dropped prefetch ids each cache retains as evidence
# (bounded so a long-running server cannot grow the list without limit)
PREFETCH_DROPPED_KEEP = 64


def _per_expert_bytes(host: dict) -> int:
    """Device bytes one expert occupies across the PAGED weight leaves —
    the unit of both paging accounting and byte-budget residency sizing.
    Pinned leaves (a factored layer's shared basis) are deliberately
    absent from ``host``: they are resident once, not per expert, and are
    accounted separately (:func:`_pinned_bytes`)."""
    return sum(int(w[0].nbytes) for w in host.values())


def _pinned_bytes(pinned: Optional[dict]) -> int:
    """Device bytes of the always-resident (never paged) leaves."""
    return sum(int(v.nbytes) for v in (pinned or {}).values())


class ExpertUsage:
    """Per-task EMA + cumulative totals of per-expert dispatch counts."""

    def __init__(self, num_experts: int, num_tasks: int = 1,
                 decay: float = 0.9):
        self.num_experts = num_experts
        self.num_tasks = max(1, num_tasks)
        self.decay = decay
        self.ema = np.zeros((self.num_tasks, num_experts), np.float64)
        self.totals = np.zeros((self.num_tasks, num_experts), np.int64)

    def update(self, counts, task_id: int = 0) -> None:
        c = np.asarray(counts, np.float64).reshape(-1)
        if c.size != self.num_experts:
            raise ValueError(f"counts size {c.size} != E={self.num_experts}")
        self.ema[task_id] = self.decay * self.ema[task_id] \
            + (1.0 - self.decay) * c
        self.totals[task_id] += c.astype(np.int64)

    def hot(self, k: int, task_id: Optional[int] = None) -> list[int]:
        """Top-k expert ids by EMA usage (one task, or summed over tasks).

        Ties break by expert id, EXPLICITLY (lexsort keys, not argsort
        order): prefetch ranking and elastic placement both consume this
        list, and both must be deterministic across platforms."""
        v = self.ema[task_id] if task_id is not None else self.ema.sum(axis=0)
        order = np.lexsort((np.arange(v.size), -v))
        return [int(e) for e in order[:k]]

    def task_overlap(self) -> float:
        """Mean pairwise cosine similarity of per-task usage — low values
        are the paper's task-level sparsity (disjoint working sets)."""
        if self.num_tasks < 2:
            return 1.0
        sims = []
        for a in range(self.num_tasks):
            for b in range(a + 1, self.num_tasks):
                u, v = self.totals[a].astype(float), self.totals[b].astype(float)
                n = np.linalg.norm(u) * np.linalg.norm(v)
                sims.append(float(u @ v / n) if n else 1.0)
        return float(np.mean(sims))


class ExpertCache:
    """Bounded device slots over a host-resident (E, ...) weight store.

    ``host``: {name: (E, ...) np.ndarray} — the per-expert weight tensors
    (``expert_param_names`` order).  ``max_resident`` slots are allocated on
    device; ``ensure`` demand-pages, ``prefetch`` warms without touching the
    demand hit/miss counters.

    With a ``transfer_engine`` (``serve/transfer.py``) the cache pages
    asynchronously: ``prefetch_async`` *submits* non-blocking host→device
    copies and returns immediately (the slot is reserved and the expert
    tracked in-flight), ``ensure`` *fences* any in-flight member before
    the caller dereferences it, and demand misses submit-then-fence so
    even unpredicted paging flows through the same accounted stream.
    Evicting an in-flight expert cancels its transfer — the slot's next
    occupant can never be clobbered by a late completion (double-buffer
    slot-reuse ordering; tested under adversarial completion schedules).
    Without an engine every code path is the PR-2 synchronous one,
    unchanged.
    """

    def __init__(self, host: dict[str, np.ndarray], max_resident: int,
                 usage: Optional[ExpertUsage] = None,
                 write_cb: Optional[Callable[[int, dict], None]] = None,
                 transfer_engine=None, label: str = "cache",
                 pinned: Optional[dict] = None,
                 policy: Optional[PlacementPolicy] = None):
        if not host:
            raise ValueError("empty expert weight store")
        # all residency DECISIONS (victim pick, prefetch ranking) live in
        # the policy; this class is mechanism — slots, copies, commits
        self.policy = policy if policy is not None else get_policy("static")
        # pinned leaves (e.g. a factored layer's shared basis) are put on
        # device ONCE here and never enter the slot store, LRU, or paging
        # byte accounting — they have no per-expert axis
        pinned = pinned or {}
        clash = set(pinned) & set(host)
        if clash:
            raise ValueError(f"leaves both pinned and paged: {sorted(clash)}")
        self.pinned = {n: jnp.asarray(v) for n, v in pinned.items()}
        self.pinned_bytes = _pinned_bytes(self.pinned)
        # transfer keys are (label, expert) — stable and test-addressable
        # (a FakeTransferEngine ``schedule`` can name them ahead of time)
        self.label = label
        self.names = tuple(host)
        self.num_experts = next(iter(host.values())).shape[0]
        for n, w in host.items():
            if w.shape[0] != self.num_experts:
                raise ValueError(f"{n}: leading dim {w.shape[0]} != E")
        self.max_resident = max(1, min(int(max_resident), self.num_experts))
        self.host = {n: np.asarray(w) for n, w in host.items()}
        self.usage = usage
        self._write_cb = write_cb
        if write_cb is None:
            # device slot store: one stacked (R, ...) tensor per weight name
            self.slots = {
                n: jnp.zeros((self.max_resident,) + w.shape[1:], w.dtype)
                for n, w in self.host.items()
            }
            def expert_slot_write(slots, new, r):
                return {n: slots[n].at[r].set(new[n]) for n in slots}

            self._write = jax.jit(expert_slot_write, donate_argnums=(0,))
            # batched variant: one donated store update for a whole fence
            # wave.  While compute holds the slots buffers the runtime
            # cannot donate in place and falls back to a copy — paying
            # that once per wave instead of once per expert is what keeps
            # the async stream cheaper than it hides.  The per-expert
            # rows go in as separate args (no host-side stack): the sets
            # fuse into one scatter-like update inside the jit

            def _write_many(slots, idx, *rows):
                for i, r in enumerate(rows):
                    slots = {n: slots[n].at[idx[i]].set(r[n])
                             for n in slots}
                return slots

            self._write_many = jax.jit(_write_many, donate_argnums=(0,))
            # full-overwrite variant: a fence wave that replaces EVERY
            # slot (the steady state when wave size == R) builds the new
            # store straight from the payload rows — no read of, or
            # donation dependency on, the old buffers, so the commit
            # never has to wait for (or copy around) in-flight compute
            # that still holds them
            def _write_full(*rows):
                return {n: jnp.stack([r[n] for r in rows])
                        for n in self.names}

            self._write_full = jax.jit(_write_full)
        else:
            # bookkeeping-only mode: the slot store lives elsewhere (one
            # shard bank of a ShardedExpertCache); page-ins go through the
            # callback, which writes host rows into the external store
            self.slots = None
            self._write = None
            self._write_many = None
            self._write_full = None
        self._slot_expert = [-1] * self.max_resident     # slot -> expert id
        self._lru: OrderedDict[int, int] = OrderedDict()  # expert -> slot
        self.engine = transfer_engine
        # expert -> (slot, Transfer): slot reserved, copy not yet committed
        self._inflight: dict[int, tuple[int, Transfer]] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.bytes_paged = 0
        self.page_ins = 0             # committed copies, demand or prefetch
        self.async_prefetches = 0     # transfers submitted by prefetch_async
        self.inflight_joins = 0       # in-flight transfers fenced by ensure
        self.async_cancelled = 0      # in-flight prefetches killed by evict
        self.prefetch_truncated = 0       # ids dropped by over-long prefetch
        # dropped ids ACCUMULATE (bounded) — a multi-wave run must not lose
        # earlier truncation evidence to the latest prefetch call
        self.prefetch_dropped: deque[int] = deque(maxlen=PREFETCH_DROPPED_KEEP)
        self._expert_bytes = _per_expert_bytes(self.host)

    # -------------------------------------------------------------- state

    @property
    def resident(self) -> list[int]:
        """Experts holding a slot — committed OR reserved by an in-flight
        prefetch (wave planning treats an arriving expert as warm; its
        copy is fenced before any dereference)."""
        return [e for e in self._slot_expert if e >= 0]

    @property
    def inflight(self) -> list[int]:
        """Experts whose copy has been submitted but not yet fenced."""
        return list(self._inflight)

    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 1.0

    def reset_stats(self) -> None:
        self.hits = self.misses = self.evictions = self.bytes_paged = 0
        self.page_ins = 0
        self.async_prefetches = self.inflight_joins = 0
        self.async_cancelled = 0
        self.prefetch_truncated = 0
        self.prefetch_dropped.clear()

    def stats(self) -> dict[str, Any]:
        out = {
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions, "bytes_paged": self.bytes_paged,
            "page_ins": self.page_ins,
            "hit_rate": self.hit_rate,
            "max_resident": self.max_resident,
            "resident_fraction": self.max_resident / self.num_experts,
            "prefetch_truncated": self.prefetch_truncated,
            "prefetch_dropped": list(self.prefetch_dropped),
            # heterogeneous residency accounting: paged bytes scale with
            # the slot count, pinned bytes are paid once (factored basis)
            "paged_expert_bytes": self._expert_bytes,
            "pinned_bytes": self.pinned_bytes,
        }
        if self.engine is not None:
            out.update({
                "async_prefetches": self.async_prefetches,
                "inflight_joins": self.inflight_joins,
                "async_cancelled": self.async_cancelled,
                "inflight": len(self._inflight),
                "stall_s": self.engine.stats.stall_s,
                "overlap_ratio": self.engine.stats.overlap_ratio,
            })
        return out

    # ------------------------------------------------------------- paging

    def _reserve_slot(self, pinned: set[int]) -> int:
        """Claim a slot for a new occupant: first free slot, else evict the
        policy's victim (LRU-not-in-working-set for every stock policy).
        Evicting an expert whose prefetch is still in flight CANCELS the
        transfer — the copy never committed, so the slot's next occupant
        cannot be clobbered by a late completion (the double-buffer
        slot-reuse ordering contract)."""
        free = [s for s, e in enumerate(self._slot_expert) if e < 0]
        if free:
            return free[0]
        victim = self.policy.victim(self._lru, pinned)
        slot = self._lru.pop(victim)
        self._slot_expert[slot] = -1
        self.evictions += 1
        vt = self._inflight.pop(victim, None)
        if vt is not None:
            self.engine.cancel(vt[1])
            self.async_cancelled += 1
        return slot

    def _commit(self, expert: int, slot: int, arrays: dict) -> None:
        """Land ``arrays`` (host or already-device leaves) in ``slot`` and
        finish the residency bookkeeping."""
        if self._write_cb is not None:
            self._write_cb(slot, arrays)
        else:
            with TraceAnnotation("repro.paging.device_put"):
                dev = {n: jax.device_put(v) for n, v in arrays.items()}
            with TraceAnnotation("repro.paging.slot_write"):
                self.slots = self._write(self.slots, dev, slot)
        self._slot_expert[slot] = expert
        self._lru[expert] = slot
        self.bytes_paged += self._expert_bytes
        self.page_ins += 1

    def _host_rows(self, expert: int) -> dict[str, np.ndarray]:
        return {n: self.host[n][expert] for n in self.names}

    def _page_in(self, expert: int, pinned: set[int]) -> None:
        """Synchronous demand page-in (also the misprediction fallback:
        an expert nobody prefetched still pages correctly — through the
        engine when one is attached, so its stall is accounted)."""
        with TraceAnnotation("repro.paging.page_in", expert=expert):
            slot = self._reserve_slot(pinned)
            new = self._host_rows(expert)
            if self.engine is not None:
                tr = self.engine.submit((self.label, expert), new,
                                        tag="demand")
                new = self.engine.fence(tr)
            self._commit(expert, slot, new)

    def _submit_async(self, expert: int, pinned: set[int],
                      tag: str = "demand") -> Transfer:
        """Reserve a slot and start a non-blocking copy for ``expert``.
        The slot is RESERVED (``_slot_expert``/``_lru`` claim it so LRU
        ordering and wave planning see it coming) but the store is not
        touched until the transfer is fenced and committed."""
        slot = self._reserve_slot(pinned)
        tr = self.engine.submit((self.label, expert),
                                self._host_rows(expert), tag=tag)
        self._inflight[expert] = (slot, tr)
        self._slot_expert[slot] = expert
        self._lru[expert] = slot
        return tr

    def _commit_batch(self, batch: list[tuple[int, int, dict]]) -> None:
        """Land a whole fence wave of ``(expert, slot, payload)`` in ONE
        donated store update.  Slots in a batch are distinct (each
        in-flight expert holds its own reservation), so the scatter is
        bit-identical to committing them one by one — it just pays the
        donate-while-compute-reads copy once instead of per expert."""
        if not batch:
            return
        if self._write_many is None or len(batch) == 1:
            for e, slot, payload in batch:
                self._commit(e, slot, payload)
            return
        # pad to the next power of two by REPEATING entry 0: batch sizes
        # vary per fence, and every distinct size is a fresh XLA compile
        # of the scatter — pow2 bucketing caps that at log2(R) variants.
        # A duplicated (slot, payload) pair writes identical values to
        # the same index, so the scatter result is unchanged
        k = len(batch)
        with TraceAnnotation("repro.paging.slot_write"):
            if k == self.max_resident:
                # every slot is being replaced: fresh store, old one dropped
                by_slot = sorted(batch, key=lambda t: t[1])
                self.slots = self._write_full(*(p for _, _, p in by_slot))
            else:
                full = batch + [batch[0]] * ((1 << (k - 1).bit_length()) - k)
                idx = jnp.asarray([s for _, s, _ in full], jnp.int32)
                self.slots = self._write_many(self.slots, idx,
                                              *(p for _, _, p in full))
        for e, slot, _ in batch:
            self._slot_expert[slot] = e
            self._lru[e] = slot
            self.bytes_paged += self._expert_bytes
            self.page_ins += 1

    def ensure_submit(self, expert_ids, record: bool = True) -> list[int]:
        """Async first half of ``ensure``: submit copies for every missing
        id without fencing any — the per-expert transfers overlap each
        other and whatever compute is already in flight.  Returns the ids
        that must be fenced (``ensure_fence``) before dereferencing.
        Requires a transfer engine."""
        needed = self._check_working_set(expert_ids)
        pinned = set(needed)
        to_fence = []
        for e in needed:
            if e in self._inflight:
                self._lru.move_to_end(e)
                if record:
                    self.hits += 1     # prefetch predicted it; fence below
                to_fence.append(e)
            elif e in self._lru:
                self._lru.move_to_end(e)
                if record:
                    self.hits += 1
            else:
                if record:
                    self.misses += 1
                self._submit_async(e, pinned)
                to_fence.append(e)
        return to_fence

    def ensure_fence(self, expert_ids) -> None:
        """Fence+commit the in-flight members of ``expert_ids`` (the
        second half of the async ``ensure``).  Payloads are fenced one by
        one, each fence a page-in span (the wait for that expert's copy),
        but committed as a single batched store write; if a fence raises
        (hung transport), everything fenced before it still commits —
        then the timeout propagates, loud."""
        batch: list[tuple[int, int, dict]] = []
        try:
            for e in expert_ids:
                e = int(e)
                if e in self._inflight:
                    slot, tr = self._inflight.pop(e)
                    with TraceAnnotation("repro.paging.page_in", expert=e):
                        payload = self.engine.fence(tr)
                    batch.append((e, slot, payload))
                    self.inflight_joins += 1
        finally:
            self._commit_batch(batch)

    def _check_working_set(self, expert_ids) -> list[int]:
        needed = list(dict.fromkeys(int(e) for e in expert_ids))
        if len(needed) > self.max_resident:
            raise ValueError(
                f"{len(needed)} experts needed at once but only "
                f"{self.max_resident} slots — page in waves")
        return needed

    def ensure(self, expert_ids, record: bool = True) -> None:
        """Make every id in ``expert_ids`` device-resident (≤ max_resident).

        With a transfer engine this is submit-all-then-fence-all, so the
        misses' copies overlap each other; in-flight prefetches are fenced
        (and counted as hits — the prediction converted demand paging into
        an already-flying copy).  Without an engine it is the synchronous
        PR-2 path, bit-for-bit."""
        with TraceAnnotation("repro.paging.ensure",
                             experts=len(expert_ids)):
            if self.engine is not None:
                self.ensure_fence(self.ensure_submit(expert_ids,
                                                     record=record))
                return
            needed = self._check_working_set(expert_ids)
            pinned = set(needed)
            for e in needed:
                if e in self._lru:
                    self._lru.move_to_end(e)
                    if record:
                        self.hits += 1
                else:
                    if record:
                        self.misses += 1
                    self._page_in(e, pinned)

    def _truncate_prefetch(self, expert_ids) -> list[int]:
        ids = list(dict.fromkeys(int(e) for e in expert_ids))
        keep, dropped = ids[: self.max_resident], ids[self.max_resident:]
        if dropped:
            self.prefetch_truncated += len(dropped)
            self.prefetch_dropped.extend(dropped)
        return keep

    def prefetch(self, expert_ids) -> None:
        """Warm residency (e.g. from ``ExpertUsage.hot``) without demand
        accounting — prefetched experts later hit in ``ensure``.

        A warm-up list longer than the slot count is truncated to the first
        ``max_resident`` (unique) ids; the tail is NOT silently dropped —
        the dropped count and ids ACCUMULATE in the cache stats
        (``prefetch_truncated`` / ``prefetch_dropped``, bounded deque)."""
        self.ensure(self._truncate_prefetch(expert_ids), record=False)

    def prefetch_async(self, expert_ids, tag: str = "prefetch") -> list[int]:
        """Router-lookahead warm-up: SUBMIT non-blocking copies for the
        given ids and return immediately (no fence — the copies ride
        behind whatever compute runs next; ``ensure`` fences them at the
        point of use).  Falls back to the synchronous ``prefetch`` when no
        engine is attached.  Returns the ids actually submitted."""
        if self.engine is None:
            self.prefetch(expert_ids)
            return []
        keep = self._truncate_prefetch(expert_ids)
        pinned = set(keep)
        submitted = []
        for e in keep:
            if e in self._lru:              # resident or already in flight
                self._lru.move_to_end(e)
                continue
            self._submit_async(e, pinned, tag=tag)
            self.async_prefetches += 1
            submitted.append(e)
        return submitted

    def drop(self, expert: int) -> bool:
        """Release ``expert``'s slot, if it holds one (an in-flight copy
        is cancelled).  This is a PLACEMENT drop — ownership moved to
        another shard — not a capacity eviction, so it does not touch the
        eviction counter.  Returns True when a slot was freed."""
        e = int(expert)
        slot = self._lru.pop(e, None)
        if slot is None:
            return False
        self._slot_expert[slot] = -1
        vt = self._inflight.pop(e, None)
        if vt is not None:
            self.engine.cancel(vt[1])
            self.async_cancelled += 1
        return True

    def fence_all(self) -> None:
        """Commit every outstanding in-flight transfer (a full barrier —
        e.g. before tearing the cache down or snapshotting the store)."""
        self.ensure_fence(list(self._inflight))

    def remap(self) -> np.ndarray:
        """(E,) int32: expert id -> device slot, ``-1`` for non-resident.

        The sentinel is deliberate: a non-resident id must never silently
        alias whatever expert happens to occupy slot 0.  Every dereference
        site masks (``PagedMoE`` wave fns select slot indices only where
        the wave mask holds) and the host-side wave loop asserts that all
        wave ids map to real slots before launching the compute.

        An in-flight (reserved, uncommitted) expert maps to its reserved
        slot, whose STORE content is stale until ``ensure`` fences it —
        callers must ensure() the ids they dereference first (the paged
        wave loop always does)."""
        m = np.full((self.num_experts,), -1, np.int32)
        for s, e in enumerate(self._slot_expert):
            if e >= 0:
                m[e] = s
        return m

    def replica_table(self) -> tuple[np.ndarray, np.ndarray]:
        """Replica-aware remap: ``(table, counts)`` where ``table`` is
        (E, W) int32 slot ids (−1 padded) and ``counts`` is (E,) int32
        resident-replica counts.  A single-device cache never replicates:
        W = 1 and counts is the residency indicator — the wave dispatch's
        ``position % counts`` load split degenerates to the identity."""
        remap = self.remap()
        return remap[:, None], (remap >= 0).astype(np.int32)


class ShardedExpertCache:
    """Expert-parallel residency: experts placed over a mesh axis by a
    :class:`~repro.serve.placement.plan.PlacementPlan`.

    Shard ``s`` of ``m`` holds a bounded bank of ``max_resident`` device
    slots and serves the experts the PLAN assigns it — under the default
    static plan that is the contiguous block ``[s*E/m, (s+1)*E/m)``,
    bit-for-bit the old modulo partition; an elastic plan may migrate a
    cold expert's home shard or replicate a hot expert across several.
    The device store is ONE stacked ``(m, R, ...)`` array per weight
    name, sharded over ``axis`` — shard s's bank physically lives on
    shard s, and a page-in writes only that shard's partition.
    Bookkeeping (LRU, hit/miss/bytes, prefetch-truncation accounting) is
    one :class:`ExpertCache` per shard in external-write mode, keyed by
    GLOBAL expert id (transfer keys are ``("shard<s>", expert)``), so the
    single-device semantics — including the ``-1`` non-resident sentinel —
    carry over per shard and an expert can hold a slot on several shards
    at once.

    A fixed per-device slot budget therefore holds ``m × R`` resident
    experts in aggregate: residency scales linearly with the shard count.
    Plan swaps (:meth:`set_plan`) happen between forwards: moved-away
    residency is dropped, new homes stream in through the transfer engine
    (tagged ``migrate``) behind the next forward's compute, and the
    generation counter guarantees no wave observes a half-applied plan.
    """

    def __init__(self, host: dict[str, np.ndarray], max_resident: int,
                 mesh, axis: str = "model",
                 usage: Optional[ExpertUsage] = None,
                 transfer_engine=None, pinned: Optional[dict] = None,
                 policy: Optional[PlacementPolicy] = None,
                 plan: Optional[PlacementPlan] = None):
        if not host:
            raise ValueError("empty expert weight store")
        self.mesh = mesh
        self.axis = axis
        self.engine = transfer_engine
        self.policy = policy if policy is not None else get_policy("static")
        # pinned leaves are REPLICATED over the mesh (every shard computes
        # its experts' waves against the same shared basis) — each device
        # pays the pinned bytes once, like the single-device cache
        pinned = pinned or {}
        clash = set(pinned) & set(host)
        if clash:
            raise ValueError(f"leaves both pinned and paged: {sorted(clash)}")
        self.pinned = {
            n: jax.device_put(jnp.asarray(v),
                              NamedSharding(mesh, P(*([None] * np.ndim(v)))))
            for n, v in pinned.items()
        }
        self.pinned_bytes = _pinned_bytes(self.pinned)
        m = int(mesh.shape[axis])
        self.num_shards = m
        self.num_experts = next(iter(host.values())).shape[0]
        if self.num_experts % m:
            raise ValueError(
                f"E={self.num_experts} does not divide the {m}-way "
                f"{axis!r} axis")
        self.e_local = self.num_experts // m
        self.plan = plan if plan is not None \
            else self.policy.initial_plan(self.num_experts, m)
        if (self.plan.num_experts, self.plan.num_shards) \
                != (self.num_experts, m):
            raise ValueError(
                f"plan is ({self.plan.num_experts} experts, "
                f"{self.plan.num_shards} shards); cache has "
                f"({self.num_experts}, {m})")
        # replica-table width is FIXED by the policy at construction (1
        # for static, m for elastic): later plan swaps must never change
        # a jit-traced shape.  A width-1 bank never holds more than the
        # shard's static share; a replicating bank may hold up to E.
        self.table_width = max(1, min(int(self.policy.table_width(m)), m))
        cap = self.e_local if self.table_width == 1 else self.num_experts
        self.max_resident = max(1, min(int(max_resident), cap))
        rs = self.max_resident
        self.names = tuple(host)
        self.usage = usage
        # per-shard routed-token load (replicated experts split theirs
        # evenly) — the imbalance evidence the elastic policy consumes
        self.shard_load = np.zeros(m, np.float64)
        self.plan_swaps = 0
        self.migrations = 0        # replica additions from plan swaps
        self.migration_drops = 0   # residency released by plan swaps
        self.replications = 0      # experts whose replica count grew
        # stacked sharded slot store: (m, R, ...) over the expert axis
        self.slots = {
            n: jax.device_put(
                jnp.zeros((m, rs) + w.shape[1:], w.dtype),
                NamedSharding(mesh, P(axis, *([None] * w.ndim))))
            for n, w in host.items()
        }
        out_sh = {n: a.sharding for n, a in self.slots.items()}

        def expert_slot_write(slots, new, s, r):
            return {n: slots[n].at[s, r].set(new[n]) for n in slots}

        self._write = jax.jit(expert_slot_write, donate_argnums=(0,),
                              out_shardings=out_sh)

        # every book sees the FULL host store and keys by GLOBAL expert
        # id — which experts a shard may page is the plan's decision, not
        # baked into the book's address space (the pre-placement code
        # sliced ``host`` here, freezing the modulo partition in)
        full = {n: np.asarray(w) for n, w in host.items()}

        def _book(s: int) -> ExpertCache:
            def write_cb(slot, new, _s=s):
                with TraceAnnotation("repro.paging.device_put"):
                    dev = {n: jax.device_put(v) for n, v in new.items()}
                with TraceAnnotation("repro.paging.slot_write"):
                    self.slots = self._write(self.slots, dev,
                                             jnp.int32(_s), jnp.int32(slot))

            return ExpertCache(full, rs, write_cb=write_cb,
                               transfer_engine=transfer_engine,
                               label=f"shard{s}", policy=self.policy)

        self.books = [_book(s) for s in range(m)]
        self._expert_bytes = self.books[0]._expert_bytes

    # -------------------------------------------------------------- state

    @property
    def total_slots(self) -> int:
        return self.num_shards * self.max_resident

    def owner(self, expert: int) -> int:
        """Primary home shard of ``expert`` — the plan's call (static
        plan: ``expert // e_local``, the historical modulo map)."""
        return self.plan.owner(expert)

    @property
    def resident(self) -> list[int]:
        """Global ids holding a slot on ANY shard (deduplicated — a
        replicated expert is listed once)."""
        out: dict[int, None] = {}
        for book in self.books:
            out.update(dict.fromkeys(book.resident))
        return list(out)

    def _sum(self, attr: str) -> int:
        return sum(getattr(b, attr) for b in self.books)

    hits = property(lambda self: self._sum("hits"))
    misses = property(lambda self: self._sum("misses"))
    evictions = property(lambda self: self._sum("evictions"))
    bytes_paged = property(lambda self: self._sum("bytes_paged"))
    page_ins = property(lambda self: self._sum("page_ins"))
    prefetch_truncated = property(
        lambda self: self._sum("prefetch_truncated"))

    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 1.0

    def reset_stats(self) -> None:
        for b in self.books:
            b.reset_stats()
        # placement event counters (plan_swaps/migrations/replications)
        # are CUMULATIVE — they describe the plan's history, not an
        # interval; only the per-interval load evidence resets
        self.shard_load[:] = 0.0

    def record_load(self, per_expert_counts) -> None:
        """Fold one forward's routed-token counts into the per-shard load
        ledger: an expert's tokens land on its plan shards (replicas
        split evenly — exactly how the wave dispatch splits them)."""
        c = np.asarray(per_expert_counts, np.float64).reshape(-1)
        for e in np.nonzero(c)[0]:
            shards = self.plan.shards_of(int(e))
            share = c[e] / len(shards)
            for s in shards:
                self.shard_load[s] += share

    def shard_load_imbalance(self) -> float:
        """max/mean of per-shard routed load (1.0 = perfectly even, m =
        everything on one shard); 0.0 before any load is recorded."""
        tot = float(self.shard_load.sum())
        if tot <= 0:
            return 0.0
        return float(self.shard_load.max() * self.num_shards / tot)

    def stats(self) -> dict[str, Any]:
        out = {
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions, "bytes_paged": self.bytes_paged,
            "page_ins": self.page_ins,
            "hit_rate": self.hit_rate,
            "max_resident": self.max_resident,       # per shard
            "num_shards": self.num_shards,
            "total_slots": self.total_slots,
            "resident_fraction": self.total_slots / self.num_experts,
            "prefetch_truncated": self.prefetch_truncated,
            "paged_expert_bytes": self._expert_bytes,
            "pinned_bytes": self.pinned_bytes,       # per device (replicated)
            "shard_load": [float(v) for v in self.shard_load],
            "shard_load_imbalance": self.shard_load_imbalance(),
            "placement": {
                "policy": self.policy.name,
                "generation": self.plan.generation,
                "plan_swaps": self.plan_swaps,
                "migrations": self.migrations,
                "migration_drops": self.migration_drops,
                "replications": self.replications,
                "max_replicas": self.plan.max_replicas,
                "table_width": self.table_width,
            },
        }
        if self.engine is not None:
            out.update({
                "async_prefetches": self._sum("async_prefetches"),
                "inflight_joins": self._sum("inflight_joins"),
                "async_cancelled": self._sum("async_cancelled"),
                "inflight": sum(len(b._inflight) for b in self.books),
                # ONE engine serves every shard's book: read its ledger
                # once here, not per book (no double counting)
                "stall_s": self.engine.stats.stall_s,
                "overlap_ratio": self.engine.stats.overlap_ratio,
                "transfer_tags": self.engine.stats.tags_dict(),
            })
        return out

    # ------------------------------------------------------------- paging

    def _by_shard(self, expert_ids) -> dict[int, list[int]]:
        """Fan global ids out to the plan's shards (GLOBAL ids per shard;
        a replicated expert appears in several shards' lists)."""
        by: dict[int, list[int]] = {}
        for e in expert_ids:
            for s in self.plan.shards_of(int(e)):
                by.setdefault(s, []).append(int(e))
        return by

    def ensure(self, expert_ids, record: bool = True) -> None:
        """Make every (global) id resident on its owning shard.

        With a transfer engine this is two phases — EVERY shard's missing
        copies are submitted before ANY is fenced, so the per-shard
        page-ins overlap each other (and the all-to-all dispatch of the
        wave already on the device): the wave stalls for the slowest
        shard's copy, not the sum of all shards' copies."""
        with TraceAnnotation("repro.paging.ensure",
                             experts=len(expert_ids)):
            by = self._by_shard(expert_ids)
            if self.engine is not None:
                pending = {s: self.books[s].ensure_submit(local,
                                                          record=record)
                           for s, local in by.items()}
                for s, fence_ids in pending.items():
                    self.books[s].ensure_fence(fence_ids)
                return
            for s, local in by.items():
                self.books[s].ensure(local, record=record)

    def prefetch(self, expert_ids) -> None:
        """Warm each shard's bank with its share of ``expert_ids`` (global
        ids, hottest first); per-shard truncation is recorded."""
        for s, local in self._by_shard(expert_ids).items():
            self.books[s].prefetch(local)

    def prefetch_async(self, expert_ids, tag: str = "prefetch") -> list[int]:
        """Submit non-blocking copies of each shard's share of
        ``expert_ids``; returns the GLOBAL ids actually submitted (a
        replicated expert is listed once per submitting shard)."""
        submitted = []
        for s, ids in self._by_shard(expert_ids).items():
            submitted.extend(self.books[s].prefetch_async(ids, tag=tag))
        return submitted

    def fence_all(self) -> None:
        for b in self.books:
            b.fence_all()

    # ---------------------------------------------------------- placement

    def set_plan(self, new_plan: PlacementPlan) -> None:
        """Install a rebalanced plan ATOMICALLY between forwards.

        Residency on shards the new plan removed is dropped (in-flight
        copies cancelled — the double-buffer slot-reuse contract), and
        page-ins for newly assigned homes are submitted through the
        transfer engine tagged ``migrate``, so they stream behind the
        next forward's compute; without an engine the next wave's
        ``ensure`` demand-pages them.  Callers never see a half-applied
        plan: this method runs only between forwards, and the generation
        bump makes each swap observable exactly once.
        """
        if (new_plan.num_experts, new_plan.num_shards) \
                != (self.num_experts, self.num_shards):
            raise ValueError("plan shape does not match cache")
        if new_plan.generation <= self.plan.generation:
            raise ValueError(
                f"plan generation must advance: {new_plan.generation} <= "
                f"{self.plan.generation}")
        if new_plan.max_replicas > self.table_width:
            raise ValueError(
                f"plan replicates {new_plan.max_replicas}-way but the "
                f"replica table is {self.table_width} wide")
        old = self.plan
        added: dict[int, list[int]] = {}
        for e in range(self.num_experts):
            before = set(old.shards_of(e))
            after = set(new_plan.shards_of(e))
            for s in before - after:
                if self.books[s].drop(e):
                    self.migration_drops += 1
            for s in after - before:
                added.setdefault(s, []).append(e)
            if len(after) > len(before):
                self.replications += 1
        self.plan = new_plan
        self.plan_swaps += 1
        self.migrations += sum(len(v) for v in added.values())
        if self.engine is not None:
            for s, ids in added.items():
                self.books[s].prefetch_async(ids, tag="migrate")

    def remap(self) -> np.ndarray:
        """(E,) int32: expert id -> GLOBAL slot index ``shard*R + slot``
        of the PRIMARY resident replica, in the flattened ``(m*R, ...)``
        view of the stacked store; ``-1`` for non-resident (same sentinel
        contract as ``ExpertCache``)."""
        table, counts = self.replica_table()
        return np.where(counts > 0, table[:, 0], -1).astype(np.int32)

    def replica_table(self) -> tuple[np.ndarray, np.ndarray]:
        """Replica-aware remap: ``(table, counts)``.

        ``table`` is (E, W) int32 — resident replicas' global slot ids
        ``shard*R + slot`` in plan order (primary first), −1 padded;
        ``counts`` is (E,) int32 resident-replica counts.  The wave
        dispatch splits an expert's tokens round-robin over its first
        ``counts[e]`` columns (``position % counts``) — with one replica
        everywhere this is exactly the historical ``remap()`` indexing.
        """
        books = [b.remap() for b in self.books]
        table = np.full((self.num_experts, self.table_width), -1, np.int32)
        counts = np.zeros(self.num_experts, np.int32)
        for e in range(self.num_experts):
            k = 0
            for s in self.plan.shards_of(e):
                if k >= self.table_width:
                    break
                slot = books[s][e]
                if slot >= 0:
                    table[e, k] = s * self.max_resident + slot
                    k += 1
            counts[e] = k
        return table, counts


class PagedMoE:
    """Serve-time MoE layer with bounded expert residency.

    Call semantics match ``core.moe.apply_moe(params, cfg, x, task_id)``:
    returns ``(y, aux)`` — bit-exact with the all-resident grouped path.
    The expert FFN runs in waves of at most ``max_resident`` experts; each
    wave writes its tokens' output rows into a shared (token, slot) row
    buffer (waves touch disjoint rows), and the final combine applies the
    gate weights and sums the k slots per token in the same order as
    ``routing.combine`` — so splitting into waves never changes the
    floating-point result.

    ``layer`` is the layer's index in its model: the ``layer`` stat of its
    ``repro.moe.call`` span.
    """

    def __init__(self, params, cfg: MoEConfig,
                 resident_fraction: float = 0.5,
                 usage: Optional[ExpertUsage] = None,
                 usage_decay: float = 0.9,
                 budget_bytes: Optional[int] = None,
                 mesh=None, ep_axis: str = "model",
                 transfer_engine=None,
                 placement=None, layer: int = 0):
        if cfg.impl not in ("grouped", "onehot"):
            raise ValueError(
                "PagedMoE pages the grouped/onehot expert paths (ep_local "
                "keeps all experts resident — nothing to page)")
        self.cfg = cfg
        self.layer = layer
        # expert-parallel mode: a mesh whose ep_axis has >1 shards switches
        # the cache to per-shard banks and the waves to the one-hot GSPMD
        # dispatch (all-to-all moves tokens; experts stay put)
        self.mesh = None
        self.ep_axis = ep_axis
        if mesh is not None and ep_axis in mesh.axis_names \
                and int(mesh.shape[ep_axis]) > 1:
            self.mesh = mesh
        names = expert_param_names(cfg)
        # quantized expert weights page as their packed leaves (<name>.q /
        # <name>.scale): the cache store stays plain arrays, and the wave
        # rebuilds QTensors from the device slots (``_slot_params``) so the
        # grouped GEMM dispatches the xla_int8 impl.  Packed residency is
        # the memory multiplier: ~4× (int8) / ~8× (int4) more experts fit
        # the same device budget.
        #
        # FACTORED expert weights split further: the shared basis is PINNED
        # (device-resident once, outside the slot store) and only the tiny
        # per-expert delta factors page (<name>.u / <name>.v, themselves
        # splitting into .q/.scale when the deltas are quantized).  The
        # wave rebuilds the FactoredTensor from pinned basis + slot deltas,
        # so the grouped GEMM dispatches the xla_factored impl — per-expert
        # paged bytes drop 10-100× and the byte budget buys residency at
        # the DELTA price.
        self._names = names
        self._qmeta: dict[str, tuple] = {}
        self._fmeta: dict[str, dict] = {}
        host: dict[str, np.ndarray] = {}
        pinned: dict[str, np.ndarray] = {}

        def _host_leaf(key: str, leaf):
            """Flatten one paged leaf (array or QTensor) into host entries;
            returns the QTensor rebuild meta (or None for plain arrays)."""
            if is_qtensor(leaf):
                host[key + ".q"] = np.asarray(leaf.q)
                host[key + ".scale"] = np.asarray(leaf.scale)
                return (leaf.bits, leaf.dtype, leaf.rows)
            host[key] = np.asarray(leaf)
            return None

        for n in names:
            wn = params[n]
            if is_factored(wn):
                pinned[n + ".basis"] = np.asarray(wn.basis)
                self._fmeta[n] = {
                    "kind": wn.kind, "dtype": wn.dtype,
                    "u": _host_leaf(n + ".u", wn.u),
                    "v": _host_leaf(n + ".v", wn.v),
                }
            elif is_qtensor(wn):
                self._qmeta[n] = _host_leaf(n, wn)
            else:
                host[n] = np.asarray(wn)
        per_expert = _per_expert_bytes(host)
        pinned_total = _pinned_bytes(pinned)
        shards = int(self.mesh.shape[ep_axis]) if self.mesh is not None else 1
        e_per_shard = cfg.num_experts // shards
        # residency decisions live in the placement policy: ``placement``
        # is a name ("static"/"lru"/"budget"/"elastic") or a constructed
        # PlacementPolicy.  A bare ``budget_bytes`` keeps its historical
        # meaning by resolving to the budget policy; an explicit policy
        # without its own budget inherits the argument.
        if isinstance(placement, PlacementPolicy):
            self.policy = placement
        elif placement in (None, "static") and budget_bytes is not None:
            self.policy = get_policy("budget", budget_bytes=budget_bytes)
        else:
            self.policy = get_policy(placement)
        if budget_bytes is not None and self.policy.budget_bytes is None:
            self.policy.budget_bytes = int(budget_bytes)
        # slot sizing is the policy's call too (extracted byte-budget /
        # fraction arithmetic): ≥ top_k on a single device so one wave can
        # always serve a token's full expert set; per-shard banks only
        # need ≥ 1 — waves accumulate into disjoint rows, so splitting
        # never hurts
        floor = cfg.top_k if shards == 1 else 1
        max_resident = self.policy.slots(
            per_expert_bytes=per_expert, pinned_bytes=pinned_total,
            experts_per_shard=e_per_shard,
            resident_fraction=resident_fraction, floor=floor)
        self.usage = usage or ExpertUsage(cfg.num_experts, cfg.num_tasks,
                                          decay=usage_decay)
        # async paging: with a transfer engine the cache double-buffers —
        # wave k+1's host→device copies are submitted while wave k
        # computes, and usage-driven prefetches become non-blocking
        self.engine = transfer_engine
        if self.mesh is not None:
            self.cache = ShardedExpertCache(host, max_resident, self.mesh,
                                            axis=ep_axis, usage=self.usage,
                                            transfer_engine=transfer_engine,
                                            pinned=pinned,
                                            policy=self.policy)
        else:
            self.cache = ExpertCache(host, max_resident, usage=self.usage,
                                     transfer_engine=transfer_engine,
                                     pinned=pinned, policy=self.policy)
        self._forwards = 0   # rebalance cadence counter (policy-driven)
        # forwards and expert waves run since the last reset_stats()
        self.forwards = 0
        self.waves = 0
        # the most recent forward's routing, (groups, g, k) per field: the
        # expert sets each token was dispatched to, for parity checks
        self.last_routing: Optional[R.Routing] = None
        self.gate = jnp.asarray(params["gate"])
        gb = params.get("gate_bias")   # optional (tasks, E) logit bias
        self.gate_bias = None if gb is None else jnp.asarray(gb)
        self.shared = {k: params[k] for k in
                       ("shared_wg", "shared_wu", "shared_wd") if k in params}
        self._route_fn = None
        self._wave_fn = None
        self._finish_fn = None

    def _slot_params(self, slots, pinned):
        """Rebuild the per-expert params dict from device slot arrays,
        re-wrapping quantized leaves as QTensors and factored leaves as
        FactoredTensors (jit-safe: both are pytrees of the slot tracers;
        the factored basis comes from the PINNED store, not the slots)."""
        def leaf(key, qmeta):
            if qmeta is not None:
                bits, dt, rows = qmeta
                return QTensor(slots[key + ".q"], slots[key + ".scale"],
                               bits=bits, dtype=dt, rows=rows)
            return slots[key]

        out = {}
        for n in self._names:
            if n in self._fmeta:
                fm = self._fmeta[n]
                out[n] = FactoredTensor(pinned[n + ".basis"],
                                        leaf(n + ".u", fm["u"]),
                                        leaf(n + ".v", fm["v"]),
                                        kind=fm["kind"], dtype=fm["dtype"])
            elif n in self._qmeta:
                out[n] = leaf(n, self._qmeta[n])
            else:
                out[n] = slots[n]
        return out

    # ------------------------------------------------------- jitted stages

    def _build(self, g: int, capacity: int):
        cfg = self.cfg
        e, k = cfg.num_experts, cfg.top_k
        sharded = self.mesh is not None
        # flattened slot-bank size the wave fns index into: per-shard banks
        # concatenate to (m*R) global slots in the sharded mode
        rs = (self.cache.total_slots if sharded
              else self.cache.max_resident)

        has_bias = self.gate_bias is not None

        def route(gate_w, gate_b, groups, real):
            def per_group(xg, rm):
                logits = jnp.einsum("td,de->te", xg.astype(jnp.float32),
                                    gate_w)
                if has_bias:
                    logits = logits + gate_b.astype(jnp.float32)
                r = R.route(logits, k, capacity,
                            renormalize=cfg.renormalize)
                # pad rows are excluded from usage stats (as in apply_moe)
                stat_valid = r.valid & rm[:, None]
                counts = jnp.zeros((e,), jnp.int32).at[
                    r.expert.reshape(-1)].add(
                        stat_valid.reshape(-1).astype(jnp.int32))
                return r, counts
            return jax.vmap(per_group)(groups, real)

        mesh, axis = self.mesh, self.ep_axis

        def wave(groups, routing, slots, pinned, wave_mask,
                 rep_table, rep_counts, rows_acc):
            if sharded:
                # (m, R, ...) shard banks -> flat (m*R, ...) global slots;
                # the reshape keeps the expert dim shard-contiguous so the
                # store stays partitioned over the expert-parallel axis
                # (pinned leaves carry no expert axis — replicated as-is)
                slots = {n: a.reshape((rs,) + a.shape[2:])
                         for n, a in slots.items()}
            params_w = self._slot_params(slots, pinned)

            def per_group(xg, r, rows):
                in_wave = wave_mask[r.expert]          # (T, k) bool
                # load-split replica dispatch: an expert's tokens are
                # dealt round-robin over its resident replicas (identical
                # weights on different shards), and each replica sees a
                # DENSE position stream (position // reps) — bit-exact
                # per token because a GEMM row depends only on its own
                # inputs, and the one-replica case reduces to exactly the
                # historical remap indexing (reps == 1 → identity).
                reps = jnp.maximum(rep_counts[r.expert], 1)
                ridx = jnp.remainder(r.position, reps)
                # the table carries -1 for unfilled replica columns;
                # dereference ONLY where the wave mask holds (a forgotten
                # mask must never alias slot 0's expert — see
                # ExpertCache.remap)
                slot_idx = jnp.where(in_wave, rep_table[r.expert, ridx], 0)
                r_w = R.Routing(
                    expert=slot_idx.astype(jnp.int32), gate=r.gate,
                    position=r.position // reps,
                    valid=r.valid & in_wave,
                    probs=r.probs)
                if sharded:
                    # one-hot dispatch: under GSPMD the (rs, C, d) buffer
                    # sharded over the expert axis turns these einsums
                    # into the token all-to-all of expert parallelism
                    buf = R.dispatch_onehot(xg, r_w, rs, capacity)
                    buf = jax.lax.with_sharding_constraint(
                        buf, ep_dispatch_sharding(mesh, axis))
                else:
                    buf = R.dispatch(xg, r_w, rs, capacity)
                sizes = R.dispatch_counts(r_w, rs)
                out = _expert_ffn(params_w, cfg, buf, sizes)
                ef = r_w.expert.reshape(-1)
                pf = jnp.minimum(r_w.position.reshape(-1), capacity - 1)
                got = out[ef, pf]                      # (T*k, d)
                sel = (r_w.valid.reshape(-1))[:, None]
                return jnp.where(sel, got, rows)
            return jax.vmap(per_group)(groups, routing, rows_acc)

        def finish(routing, rows_acc, real):
            def per_group(r, rows, rm):
                # the same weighted k-sum as routing.combine
                y = R.combine_rows(rows, r)
                aux = R.load_balance_loss(r.probs, r.expert, e, mask=rm)
                return y, aux
            return jax.vmap(per_group)(routing, rows_acc, real)

        self._route_fn = jax.jit(route)
        self._wave_fn = jax.jit(wave, donate_argnums=(7,))
        self._finish_fn = jax.jit(finish)
        self._built_for = (g, capacity)

    # ------------------------------------------------------------- forward

    def reset_stats(self) -> None:
        """Zero the forward and wave counts and the cache's counters."""
        self.forwards = self.waves = 0
        self.cache.reset_stats()

    def __call__(self, x: jax.Array, task_id: int = 0):
        with TraceAnnotation("repro.moe.call", layer=self.layer):
            return self._forward(x, task_id)

    def _forward(self, x: jax.Array, task_id: int):
        cfg = self.cfg
        orig_shape = x.shape
        d = x.shape[-1]
        flat = x.reshape(-1, d)
        t_total = flat.shape[0]
        g, t_pad = group_shape(t_total, cfg.group_size)
        if t_pad != t_total:
            flat = jnp.concatenate(
                [flat, jnp.zeros((t_pad - t_total, d), flat.dtype)])
        real = (jnp.arange(t_pad) < t_total).reshape(t_pad // g, g)
        groups = flat.reshape(t_pad // g, g, d)
        capacity = cfg.capacity(g)
        if getattr(self, "_built_for", None) != (g, capacity):
            self._build(g, capacity)

        with TraceAnnotation("repro.moe.route"):
            gate_w = self.gate
            if gate_w.ndim == 3:
                gate_w = gate_w[int(task_id)]
            gate_b = self.gate_bias
            if gate_b is not None and gate_b.ndim == 2:
                gate_b = gate_b[int(task_id)]
            if gate_b is None:
                gate_b = jnp.zeros((cfg.num_experts,), jnp.float32)
            routing, counts = self._route_fn(gate_w, gate_b, groups, real)
        self.last_routing = routing

        with TraceAnnotation("repro.moe.readback"):
            counts_np = np.asarray(counts.sum(axis=0))
        with TraceAnnotation("repro.moe.plan"):
            self.usage.update(counts_np, task_id)
            if self.mesh is not None:
                # per-shard load evidence for the elastic policy (and the
                # imbalance numbers in stats()) — recorded under the
                # CURRENT plan, i.e. where this forward's tokens actually go
                self.cache.record_load(counts_np)
            needed = [int(i) for i in np.nonzero(counts_np)[0]]
            # wave order: already-resident experts first, so warm residency
            # (prefetch or the previous batch) turns into demand hits
            res = set(self.cache.resident)
            needed.sort(key=lambda i: (i not in res, i))
            waves = self._plan_waves(needed)

        n = groups.shape[0]
        rows = jnp.zeros((n, g * cfg.top_k, d), groups.dtype)
        eng = self.engine
        for k, wave_ids in enumerate(waves):
            # fence point: everything this wave dereferences must have
            # landed — in-flight lookahead copies commit here, anything
            # mispredicted demand-pages (correctness never depends on
            # prediction quality)
            self.cache.ensure(wave_ids)
            with TraceAnnotation("repro.moe.launch", wave=k,
                                 experts=len(wave_ids)):
                table, rep_counts = self.cache.replica_table()
                # masking contract: every id this wave dereferences must be
                # resident on at least one of its plan shards (the table
                # carries -1 sentinels for everything else)
                assert (rep_counts[wave_ids] >= 1).all(), \
                    f"wave ids {wave_ids} not all resident: " \
                    f"{rep_counts[wave_ids]}"
                mask = np.zeros((cfg.num_experts,), bool)
                mask[wave_ids] = True
                rows = self._wave_fn(groups, routing, self.cache.slots,
                                     self.cache.pinned, jnp.asarray(mask),
                                     jnp.asarray(table),
                                     jnp.asarray(rep_counts), rows)
            if eng is not None:
                if k + 1 < len(waves):
                    # router lookahead inside the batch: the wave launch
                    # above is non-blocking, so wave k+1's copies are
                    # submitted NOW and ride behind wave k's compute —
                    # the double-buffer. Evicted slots are safe to retarget
                    # (commits happen only at the next fence point).
                    self.cache.prefetch_async(waves[k + 1])
                eng.on_wave()   # virtual-clock transports model the
                #                 wave's compute time passing here
        self.forwards += 1
        self.waves += len(waves)
        # rebalance point: ALL of this forward's waves have launched, the
        # next forward has not started — the only place a plan may swap.
        # Migration page-ins submitted here stream behind the combine and
        # the trunk layers that follow (tagged "migrate" in the ledger).
        self._maybe_rebalance()
        with TraceAnnotation("repro.moe.finish"):
            y, aux = self._finish_fn(routing, rows, real)
            y = y.reshape(-1, d)[:t_total].reshape(orig_shape).astype(
                x.dtype)

        if cfg.num_shared_experts:
            gshared = unified_linear(x, self.shared["shared_wg"],
                                     activation="silu")
            ushared = unified_linear(x, self.shared["shared_wu"])
            y = y + unified_linear((gshared * ushared).astype(x.dtype),
                                   self.shared["shared_wd"])
        return y, aux.mean()

    def _plan_waves(self, needed: list[int]) -> list[list[int]]:
        """Chunk the needed experts into residency-bounded waves.

        Single device: consecutive chunks of ``max_resident``.  Expert-
        parallel: first-fit against every shard's bank — an expert joins
        the earliest wave in which ALL of its plan shards still have a
        free slot (a replicated expert claims one slot per shard).  All
        shards compute concurrently, so the wave count is the max
        per-shard slot pressure, not the global count (the linear-scaling
        win); for single-replica plans this is exactly the per-shard
        chunking the static path always did."""
        rs = self.cache.max_resident
        if self.mesh is None:
            return [needed[i:i + rs] for i in range(0, len(needed), rs)]
        plan = self.cache.plan
        waves: list[list[int]] = []
        loads: list[np.ndarray] = []
        for e in needed:   # first-fit keeps the resident-first order
            shards = plan.shards_of(e)
            w = 0
            while True:
                if w == len(waves):
                    waves.append([])
                    loads.append(np.zeros(self.cache.num_shards, np.int64))
                if all(loads[w][s] < rs for s in shards):
                    waves[w].append(e)
                    for s in shards:
                        loads[w][s] += 1
                    break
                w += 1
        return waves

    def _maybe_rebalance(self) -> None:
        """Consult the placement policy between forwards (its cadence):
        an accepted proposal swaps the plan atomically via ``set_plan``."""
        if self.mesh is None:
            return
        every = getattr(self.policy, "rebalance_every", 0)
        self._forwards += 1
        if not every or self._forwards % every:
            return
        new = self.policy.update(self.cache.plan, self.usage,
                                 self.cache.shard_load,
                                 slots_per_shard=self.cache.max_resident)
        if new is not None:
            self.cache.set_plan(new)

    def predict(self, task_id: Optional[int] = None) -> list[int]:
        """Router-lookahead prediction: the next batch's expert working
        set, hottest first, from the per-task usage EMA (task-level
        sparsity makes this stable — the paper's §IV-F premise).  The
        ranking itself is the placement policy's call — the scheduler's
        cross-quantum lookahead and the per-batch prefetch both consume
        the plan through this one interface."""
        budget = (self.cache.total_slots if self.mesh is not None
                  else self.cache.max_resident)
        return self.policy.prefetch_ranking(self.usage, budget, task_id)

    def prefetch(self, task_id: Optional[int] = None) -> None:
        """Warm the device slots with the usage-EMA-hot experts for a task —
        called by the scheduler ahead of a task-bucket switch.  In the
        expert-parallel mode every shard warms its own bank with its share
        of the hot set (aggregate residency = shards × bank size).

        With a transfer engine the warm-up is NON-BLOCKING: copies are
        submitted and ride behind whatever computes next (the dense trunk
        blocks ahead of this layer, or the previous task's tail); the
        first wave that needs them fences."""
        hot = self.predict(task_id)
        if self.engine is not None:
            self.cache.prefetch_async(hot)
        else:
            self.cache.prefetch(hot)
