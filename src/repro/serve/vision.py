"""Batched M³ViT serving: the paper's own vision model behind the scheduler.

Single-shot dense prediction (patchify → trunk → task head, no KV cache),
executed layer-by-layer so every MoE block runs through the paged expert
cache (``serve/expert_cache.py``): attention/MLP sub-blocks are jitted once
and reused across layers, while expert FFNs page their weights in bounded
waves.  Task switching between semseg and depth is the paper's §IV-F gate
index switch — plus, at the serving layer, an expert-cache prefetch of the
incoming task's usage-hot experts.

``VisionBackend`` adapts this to the ``Scheduler`` bucket protocol: a
request's prompt is an image (H, W, 3) (or precomputed patch embeddings);
a bucket batches up to ``slots`` same-task requests and completes them in
one forward.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import m3vit as MV
from repro.configs.base import ArchConfig
from repro.dist.sharding import ShardingRules, use_rules
from repro.models import layers as L
from repro.models import transformer as T
from repro.models import vit as V
from repro.ops.policy import use_policy
from repro.serve.expert_cache import PagedMoE
from repro.serve.scheduler import Request
from repro.serve.slo.tiers import is_preemptible
from repro.serve.transfer import TransferEngine

__all__ = ["M3ViTServer", "VisionBackend"]


class M3ViTServer:
    """Layer-streamed M³ViT executor with paged MoE blocks.

    ``resident_fraction`` bounds each MoE layer's device-resident experts;
    1.0 keeps everything resident (still exercising the paged code path,
    which is bit-exact with ``core.moe.apply_moe`` — see tests).

    ``rules`` (mesh serving): dense blocks run under the sharding rules
    (batch over ``data``, heads/ff over ``model``) and every MoE layer's
    ``PagedMoE`` switches to expert-parallel paging over the ``model``
    axis — per-shard slot banks, so the same per-device budget holds
    ``shards ×`` more resident experts.

    ``ep_mesh`` is the hybrid placement from the accelerator co-design
    line of work (M³ViT / UbiMoE): the dense trunk — tiny next to the
    expert weights — stays replicated/local, and ONLY the MoE layers go
    expert-parallel over the mesh.  Pass it without ``rules`` to get
    expert parallelism with zero collectives in the dense blocks.

    ``async_paging`` attaches one shared :class:`TransferEngine` to every
    MoE layer's ``PagedMoE``: expert page-ins become non-blocking copies
    that ride behind compute (a layer's prefetch streams while earlier
    dense blocks run; wave k+1's copies stream while wave k computes) and
    are fenced only at the point of use — the serve-time realization of
    the paper's never-stall expert streaming.  Results are bit-identical
    to synchronous paging (tested); only the stall time moves.  Pass
    ``transfer_engine`` to inject a transport (e.g. the deterministic
    ``FakeTransferEngine`` in tests).
    """

    def __init__(self, cfg: ArchConfig, params,
                 resident_fraction: float = 0.5,
                 expert_budget_bytes: Optional[int] = None,
                 rules: Optional[ShardingRules] = None,
                 ep_mesh=None, async_paging: bool = False,
                 transfer_engine=None, factor=None,
                 placement=None):
        if cfg.family != "vit-moe":
            raise ValueError("M3ViTServer serves the vit-moe family")
        self.cfg = cfg
        self.rules = rules
        if transfer_engine is None and async_paging:
            transfer_engine = TransferEngine()
        self.engine = transfer_engine
        mesh = ep_mesh if ep_mesh is not None else (
            rules.mesh if rules is not None else None)
        self.params = params
        self.mcfg = T.moe_config(cfg)
        period = cfg.period
        n_scan = cfg.num_layers // period
        self.kinds = [cfg.block_pattern[i % period]
                      for i in range(cfg.num_layers)]
        self.layer_params: list[Any] = []
        for i in range(cfg.num_layers):
            p, b = divmod(i, period)
            if p < n_scan:
                lp = jax.tree.map(lambda a: a[p],
                                  params["layers"][f"b{b}"])
            else:
                lp = params["rest"][i - n_scan * period]
            self.layer_params.append(lp)
        # factored experts (``factor=(kind, rank, delta_bits)``): each MoE
        # layer's expert stack converts to basis + per-expert deltas HERE,
        # after the per-layer slice — a layer's experts share that layer's
        # basis (averaging across layers would be semantically wrong, and
        # the stacked tree's ndim-4 leaves are not factorable anyway).
        # PagedMoE then pins the basis and pages only the deltas, so the
        # same expert_budget_bytes holds 10-100× more resident experts.
        if factor is not None:
            from repro.factor import factorize_tree
            f_kind, f_rank, f_bits = factor
            for i, kind in enumerate(self.kinds):
                if kind == "attn_moe":
                    lp = dict(self.layer_params[i])
                    lp["moe"] = factorize_tree(lp["moe"], kind=f_kind,
                                               rank=f_rank,
                                               delta_bits=f_bits)
                    self.layer_params[i] = lp
        # expert_budget_bytes (per MoE layer) beats resident_fraction when
        # given: quantized expert weights then fit ~4× more resident
        # experts into the same device budget (the hit-rate win)
        # ``placement`` (policy name or PlacementPolicy) decides shard
        # ownership, victim pick, and prefetch ranking for every paged
        # layer; a string constructs one policy instance PER layer, so
        # each layer's plan evolves against its own router's usage
        self.placement = placement
        self.paged = {
            i: PagedMoE(self.layer_params[i]["moe"], self.mcfg,
                        resident_fraction=resident_fraction,
                        budget_bytes=expert_budget_bytes,
                        mesh=mesh, transfer_engine=self.engine,
                        placement=placement, layer=i)
            for i, kind in enumerate(self.kinds) if kind == "attn_moe"
        }

        # layer blocks run OUTSIDE transformer.forward, so the config's
        # compute policy is scoped here (same policy per step as the LM path)
        def dense_block(bp, x, pos):
            with use_policy(cfg.policy):
                h = L.apply_norm(bp["ln1"], x, cfg)
                a, _ = L.apply_attention(bp["attn"], h, cfg, pos=pos,
                                         causal=False)
                x = x + a
                h = L.apply_norm(bp["ln2"], x, cfg)
                return x + L.apply_mlp(bp["mlp"], h, cfg)

        def moe_pre(bp, x, pos):
            with use_policy(cfg.policy):
                h = L.apply_norm(bp["ln1"], x, cfg)
                a, _ = L.apply_attention(bp["attn"], h, cfg, pos=pos,
                                         causal=False)
                x = x + a
                return x, L.apply_norm(bp["ln2"], x, cfg)

        def embed(prm, img):
            with use_policy(cfg.policy):
                return V.embed_patches(prm, img, cfg)

        def final_norm(prm, x):
            return L.apply_norm(prm["final_norm"], x, cfg)

        def head_for(t):
            def task_head(prm, f):
                with use_policy(cfg.policy):
                    return V.apply_head(prm, f, t)
            return jax.jit(task_head)

        self._embed = jax.jit(embed)
        self._dense = jax.jit(dense_block)
        self._moe_pre = jax.jit(moe_pre)
        self._final = jax.jit(final_norm)
        self._heads = {t: head_for(t) for t in MV.TASKS}

    def infer(self, images, task) -> np.ndarray:
        """images: (B, H, W, 3) f32 or (B, T, d) patch embeddings.
        ``task``: name or index.  Returns the dense prediction (numpy)."""
        task_id = MV.TASKS.index(task) if isinstance(task, str) else int(task)
        # rules scope covers the jit traces below, so the dense blocks'
        # constrain() calls bind to the serving mesh
        with use_rules(self.rules):
            with TraceAnnotation("repro.vision.embed"):
                x = self._embed(self.params, jnp.asarray(images))
                b, s = x.shape[0], x.shape[1]
                pos = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32)[None], (b, s))
            for i, kind in enumerate(self.kinds):
                bp = self.layer_params[i]
                if kind == "attn_moe":
                    with TraceAnnotation("repro.vision.moe_pre", layer=i):
                        xr, h = self._moe_pre(bp, x, pos)
                    with use_policy(self.cfg.policy):
                        y, _ = self.paged[i](h, task_id=task_id)
                    x = xr + y
                else:
                    with TraceAnnotation("repro.vision.dense", layer=i):
                        x = self._dense(bp, x, pos)
            with TraceAnnotation("repro.vision.final"):
                feats = self._final(self.params, x)
            with TraceAnnotation("repro.vision.head"):
                pred = self._heads[MV.TASKS[task_id]](self.params, feats)
            with TraceAnnotation("repro.vision.readback"):
                return np.asarray(pred)

    def prefetch(self, task_id: int) -> None:
        """Warm every MoE layer's expert cache with the task's hot set —
        called by the scheduler ahead of a task-bucket switch.  With async
        paging this only SUBMITS the copies (router-lookahead prefetch);
        each layer fences its own experts when its wave needs them."""
        for paged in self.paged.values():
            paged.prefetch(task_id)

    # scheduler lookahead hook: identical to prefetch, but named for the
    # cross-bucket case — stream the NEXT bucket's hot set behind the
    # quantum that is about to run
    lookahead = prefetch

    def cache_stats(self) -> dict[str, Any]:
        # forwards and waves are paged-layer counts, summed over the MoE
        # layers (a served batch is one forward of every layer)
        agg = {"hits": 0, "misses": 0, "evictions": 0, "bytes_paged": 0,
               "page_ins": 0, "forwards": 0, "waves": 0}
        async_agg = {"async_prefetches": 0, "inflight_joins": 0,
                     "async_cancelled": 0}
        frac = 0.0
        shard_load = None
        placement: dict[str, Any] = {}
        for paged in self.paged.values():
            s = paged.cache.stats()
            for k in ("hits", "misses", "evictions", "bytes_paged",
                      "page_ins"):
                agg[k] += s[k]
            agg["forwards"] += paged.forwards
            agg["waves"] += paged.waves
            for k in async_agg:
                async_agg[k] += s.get(k, 0)
            frac = s["resident_fraction"]
            if "shard_load" in s:       # expert-parallel layers only
                sl = np.asarray(s["shard_load"], np.float64)
                shard_load = sl if shard_load is None else shard_load + sl
                p = s["placement"]
                placement = {
                    "policy": p["policy"],
                    "generation": max(placement.get("generation", 0),
                                      p["generation"]),
                    "plan_swaps": placement.get("plan_swaps", 0)
                    + p["plan_swaps"],
                    "migrations": placement.get("migrations", 0)
                    + p["migrations"],
                    "replications": placement.get("replications", 0)
                    + p["replications"],
                    "max_replicas": max(placement.get("max_replicas", 1),
                                        p["max_replicas"]),
                }
        tot = agg["hits"] + agg["misses"]
        agg["hit_rate"] = agg["hits"] / tot if tot else 1.0
        agg["resident_fraction"] = frac
        if shard_load is not None:
            agg["shard_load"] = [float(v) for v in shard_load]
            s_tot = float(shard_load.sum())
            agg["shard_load_imbalance"] = (
                float(shard_load.max() * shard_load.size / s_tot)
                if s_tot > 0 else 0.0)
            agg["placement"] = placement
        if self.engine is not None:
            # one engine is shared by every layer, so stall/overlap are
            # read from its single ledger, not summed per layer
            agg.update(async_agg)
            agg["stall_s"] = self.engine.stats.stall_s
            agg["hidden_s"] = self.engine.stats.hidden_s
            agg["overlap_ratio"] = self.engine.stats.overlap_ratio
            agg["transfer_tags"] = self.engine.stats.tags_dict()
        return agg

    def reset_stats(self) -> None:
        """Zero cache and layer counters AND the shared transfer ledger —
        call at a measurement boundary so stall_s/overlap_ratio cover one
        interval."""
        for paged in self.paged.values():
            paged.reset_stats()
        if self.engine is not None:
            self.engine.reset_stats()


class VisionTaskBucket:
    """Stages up to ``slots`` same-task requests and serves them in one
    batched forward (a vision request completes in a single quantum)."""

    def __init__(self, backend: "VisionBackend", task_id: int, slots: int):
        self.backend = backend
        self.task_id = task_id
        self.slots = slots
        self.staged: list[Request] = []
        self.steps = 0
        self.slot_steps = 0

    @property
    def active(self) -> int:
        return len(self.staged)

    @property
    def free_slots(self) -> list[int]:
        return list(range(self.slots - len(self.staged)))

    def admit(self, req: Request, now: float) -> list[Request]:
        req.t_admit = now
        self.staged.append(req)
        return []

    def bump_batch(self) -> Optional[Request]:
        """SLO preemption hook: displace the most recently staged batch-tier
        request so a due interactive one can take its place in the next
        forward.  Vision inference is stateless (one batched forward per
        request), so a bump is trivially result-identical — the request
        just rides a later batch."""
        for i in range(len(self.staged) - 1, -1, -1):
            if is_preemptible(self.staged[i]):
                req = self.staged.pop(i)
                req.preemptions += 1
                return req
        return None

    def run_quantum(self, n: int, now_fn, admit_cb=None) -> list[Request]:
        if admit_cb is not None:
            admit_cb()      # top up the batch before launching it
        if not self.staged:
            return []
        batch = self.staged
        self.staged = []
        step = self.steps
        with TraceAnnotation("repro.vision.quantum", step=step,
                             task=self.task_id, batch=len(batch)):
            server = self.backend.server
            with TraceAnnotation("repro.vision.prefetch"):
                server.prefetch(self.task_id)
            with TraceAnnotation("repro.vision.stack"):
                imgs = np.stack([np.asarray(r.prompt) for r in batch])
                if imgs.shape[0] < self.slots:  # fixed batch: one compile
                    pad = np.repeat(imgs[:1], self.slots - imgs.shape[0],
                                    axis=0)
                    imgs = np.concatenate([imgs, pad], axis=0)
            preds = server.infer(imgs, self.task_id)
        now = now_fn()
        self.steps += 1
        self.slot_steps += len(batch)
        for i, req in enumerate(batch):
            req.result = preds[i]
            req.t_first = req.t_done = now
            req.step = step
        return batch


class VisionBackend:
    """Scheduler backend serving M³ViT semseg/depth through task buckets."""

    def __init__(self, cfg: ArchConfig, params,
                 resident_fraction: float = 0.5,
                 expert_budget_bytes: Optional[int] = None,
                 rules: Optional[ShardingRules] = None,
                 ep_mesh=None, async_paging: bool = False,
                 transfer_engine=None, factor=None,
                 placement=None):
        self.server = M3ViTServer(cfg, params,
                                  resident_fraction=resident_fraction,
                                  expert_budget_bytes=expert_budget_bytes,
                                  rules=rules, ep_mesh=ep_mesh,
                                  async_paging=async_paging,
                                  transfer_engine=transfer_engine,
                                  factor=factor, placement=placement)
        self.num_tasks = len(MV.TASKS)
        self.usage = None   # per-layer usage lives inside each PagedMoE

    def make_bucket(self, task_id: int, slots: int) -> VisionTaskBucket:
        return VisionTaskBucket(self, task_id, slots)

    def lookahead(self, task_id: int) -> None:
        """Scheduler hook: stream task ``task_id``'s usage-hot experts
        behind the quantum about to run.  No-op without a transfer engine —
        a synchronous lookahead would BLOCK before the quantum (the exact
        stall this feature removes) and evict the current task's set."""
        if self.server.engine is not None:
            self.server.lookahead(task_id)

    def cache_stats(self) -> dict[str, Any]:
        return self.server.cache_stats()

    def reset_stats(self) -> None:
        self.server.reset_stats()
