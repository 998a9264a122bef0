"""Task-aware serving scheduler: continuous batching over task buckets.

The multi-request generalization of the paper's zero-cost task switch
(§IV-F).  Requests carry a ``task_id``, an arrival time, and a prompt; the
scheduler keeps one *bucket* of decode slots per task (all slots in a bucket
share the task's gating network, so the jitted decode step is cached per
task exactly like the static engine), admits queued requests into freed
slots mid-flight, and rotates decode quanta round-robin across tasks so one
hot task cannot starve the rest.

Continuous batching mechanics:

  * each bucket owns a batched decode state (KV caches / recurrent state)
    of ``slots`` sequences plus a per-slot ``cache_pos`` vector — the
    vector-``cache_index`` decode path added to ``models/transformer.py``;
  * admission prefills the new request alone (batch 1, prompt padded up to
    a length bucket for attention archs so prefill compiles are bounded)
    and splices the resulting state into the freed slot with a donated
    per-leaf ``dynamic_update_slice`` (``_StateSlots``);
  * a request finishes on its own EOS/max-tokens; its slot is immediately
    reusable — no waiting for the rest of the batch (the static engine's
    tail waste, and where the throughput win comes from);
  * MoE archs: every decode step exports the per-expert dispatch counts
    (``forward(..., return_expert_counts=True)``) into a per-task
    ``ExpertUsage`` — the router statistics that drive expert-cache
    prefetch and make task-level sparsity observable.

SLO-aware serving (``Scheduler(..., slo=SLOPolicy(...))``, the
``repro.serve.slo`` subsystem):

  * requests carry a *tier* (interactive vs batch) with TTFT/TPOT
    deadlines; admission laps serve interactive queues first;
  * a due interactive request with no free slot *preempts* a batch-tier
    decode slot: its KV/recurrent state is parked bit-exactly (int8 KV
    caches make parked bytes ~4× cheaper — ``slo/preempt.py``) and later
    spliced back through the same fused admit-splice, continuing decode
    token-identically;
  * a radix prefix cache (``ServeConfig.prefix_cache`` > 0) seeds
    admissions from cached shared-prompt prefill state, skipping the
    matched tokens;
  * long prompts admit in ``prefill_chunk``-token chunks interleaved
    with decode steps (one chunk per step), so a long prefill no longer
    head-of-line-blocks every decode slot;
  * ``metrics()`` reports per-tier TTFT/TPOT percentiles, preemption
    counts, and goodput-under-SLO alongside tok/s.

``Scheduler`` is backend-generic: ``LMBackend`` serves autoregressive
decode; ``serve/vision.py`` provides a batched M³ViT backend so the paper's
own semseg/depth model is served through the same queue and fairness
machinery (vision "preemption" is a staged-batch bump — inference is
stateless, so it is trivially result-identical).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.dist.sharding import ShardingRules, use_rules
from repro.models import model as M
from repro.serve.engine import (ServeConfig, feedback_inputs, is_recurrent,
                                shard_state, state_batch_axes)
from repro.serve.expert_cache import ExpertUsage
from repro.serve.slo.preempt import SlotParker
from repro.serve.slo.prefix import RadixPrefixCache
from repro.serve.slo.tiers import (SLOPolicy, goodput, is_preemptible,
                                   meets_slo, request_tpot)

__all__ = ["Request", "Scheduler", "LMBackend"]


@dataclass
class Request:
    rid: int
    task_id: int
    prompt: Any                     # (S0,) int32 tokens | (S0, d) embeddings
    max_new_tokens: int = 0         # LM: tokens to generate (>=1)
    arrival: float = 0.0
    eos_id: Optional[int] = None    # None => backend default
    # SLO tier (see repro.serve.slo.tiers): deadlines are None until the
    # trace/tier tags them; ``tier`` names the service class
    tier: str = "interactive"
    tenant: int = 0
    ttft_slo_s: Optional[float] = None
    tpot_slo_s: Optional[float] = None
    # filled in by the scheduler
    tokens: list = field(default_factory=list)
    result: Any = None              # vision: prediction array
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    preemptions: int = 0            # times this request's slot was parked
    prefix_hit_tokens: int = 0      # prefill tokens skipped via prefix cache
    # vision: the bucket forward that served it (the ``step`` stat of its
    # ``repro.vision.quantum`` span, whose ``task`` is task_id)
    step: Optional[int] = None

    @property
    def ttft(self) -> float:
        """Arrival -> first token; nan until the first token exists (a
        ``0 - arrival`` garbage value here used to poison percentiles)."""
        if self.t_first is None:
            return float("nan")
        return self.t_first - self.arrival

    @property
    def latency(self) -> float:
        if self.t_done is None:
            return float("nan")
        return self.t_done - self.arrival

    @property
    def tpot(self) -> float:
        """Mean time per output token after the first (nan unfinished)."""
        return request_tpot(self)


def _pad_len(s0: int, bucket: int) -> int:
    return s0 if bucket <= 0 else -(-s0 // bucket) * bucket


def _state_bytes(state) -> int:
    return sum(int(l.nbytes) for l in jax.tree.leaves(state))


class _StateSlots:
    """Recovers the per-leaf batch axis of a batched decode state, for
    splicing a batch-1 state into slot ``i`` (``LMBackend.admit_step``).

    The batch axis differs per leaf (stacked scanned layers prepend the
    period axis), so it is recovered structurally: build the state shape
    twice with different batch sizes and the axis whose dim changed is the
    batch axis.
    """

    def __init__(self, cfg: ArchConfig, max_len: int):
        self._axes = state_batch_axes(cfg, max_len)


@dataclass
class _PrefillJob:
    """An in-flight chunked admission: a reserved slot plus a batch-1
    staging state advanced one ``prefill_chunk`` per decode step."""

    req: Request
    slot: int
    small: Any          # batch-1 staging state
    prompt: np.ndarray  # (1, S0[, d])
    off: int            # next prefill position (prefix-matched tokens skip)
    s0: int


class LMBackend:
    """Autoregressive decode backend with *mixed-task* batches: one decode
    step serves slots gated by different tasks (per-token gating — the
    per-slot generalization of the paper's zero-cost task switch), with
    vector cache positions and MoE router-usage export.  Admission prefills
    are per-task jitted (the §IV-F cached-pointer switch)."""

    bucketing = "mixed"   # one full-width bucket; fairness at admission

    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig,
                 rules: Optional[ShardingRules] = None,
                 prompt_pad: int = 16):
        if scfg.temperature > 0.0:
            raise ValueError("the scheduler decodes greedily (argmax is "
                             "fused into the jitted step)")
        from repro.serve.engine import _policy_override, place_params

        self.cfg = cfg = _policy_override(cfg, scfg)
        self.params = place_params(params, rules)
        self.scfg = scfg
        self.rules = rules
        self.recurrent = is_recurrent(cfg)
        # padded prefill relies on cache_len masking — attention archs only
        self.prompt_pad = 0 if self.recurrent else prompt_pad
        self.num_tasks = max(cfg.num_tasks,
                             cfg.moe.num_tasks if cfg.moe else 1)
        self.usage = (ExpertUsage(cfg.moe.num_experts, self.num_tasks)
                      if cfg.moe else None)
        self._slots_io = _StateSlots(cfg, scfg.max_len)
        self._prefill: dict[int, Any] = {}   # task -> jitted fused admit
        self._decode_fn = None               # one decode fn, tasks traced
        self._staged: dict[int, tuple] = {}  # task -> (mid, finish) jits
        self._parkers: dict[str, SlotParker] = {}
        # shared prompt-prefix reuse needs the attention truncation
        # property (stale rows masked by causal/cache_len); recurrent
        # state is a running reduction, so no cache for those archs
        self.prefix: Optional[RadixPrefixCache] = None
        if scfg.prefix_cache > 0 and not self.recurrent:
            self.prefix = RadixPrefixCache(
                scfg.prefix_cache, min_match=max(1, scfg.prefix_min))

    # ----------------------------------------------------------- steps

    def admit_step(self, task_id: int):
        """One fused jitted call per admission: batch-1 prefill against an
        in-graph zero state, greedy first token at the last REAL prompt
        position, and splice into the (donated) bucket state slot."""
        if task_id not in self._prefill:
            cfg, rules, scfg = self.cfg, self.rules, self.scfg
            axes = self._slots_io._axes

            def admit(params, inputs, big_state, slot, last_idx):
                with use_rules(rules):
                    small = M.init_state(cfg, 1, scfg.max_len)
                    logits, st, _ = M.forward(
                        params, inputs, cfg, state=small, cache_index=0,
                        task_id=task_id, return_state=True)
                tok = jnp.argmax(jax.lax.dynamic_index_in_dim(
                    logits, last_idx, axis=1, keepdims=False)[0], axis=-1)
                leaves, treedef = jax.tree_util.tree_flatten(big_state)
                small_leaves = jax.tree.leaves(st)
                out = [jax.lax.dynamic_update_slice_in_dim(b, s, slot,
                                                           axis=ax)
                       for b, s, ax in zip(leaves, small_leaves, axes)]
                return tok.astype(jnp.int32), \
                    jax.tree_util.tree_unflatten(treedef, out)

            self._prefill[task_id] = jax.jit(admit, donate_argnums=(2,))
        return self._prefill[task_id]

    def staged_steps(self, task_id: int):
        """Jitted staged-admission steps, cached per task.

        ``mid(params, toks, small, idx) -> small``           one chunk;
        ``finish(params, toks, small, idx, last_rel, big, slot)
              -> (first_tok, small_out, big_out)``  final chunk + splice.

        Unlike the fused ``admit_step`` these run against an *explicit*
        batch-1 staging state, which is what lets an admission (a) start
        from a prefix-cache entry at offset ``idx`` and (b) advance one
        chunk at a time between decode steps.  ``small`` is never donated
        — a prefix-cache entry must survive being read — and
        ``small_out`` is returned so the finished prompt can be inserted
        into the cache.
        """
        if task_id not in self._staged:
            cfg, rules = self.cfg, self.rules
            axes = self._slots_io._axes

            def mid(params, toks, small, idx):
                with use_rules(rules):
                    _, st, _ = M.forward(
                        params, toks, cfg, state=small, cache_index=idx,
                        task_id=task_id, return_state=True,
                        logits_mode="last")
                return st

            def finish(params, toks, small, idx, last_rel, big, slot):
                with use_rules(rules):
                    logits, st, _ = M.forward(
                        params, toks, cfg, state=small, cache_index=idx,
                        task_id=task_id, return_state=True)
                tok = jnp.argmax(jax.lax.dynamic_index_in_dim(
                    logits, last_rel, axis=1, keepdims=False)[0], axis=-1)
                leaves, treedef = jax.tree_util.tree_flatten(big)
                small_leaves = jax.tree.leaves(st)
                out = [jax.lax.dynamic_update_slice_in_dim(b, s, slot,
                                                           axis=ax)
                       for b, s, ax in zip(leaves, small_leaves, axes)]
                return (tok.astype(jnp.int32), st,
                        jax.tree_util.tree_unflatten(treedef, out))

            self._staged[task_id] = (
                jax.jit(mid), jax.jit(finish, donate_argnums=(5,)))
        return self._staged[task_id]

    def decode_step(self):
        """One decode fn for every batch composition: the per-slot task ids
        are a traced (B,) operand, so mixing tasks never recompiles."""
        if self._decode_fn is None:
            cfg, rules = self.cfg, self.rules
            want_counts = cfg.moe is not None

            def decode(params, toks, state, cache_pos, task_ids):
                with use_rules(rules):
                    out = M.forward(
                        params, feedback_inputs(cfg, toks), cfg, state=state,
                        cache_index=cache_pos, decode=True,
                        task_id=task_ids, return_state=True,
                        return_expert_counts=want_counts)
                if want_counts:
                    logits, st, _, counts = out
                else:
                    logits, st, _ = out
                    counts = jnp.zeros((0,), jnp.int32)
                # greedy sampling stays in-graph: one host sync per step
                return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), \
                    st, counts

            self._decode_fn = jax.jit(decode, donate_argnums=(2,))
        return self._decode_fn

    def parker(self, compress: str = "none") -> SlotParker:
        """Park/restore machinery for this backend's state layout (one
        jit pair per compression mode, shared by every bucket)."""
        if compress not in self._parkers:
            shapes = jax.tree.leaves(jax.eval_shape(
                lambda: M.init_state(self.cfg, 1, self.scfg.max_len)))
            self._parkers[compress] = SlotParker(
                self._slots_io._axes, shapes, compress)
        return self._parkers[compress]

    def make_bucket(self, task_id: int, slots: int) -> "LMTaskBucket":
        return LMTaskBucket(self, task_id, slots)


class LMTaskBucket:
    """``slots`` decode lanes.  With ``task_id=None`` (the LM backend's
    mixed mode) every slot carries its own task id into the decode step;
    with a fixed task id all lanes share one gating network."""

    def __init__(self, backend: LMBackend, task_id: Optional[int],
                 slots: int):
        self.backend = backend
        self.task_id = task_id
        self.slots = slots
        # decode lanes live batch-sharded over the data axes when a mesh is
        # active — admit splices and decode steps keep that placement
        self.state = shard_state(
            M.init_state(backend.cfg, slots, backend.scfg.max_len),
            backend.rules, backend._slots_io._axes)
        self.cache_pos = np.zeros((slots,), np.int32)
        self.last_tok = np.zeros((slots,), np.int32)
        self.task_slots = np.zeros((slots,), np.int32)
        self.reqs: list[Optional[Request]] = [None] * slots
        self.jobs: list[_PrefillJob] = []   # in-flight chunked admissions
        self.reserved: set[int] = set()     # slots held by jobs
        self.steps = 0               # decode steps executed
        self.slot_steps = 0          # decode slot-steps with a live request
        self.prefill_chunks = 0      # interleaved chunk steps executed

    @property
    def active(self) -> int:
        return sum(r is not None for r in self.reqs)

    @property
    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.reqs)
                if r is None and i not in self.reserved]

    def _eos(self, req: Request) -> int:
        return self.backend.scfg.eos_id if req.eos_id is None else req.eos_id

    def _emit(self, req: Request, tok: int, now: float):
        """Record one generated token; returns True when the request is
        done (its own EOS or token budget — the slot frees immediately)."""
        req.tokens.append(tok)
        if req.t_first is None:
            req.t_first = now
        eos = self._eos(req)
        return (eos >= 0 and tok == eos) \
            or len(req.tokens) >= req.max_new_tokens

    # ------------------------------------------------------- admission

    def _activate(self, req: Request, slot: int, tok: int, s0: int,
                  now: float) -> list[Request]:
        """Common admission tail: wire the slot and emit the first token."""
        self.cache_pos[slot] = s0
        self.last_tok[slot] = tok
        self.task_slots[slot] = req.task_id
        self.reqs[slot] = req
        if self._emit(req, tok, now):
            req.t_done = now
            self.reqs[slot] = None
            self.cache_pos[slot] = 0
            self.last_tok[slot] = 0
            return [req]
        return []

    def admit(self, req: Request, now: float,
              chunk_interleave: bool = False) -> list[Request]:
        """Prefill ``req`` and splice it into a free slot.

        Three admission paths, cheapest applicable wins:
          * fused one-shot (no prefix cache): batch-1 prefill against an
            in-graph zero state, one jitted call;
          * staged one-shot: explicit staging state — seeded from the
            radix prefix cache when the prompt shares a cached prefix
            (only the suffix is prefilled, at ``cache_index = L``) and
            inserted back into the cache afterwards;
          * chunked job (``chunk_interleave``): the slot is reserved and
            the prompt advances one ``prefill_chunk`` per decode step
            (``advance_prefill``), so long prompts stop head-of-line-
            blocking the decode batch.
        """
        b = self.backend
        slot = self.free_slots[0]
        prompt = np.asarray(req.prompt)[None]        # (1, S0[, d])
        s0 = prompt.shape[1]
        padded = _pad_len(s0, b.prompt_pad)
        if padded > b.scfg.max_len:
            raise ValueError(f"prompt {s0} > max_len {b.scfg.max_len}")
        if s0 + req.max_new_tokens - 1 > b.scfg.max_len:
            # decode step i writes K/V at position s0+i: reject a request
            # that cannot fit BEFORE it occupies a slot, not mid-flight
            raise ValueError(
                f"request {req.rid}: prompt {s0} + {req.max_new_tokens} "
                f"new tokens does not fit max_len {b.scfg.max_len}")

        # shared-prefix lookup (token prompts on attention archs only)
        entry, matched = None, 0
        if b.prefix is not None and prompt.ndim == 2:
            entry, matched = b.prefix.lookup(prompt[0])
            matched = min(matched, s0 - 1)   # always prefill >= 1 token
            if entry is None or matched < b.prefix.min_match:
                entry, matched = None, 0

        chunk = b.scfg.prefill_chunk
        suffix_len = s0 - matched
        # chunking only pays while there are active decoders to protect:
        # on an idle batch a one-shot prefill blocks nobody and is far
        # cheaper than a chunk-per-step dispatch train
        if (chunk_interleave and self.active > 0 and chunk > 0
                and suffix_len > chunk and not b.recurrent
                and matched + _pad_len(suffix_len, chunk) <= b.scfg.max_len):
            small = entry if entry is not None \
                else M.init_state(b.cfg, 1, b.scfg.max_len)
            self.jobs.append(_PrefillJob(req=req, slot=slot, small=small,
                                         prompt=prompt, off=matched, s0=s0))
            self.reserved.add(slot)
            req.t_admit = now
            req.prefix_hit_tokens = matched
            return []

        req.t_admit = now
        if b.prefix is None or prompt.ndim != 2:
            # legacy fused path (also serves embedding prompts)
            if padded != s0:
                pad = np.zeros((1, padded - s0) + prompt.shape[2:],
                               prompt.dtype)
                prompt = np.concatenate([prompt, pad], axis=1)
            tok, self.state = b.admit_step(req.task_id)(
                b.params, jnp.asarray(prompt), self.state, slot,
                jnp.int32(s0 - 1))
            return self._activate(req, slot, int(np.asarray(tok)), s0, now)
        tok = self._admit_staged(req, slot, entry, matched, prompt)
        return self._activate(req, slot, tok, s0, now)

    def _admit_staged(self, req: Request, slot: int, entry, matched: int,
                      prompt: np.ndarray) -> int:
        """One-shot staged admission: suffix prefill at offset ``matched``
        (0 with a fresh staging state on a prefix miss), splice, and
        insert the finished prompt's state into the prefix cache."""
        b = self.backend
        s0 = prompt.shape[1]
        if matched and matched + _pad_len(s0 - matched, b.prompt_pad) \
                > b.scfg.max_len:
            # padded suffix would write past the cache: drop the hit
            # rather than let dynamic_update_slice clamp-shift the rows
            entry, matched = None, 0
        small = entry if entry is not None \
            else M.init_state(b.cfg, 1, b.scfg.max_len)
        suffix = prompt[:, matched:]
        padded = _pad_len(suffix.shape[1], b.prompt_pad)
        if padded != suffix.shape[1]:
            pad = np.zeros((1, padded - suffix.shape[1]) + suffix.shape[2:],
                           suffix.dtype)
            suffix = np.concatenate([suffix, pad], axis=1)
        _, finish = b.staged_steps(req.task_id)
        tok, small_out, self.state = finish(
            b.params, jnp.asarray(suffix), small, jnp.int32(matched),
            jnp.int32(s0 - matched - 1), self.state, slot)
        req.prefix_hit_tokens = matched
        b.prefix.insert(prompt[0], small_out, _state_bytes(small_out))
        return int(np.asarray(tok))

    def advance_prefill(self, now_fn) -> list[Request]:
        """Advance EVERY chunked admission by one chunk (called once per
        decode step, the interleaving grain).  Jobs progress in parallel —
        a reserved slot idles for ~(prompt/chunk) steps, not for the sum
        of every queued prompt's chunks.  A job's final chunk fuses
        first-token sampling with the slot splice, exactly like a one-shot
        admission — token-identical either way."""
        b = self.backend
        finished: list[Request] = []
        chunk = b.scfg.prefill_chunk
        for job in list(self.jobs):
            mid, finish = b.staged_steps(job.req.task_id)
            remaining = job.s0 - job.off
            self.prefill_chunks += 1
            if remaining > chunk:
                toks = jnp.asarray(job.prompt[:, job.off:job.off + chunk])
                job.small = mid(b.params, toks, job.small,
                                jnp.int32(job.off))
                job.off += chunk
                continue
            tail = job.prompt[:, job.off:]
            if remaining < chunk:   # pad final chunk to the compiled width
                pad = np.zeros((1, chunk - remaining) + tail.shape[2:],
                               tail.dtype)
                tail = np.concatenate([tail, pad], axis=1)
            tok, small_out, self.state = finish(
                b.params, jnp.asarray(tail), job.small, jnp.int32(job.off),
                jnp.int32(remaining - 1), self.state, job.slot)
            if b.prefix is not None and job.prompt.ndim == 2:
                b.prefix.insert(job.prompt[0], small_out,
                                _state_bytes(small_out))
            self.jobs.remove(job)
            self.reserved.discard(job.slot)
            finished.extend(self._activate(
                job.req, job.slot, int(np.asarray(tok)), job.s0, now_fn()))
        return finished

    # ------------------------------------------------------ preemption

    def pick_victim(self) -> Optional[int]:
        """The preemption victim: the *youngest* preemptible (batch-tier)
        slot — the least sunk decode work in the current burst."""
        cands = [(r.t_admit or 0.0, i) for i, r in enumerate(self.reqs)
                 if r is not None and is_preemptible(r)]
        return max(cands)[1] if cands else None

    def park(self, slot: int, parker: SlotParker) -> dict:
        """Evict ``slot``: extract its state bit-exactly (optionally int8-
        packed) and free the lane.  Returns the parked record."""
        req = self.reqs[slot]
        parked = {"req": req,
                  "state": parker.park(self.state, slot),
                  "cache_pos": int(self.cache_pos[slot]),
                  "last_tok": int(self.last_tok[slot])}
        req.preemptions += 1
        self.reqs[slot] = None
        self.cache_pos[slot] = 0
        self.last_tok[slot] = 0
        return parked

    def restore(self, parked: dict, parker: SlotParker) -> int:
        """Splice a parked record back into a free slot and resume decode
        where it left off (same cache position, same feedback token)."""
        slot = self.free_slots[0]
        self.state = parker.restore(self.state, parked["state"], slot)
        req = parked["req"]
        self.cache_pos[slot] = parked["cache_pos"]
        self.last_tok[slot] = parked["last_tok"]
        self.task_slots[slot] = req.task_id
        self.reqs[slot] = req
        return slot

    # ---------------------------------------------------------- decode

    def run_quantum(self, n: int, now_fn,
                    admit_cb=None) -> list[Request]:
        """Up to ``n`` decode steps over the whole bucket; returns finished
        requests (their slots are already freed).  ``admit_cb`` runs before
        every step so slots freed mid-quantum refill immediately — the
        continuous part of continuous batching.  In-flight chunked
        admissions advance one chunk per step, interleaved with decode."""
        b = self.backend
        decode = b.decode_step()
        finished: list[Request] = []
        counts_sum = None
        for _ in range(n):
            if admit_cb is not None:
                admit_cb()
            if self.jobs:
                finished.extend(self.advance_prefill(now_fn))
                # no decodable slot -> no decode latency to protect:
                # drain prefill chunks at full speed until a job
                # activates (admissions stay live via admit_cb)
                while self.active == 0 and self.jobs:
                    if admit_cb is not None:
                        admit_cb()
                    finished.extend(self.advance_prefill(now_fn))
            if self.active == 0:
                break
            tok, self.state, counts = decode(
                b.params, jnp.asarray(self.last_tok), self.state,
                jnp.asarray(self.cache_pos), jnp.asarray(self.task_slots))
            self.steps += 1
            self.slot_steps += self.active
            if b.usage is not None:   # device-side accumulate, sync once
                counts_sum = counts if counts_sum is None \
                    else counts_sum + counts
            nxt = np.asarray(tok)
            now = now_fn()
            for i, req in enumerate(self.reqs):
                if req is None:
                    continue
                self.cache_pos[i] += 1
                self.last_tok[i] = nxt[i]
                if self._emit(req, int(nxt[i]), now):
                    # finished-first: a request whose generation exactly
                    # fills the cache frees its slot instead of tripping
                    # the overrun guard below
                    req.t_done = now
                    self.reqs[i] = None
                    self.cache_pos[i] = 0
                    self.last_tok[i] = 0
                    finished.append(req)
                elif self.cache_pos[i] >= b.scfg.max_len:
                    raise RuntimeError("decode ran past max_len")
        if counts_sum is not None and self.backend.usage is not None:
            c = np.asarray(counts_sum)
            if c.ndim == 2:        # mixed batch: one (E,) row per task
                for t in range(c.shape[0]):
                    if c[t].any():
                        self.backend.usage.update(c[t], t)
            else:
                self.backend.usage.update(c, self.task_id or 0)
        return finished


def _interactive(req: Request) -> bool:
    return not is_preemptible(req)


class Scheduler:
    """Task-fair continuous batching over a backend's buckets.

    Two bucketing modes (picked by ``backend.bucketing``):

      * ``"mixed"`` (LM decode): ONE bucket spanning ``total_slots`` decode
        lanes; freed slots are offered round-robin across task queues, so a
        hot task cannot monopolize admission while the decode batch itself
        mixes tasks (per-slot gating).
      * ``"per_task"`` (vision): one bucket per task, ``total_slots`` split
        evenly; decode/infer quanta rotate round-robin across runnable
        tasks.

    Either way total batch capacity equals a static engine's batch of
    ``total_slots``.

    ``slo`` (an :class:`repro.serve.slo.SLOPolicy`) turns on tiered
    admission: interactive queues admit first (still round-robin across
    tasks within a tier), due interactive requests preempt batch-tier
    decode slots (KV park/restore — bit-exact), parked requests restore
    FIFO once the burst passes, and long prompts admit chunk-interleaved.
    """

    def __init__(self, backend, total_slots: int = 8, quantum: int = 4,
                 num_tasks: Optional[int] = None, clock=None,
                 slo: Optional[SLOPolicy] = None):
        self.backend = backend
        self.num_tasks = num_tasks or getattr(backend, "num_tasks", 1)
        self.mixed = getattr(backend, "bucketing", "per_task") == "mixed"
        self.slots_per_bucket = total_slots if self.mixed \
            else max(1, total_slots // self.num_tasks)
        self.quantum = quantum
        self.clock = clock or time.perf_counter
        self.slo = slo
        self.buckets: dict[Any, Any] = {}
        self.queues: dict[int, deque] = {}
        self.rotation: list[int] = []
        self._rr = 0
        self.finished: list[Request] = []
        self._t0: Optional[float] = None
        # SLO machinery
        self.parked: deque = deque()
        self.preemptions = 0
        self.restores = 0
        self.parked_bytes = 0
        self.parked_bytes_peak = 0
        self._parker: Optional[SlotParker] = None

    def now(self) -> float:
        if self._t0 is None:
            self._t0 = self.clock()
        return self.clock() - self._t0

    def submit(self, req: Request) -> None:
        if req.task_id not in self.queues:
            self.queues[req.task_id] = deque()
            self.rotation.append(req.task_id)
        self.queues[req.task_id].append(req)

    def _bucket(self, key):
        if key not in self.buckets:
            self.buckets[key] = self.backend.make_bucket(
                key, self.slots_per_bucket)
        return self.buckets[key]

    def _runnable(self, task_id: int, now: float) -> bool:
        q = self.queues.get(task_id)
        queued = bool(q) and (q[0].arrival <= now if self.slo is None
                              else any(r.arrival <= now for r in q))
        bucket = self.buckets.get(task_id)
        return queued or (bucket is not None and bucket.active > 0)

    def _peek_next_task(self, current: int, now: float) -> Optional[int]:
        """The task the rotation will pick after ``current`` — the cross-
        bucket lookahead target whose hot experts can stream behind the
        quantum that is about to run."""
        for off in range(len(self.rotation)):
            t = self.rotation[(self._rr + off) % len(self.rotation)]
            if t != current and self._runnable(t, now):
                return t
        return None

    def pending(self) -> bool:
        if self.parked:
            return True
        if any(self.queues.get(t) for t in self.rotation):
            return True
        return any(b.active > 0 or getattr(b, "jobs", None)
                   for b in self.buckets.values())

    # ------------------------------------------------------- admission

    def _pop_due(self, task: int, now: float, pred=None):
        """Pop the first due request in ``task``'s queue matching ``pred``
        (SLO mode scans past not-yet-due heads; legacy admission is
        strictly head-of-queue and does not use this)."""
        q = self.queues.get(task)
        if not q:
            return None
        for i, r in enumerate(q):
            if r.arrival <= now and (pred is None or pred(r)):
                del q[i]
                return r
        return None

    def _due_any(self, now: float, pred) -> bool:
        return any(r.arrival <= now and pred(r)
                   for q in self.queues.values() for r in q)

    def _task_due(self, task: int, now: float, pred) -> bool:
        return any(r.arrival <= now and pred(r)
                   for r in self.queues.get(task, ()))

    def _admit_mixed(self, bucket) -> bool:
        """Offer freed slots round-robin across task queues (one request per
        runnable task per lap) — admission-level fairness for mixed mode."""
        admitted = False
        progress = True
        while bucket.free_slots and progress and self.rotation:
            progress = False
            for off in range(len(self.rotation)):
                if not bucket.free_slots:
                    break
                t = self.rotation[(self._rr + off) % len(self.rotation)]
                q = self.queues.get(t)
                if q and q[0].arrival <= self.now():
                    self.finished.extend(
                        bucket.admit(q.popleft(), self.now()))
                    self._rr = (self._rr + off + 1) % len(self.rotation)
                    admitted = progress = True
                    break
        return admitted

    def _admit_lap(self, bucket, pred, limit: Optional[int] = None) -> bool:
        """Round-robin admission laps restricted to ``pred`` requests —
        the SLO-mode analogue of ``_admit_mixed`` (task fairness holds
        *within* each tier)."""
        interleave = bool(self.slo and self.slo.chunk_interleave)
        admitted = 0
        progress = True
        while bucket.free_slots and progress and self.rotation:
            progress = False
            for off in range(len(self.rotation)):
                if not bucket.free_slots:
                    break
                t = self.rotation[(self._rr + off) % len(self.rotation)]
                r = self._pop_due(t, self.now(), pred)
                if r is not None:
                    self.finished.extend(bucket.admit(
                        r, self.now(), chunk_interleave=interleave))
                    self._rr = (self._rr + off + 1) % len(self.rotation)
                    admitted += 1
                    progress = True
                    if limit is not None and admitted >= limit:
                        return True
                    break
        return admitted > 0

    def _get_parker(self) -> Optional[SlotParker]:
        if self._parker is None:
            mk = getattr(self.backend, "parker", None)
            if mk is not None:
                self._parker = mk(self.slo.park_compress)
        return self._parker

    def _park_victim(self, bucket) -> bool:
        victim = bucket.pick_victim()
        if victim is None:
            return False
        parked = bucket.park(victim, self._parker)
        self.parked.append(parked)
        self.preemptions += 1
        self.parked_bytes += parked["state"].nbytes
        self.parked_bytes_peak = max(self.parked_bytes_peak,
                                     self.parked_bytes)
        return True

    def _admit_slo(self, bucket) -> bool:
        """Tiered admission: interactive first, then preemption for the
        still-waiting interactive, then FIFO restores of parked requests,
        then batch admission into whatever capacity remains."""
        admitted = self._admit_lap(bucket, _interactive)
        if self.slo.preemption and self._get_parker() is not None:
            while (not bucket.free_slots
                   and len(self.parked) < self.slo.max_parked
                   and self._due_any(self.now(), _interactive)):
                if not self._park_victim(bucket):
                    break
                admitted |= self._admit_lap(bucket, _interactive, limit=1)
        while (bucket.free_slots and self.parked
               and not self._due_any(self.now(), _interactive)):
            parked = self.parked.popleft()
            bucket.restore(parked, self._get_parker())
            self.parked_bytes -= parked["state"].nbytes
            self.restores += 1
            admitted = True
        admitted |= self._admit_lap(bucket, is_preemptible)
        return admitted

    # ------------------------------------------------------------ step

    def step(self) -> bool:
        """One scheduling quantum.  Returns False when nothing was runnable
        (e.g. every remaining arrival is in the future)."""
        with TraceAnnotation("repro.sched.step"):
            return self._step()

    def _step(self) -> bool:
        now = self.now()
        if self.mixed:
            bucket = self._bucket(None)
            admit = self._admit_slo if self.slo is not None \
                else self._admit_mixed
            admitted = admit(bucket)
            if bucket.active == 0 and not admitted and not bucket.jobs:
                return False
            self.finished.extend(bucket.run_quantum(
                self.quantum, self.now,
                admit_cb=lambda: admit(bucket)))
            return True
        # per-task buckets: with an SLO policy, tasks holding a due
        # interactive request take the quantum first
        offsets = list(range(len(self.rotation)))
        if self.slo is not None:
            urgent = [o for o in offsets if self._task_due(
                self.rotation[(self._rr + o) % len(self.rotation)],
                now, _interactive)]
            rest = [o for o in offsets if o not in urgent]
            offsets = urgent + rest
        for off in offsets:
            task = self.rotation[(self._rr + off) % len(self.rotation)]
            if self._runnable(task, now):
                self._rr = (self._rr + off + 1) % len(self.rotation)
                bucket = self._bucket(task)
                q = self.queues[task]

                def fill():
                    if self.slo is None:
                        while bucket.free_slots and q \
                                and q[0].arrival <= self.now():
                            done = bucket.admit(q.popleft(), self.now())
                            self.finished.extend(done)
                        return
                    # tiered: interactive first, then batch
                    while bucket.free_slots:
                        r = self._pop_due(task, self.now(), _interactive) \
                            or self._pop_due(task, self.now(),
                                             is_preemptible)
                        if r is None:
                            break
                        self.finished.extend(bucket.admit(r, self.now()))
                    # stateless "preemption": bump a staged batch-tier
                    # request back to the queue to seat a due interactive
                    bump = getattr(bucket, "bump_batch", None)
                    if bump is None or not self.slo.preemption:
                        return
                    while not bucket.free_slots and self._task_due(
                            task, self.now(), _interactive):
                        bumped = bump()
                        if bumped is None:
                            break
                        self.queues[task].appendleft(bumped)
                        self.preemptions += 1
                        r = self._pop_due(task, self.now(), _interactive)
                        if r is None:
                            break
                        self.finished.extend(bucket.admit(r, self.now()))

                def admit():
                    with TraceAnnotation("repro.sched.admit"):
                        fill()

                admit()
                # router lookahead across buckets: submit the NEXT task's
                # usage-hot experts before this quantum launches, so their
                # copies ride behind its compute.  The current task's own
                # prefetch runs inside run_quantum AFTER this, so where the
                # two sets conflict the current task wins the slots.
                la = getattr(self.backend, "lookahead", None)
                if la is not None:
                    nxt = self._peek_next_task(task, now)
                    if nxt is not None:
                        with TraceAnnotation("repro.sched.lookahead"):
                            la(nxt)
                self.finished.extend(bucket.run_quantum(
                    self.quantum, self.now, admit_cb=admit))
                return True
        return False

    def run(self, requests=None) -> list[Request]:
        """Submit ``requests`` (optional) and drain everything.  Spins (with
        a tiny sleep) while all remaining arrivals are in the future —
        open-loop driving."""
        for r in requests or ():
            self.submit(r)
        self.now()                     # start the clock
        while self.pending():
            if not self.step():
                time.sleep(0.0005)
        return self.finished

    # ------------------------------------------------------------ metrics

    def metrics(self) -> dict[str, Any]:
        done = [r for r in self.finished if r.t_done is not None]
        toks = sum(len(r.tokens) for r in done)
        items = len(done)
        span = max((r.t_done for r in done), default=0.0) - \
            min((r.arrival for r in done), default=0.0)
        # unfinished requests report nan ttft/latency — filter, and guard
        # every percentile against an empty sample (an empty ``done`` used
        # to crash here; a half-finished one used to skew the tail)
        lat = np.array([r.latency for r in done], np.float64)
        lat = lat[np.isfinite(lat)]
        ttft = np.array([r.ttft for r in done], np.float64)
        ttft = ttft[np.isfinite(ttft)]

        def pct(a, p):
            return float(np.percentile(a, p)) if a.size else 0.0

        out: dict[str, Any] = {
            "requests": items,
            "tokens": toks,
            "span_s": span,
            "tok_per_s": toks / span if span > 0 else 0.0,
            "items_per_s": items / span if span > 0 else 0.0,
            "latency_p50_s": pct(lat, 50),
            "latency_p99_s": pct(lat, 99),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p99_s": pct(ttft, 99),
            "per_task": {
                t: sum(1 for r in done if r.task_id == t)
                for t in self.rotation
            },
        }
        # goodput-under-SLO + per-tier tails (requests without deadlines
        # count as met, so these reduce to throughput when SLOs are unset)
        out.update(goodput(done, span))
        tiers: dict[str, Any] = {}
        for name in sorted({r.tier for r in done}):
            rs = [r for r in done if r.tier == name]
            tt = np.array([r.ttft for r in rs], np.float64)
            tt = tt[np.isfinite(tt)]
            tp = np.array([request_tpot(r) for r in rs], np.float64)
            tp = tp[np.isfinite(tp)]
            tiers[name] = {
                "requests": len(rs),
                "ttft_p50_s": pct(tt, 50),
                "ttft_p99_s": pct(tt, 99),
                "tpot_p50_s": pct(tp, 50),
                "slo_attainment": sum(meets_slo(r) for r in rs) / len(rs),
                "preemptions": sum(r.preemptions for r in rs),
            }
        out["tiers"] = tiers
        if self.slo is not None:
            out["preemptions"] = self.preemptions
            out["restores"] = self.restores
            out["parked_now"] = len(self.parked)
            out["parked_bytes_peak"] = self.parked_bytes_peak
        prefix = getattr(self.backend, "prefix", None)
        if prefix is not None:
            out["prefix_cache"] = prefix.stats()
        chunks = sum(getattr(b, "prefill_chunks", 0)
                     for b in self.buckets.values())
        if chunks:
            out["prefill_chunks"] = chunks
        usage = getattr(self.backend, "usage", None)
        if usage is not None:
            out["expert_usage_task_overlap"] = usage.task_overlap()
        slot_steps = sum(getattr(b, "slot_steps", 0)
                         for b in self.buckets.values())
        steps = sum(getattr(b, "steps", 0) for b in self.buckets.values())
        cap = self.slots_per_bucket
        if steps:
            out["slot_utilization"] = slot_steps / (steps * cap)
        cache_stats = getattr(self.backend, "cache_stats", None)
        if callable(cache_stats):
            cs = cache_stats()
            out["expert_cache"] = cs
            # expert-parallel backends report placement evidence (plan
            # generation, migration/replication events, per-shard load) —
            # surface it top-level so serving reports and benchmark
            # artifacts need not dig through the cache blob
            if "placement" in cs:
                out["placement"] = cs["placement"]
                out["shard_load"] = cs.get("shard_load")
                out["shard_load_imbalance"] = cs.get("shard_load_imbalance")
        return out
