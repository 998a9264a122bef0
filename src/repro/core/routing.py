"""Expert-by-expert computation reordering (Edge-MoE §IV-D).

The paper's MoE insight: never compute token-by-token (which reloads expert
weights constantly, Fig. 9c) — instead build **per-expert queues** of token
indices during gating, plus a **metaqueue** of experts with non-empty queues;
then process expert-by-expert, loading each expert's weights exactly once and
computing all of its queued tokens before moving on (Fig. 9d).  Gate scores
weight each expert's output as it is accumulated onto the token's partial
output, so no separate aggregation pass exists.

TPU adaptation.  The queue construction is a stable sort of (token, expert)
assignments by expert id; the expert-by-expert sweep is a grouped GEMM over
the sorted/grouped token buffer.  We realize it with fixed-capacity per-expert
buffers (shape-static, SPMD-friendly):

  * ``route_topk``            — gating softmax (single-pass, §IV-B) + top-k.
  * ``build_dispatch``        — the queues: for every (token, slot) its expert,
                                its position in that expert's buffer, and a
                                validity bit (capacity overflow ⇒ dropped, as
                                in GShard; tests use capacity=T so the grouped
                                path is exact vs the dense reference).
  * ``dispatch``/``combine``  — gather tokens into (E, C, d) per-expert
                                buffers and weighted-scatter results back
                                (the paper's indirect reader/writer).
  * ``load_balance_loss``     — auxiliary loss (standard Switch/GShard form),
                                the training-time counterpart of the paper's
                                "workload imbalance" concern.

At pod scale the same reordering inverts: experts stay resident (expert
parallelism over the `model` mesh axis) and the (E, C, d) dispatch buffer is
what moves through the all-to-all — the distributed expression of "load each
expert once".  A dense one-hot einsum path (``dispatch_onehot``) lowers to the
cleanest GSPMD collectives and is used for the multi-pod dry-run; it is
bit-identical to the gather path.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import online_softmax

__all__ = [
    "route",
    "route_topk",
    "build_dispatch",
    "dispatch",
    "combine_rows",
    "dispatch_counts",
    "dispatch_onehot",
    "combine",
    "combine_onehot",
    "load_balance_loss",
    "Routing",
]


class Routing(NamedTuple):
    """Routing decision for T tokens, k slots each, E experts, capacity C."""

    expert: jax.Array      # (T, k) int32 — selected expert per slot
    gate: jax.Array        # (T, k) f32   — combine weight per slot
    position: jax.Array    # (T, k) int32 — row within the expert's buffer
    valid: jax.Array       # (T, k) bool  — False if dropped by capacity
    probs: jax.Array       # (T, E) f32   — full gating distribution (aux loss)


def route_topk(gate_logits: jax.Array, k: int, *, renormalize: bool = True):
    """Top-k experts + combine weights from gating logits (T, E).

    Softmax uses the single-pass dynamic-bias formulation (§IV-B) — the paper
    applies the same softmax module to MoE gating.  ``renormalize`` divides the
    selected gates so they sum to 1 over the k slots (M3ViT convention).
    """
    probs = online_softmax.softmax(gate_logits.astype(jnp.float32), axis=-1)
    gate, expert = jax.lax.top_k(probs, k)
    if renormalize:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return expert.astype(jnp.int32), gate, probs


def build_dispatch(expert: jax.Array, num_experts: int, capacity: int):
    """Construct the per-expert queues (paper Fig. 9d) with fixed capacity.

    ``position[t, s]`` is the index of token t (slot s) inside expert
    ``expert[t, s]``'s queue — computed with a cumulative count in token
    order, which is exactly the arrival-order queue of the paper.  Entries
    beyond ``capacity`` are invalid (dropped).  The metaqueue ("skip empty
    experts") emerges as experts whose queue length is 0: the grouped GEMM
    kernel skips zero-size groups.

    Returns (position (T, k) int32, valid (T, k) bool).
    """
    t, k = expert.shape
    flat = expert.reshape(-1)  # token-major: each token's k slots consecutive
    onehot = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)
    # position of each assignment within its expert's queue (exclusive cumsum)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)
    position = jnp.take_along_axis(pos_in_expert, flat[:, None], axis=1)[:, 0]
    valid = position < capacity
    return position.reshape(t, k).astype(jnp.int32), valid.reshape(t, k)


def route(gate_logits: jax.Array, k: int, capacity: int, *,
          renormalize: bool = True) -> Routing:
    """route_topk + build_dispatch: full routing decision for one token group."""
    num_experts = gate_logits.shape[-1]
    expert, gate, probs = route_topk(gate_logits, k, renormalize=renormalize)
    position, valid = build_dispatch(expert, num_experts, capacity)
    return Routing(expert=expert, gate=gate, position=position, valid=valid,
                   probs=probs)


def dispatch_counts(routing: Routing, num_experts: int) -> jax.Array:
    """Per-expert queue lengths (E,) int32 — the paper's metaqueue, and the
    router-usage statistic exported to the serving layer's expert cache."""
    return jnp.zeros((num_experts,), jnp.int32).at[
        routing.expert.reshape(-1)].add(
            routing.valid.reshape(-1).astype(jnp.int32))


def dispatch(x: jax.Array, routing: Routing, num_experts: int, capacity: int):
    """Gather tokens into per-expert buffers: (T, d) -> (E, C, d).

    The indirect (sparse) reader of the unified linear module: each expert's
    buffer holds exactly the tokens in its queue, contiguously.
    """
    d = x.shape[-1]
    t, k = routing.expert.shape
    tok = jnp.repeat(jnp.arange(t), k)
    e = routing.expert.reshape(-1)
    p = routing.position.reshape(-1)
    v = routing.valid.reshape(-1)
    # invalid entries write to a scrap row (capacity index) then are sliced off
    buf = jnp.zeros((num_experts, capacity + 1, d), dtype=x.dtype)
    p_safe = jnp.where(v, p, capacity)
    buf = buf.at[e, p_safe].set(x[tok])
    return buf[:, :capacity]


def combine(expert_out: jax.Array, routing: Routing) -> jax.Array:
    """Weighted scatter of per-expert outputs back to token order.

    (E, C, d) -> (T, d): each token accumulates gate-weighted outputs from its
    k experts — the paper's "weighted accumulation atop the existing output
    buffer" done by the indirect writer.
    """
    e = routing.expert.reshape(-1)
    p = routing.position.reshape(-1)
    rows = expert_out[e, jnp.minimum(p, expert_out.shape[1] - 1)]
    return combine_rows(rows, routing)


def combine_rows(rows: jax.Array, routing: Routing) -> jax.Array:
    """Gate-weighted sum of each token's k expert rows: (T*k, d) -> (T, d).

    The shared tail of ``combine`` and the paged serving layer's combine.
    The k-sum is one contraction (a dot), not a multiply then a reduce:
    XLA may fuse the latter into fused multiply-adds inside a jitted
    caller but not in an eager one, which made the two paths differ in
    the last bit.  ``HIGHEST`` keeps f32 operands f32 on the TPU, whose
    default dot precision rounds them to bf16.
    """
    t, k = routing.expert.shape
    w = (routing.gate * routing.valid).astype(rows.dtype)
    return jnp.einsum("tk,tkd->td", w, rows.reshape(t, k, -1),
                      precision=jax.lax.Precision.HIGHEST)


def dispatch_onehot(x: jax.Array, routing: Routing, num_experts: int,
                    capacity: int):
    """Dense einsum dispatch (GShard-style), bit-identical to ``dispatch``.

    Builds the (T, E, C) dispatch tensor and contracts it with x.  Lowers to
    plain dots under GSPMD — the path used for the 512-chip dry-run, where
    gather/scatter would serialize.
    """
    t, k = routing.expert.shape
    eh = jax.nn.one_hot(routing.expert, num_experts, dtype=x.dtype)       # (T,k,E)
    ph = jax.nn.one_hot(routing.position, capacity, dtype=x.dtype)       # (T,k,C)
    ph = ph * routing.valid[..., None].astype(x.dtype)
    dispatch_mask = jnp.einsum("tke,tkc->tec", eh, ph)                    # (T,E,C)
    return jnp.einsum("tec,td->ecd", dispatch_mask, x)


def combine_onehot(expert_out: jax.Array, routing: Routing) -> jax.Array:
    """Dense einsum combine matching ``dispatch_onehot``."""
    num_experts, capacity, _ = expert_out.shape
    eh = jax.nn.one_hot(routing.expert, num_experts, dtype=expert_out.dtype)
    ph = jax.nn.one_hot(routing.position, capacity, dtype=expert_out.dtype)
    w = (routing.gate[..., None].astype(expert_out.dtype)
         * routing.valid[..., None].astype(expert_out.dtype)) * ph         # (T,k,C)
    combine_mask = jnp.einsum("tke,tkc->tec", eh, w)
    return jnp.einsum("tec,ecd->td", combine_mask, expert_out)


def load_balance_loss(probs: jax.Array, expert: jax.Array, num_experts: int,
                      mask: jax.Array | None = None):
    """Switch-style auxiliary loss: E * sum_e f_e * P_e.

    f_e = fraction of (token, slot) assignments routed to e; P_e = mean gate
    probability of e.  Minimized when routing is uniform.  ``mask`` (T,)
    excludes tokens (e.g. group-padding rows) from both statistics; an
    all-ones mask is bit-identical to no mask.
    """
    t, k = expert.shape
    w = jnp.ones((t,), jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    counts = jnp.zeros((num_experts,), jnp.float32).at[
        expert.reshape(-1)].add(jnp.repeat(w, k))
    denom = jnp.maximum(w.sum(), 1.0)
    f = counts / (denom * k)
    # sums of products as f32 dots, as in ``combine_rows``: no multiply
    # then reduce for XLA to contract into FMAs in one caller and not another
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    p = dot(w, probs) / denom
    return num_experts * dot(f, p)
