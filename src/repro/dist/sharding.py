"""Sharding rules: one layout table serving every (arch × mesh) cell.

The distribution layer exposes three views of the same table:

  * **activations** — model code calls ``constrain(x, "btd")`` with a
    LOGICAL axis name.  Inside a ``use_rules`` context this lowers to
    ``jax.lax.with_sharding_constraint`` with the mesh-trimmed spec;
    outside any context (single-device tests, smoke training) it is a
    free no-op, so the models never branch on the mesh.
  * **parameters** — ``param_sharding_rules`` maps every parameter path
    (regex over ``"layers/b0/attn/wq"``-style path strings) to a
    ``NamedSharding``.  Scanned parameter stacks carry a leading period
    dim, so parameter specs are rank-padded on the LEFT.
  * **derived trees** — ``batch_sharding`` (leading dim over the batch
    axes, scalars replicated) and ``opt_state_shardings`` (each
    optimizer state follows the parameter it tracks; factored ``vr``
    row stats drop the trailing dim, ``vc`` col stats drop the -2 dim).

Every spec passes through ``_trim_spec``: rank padding plus
*divisibility trimming* — a mesh axis that does not divide its dim is
dropped (replicated) instead of erroring.  That is what lets the 512-way
production layouts and the 1-device test mesh share one table: a 8-way
``model`` axis simply falls off a 6-head KV dim.  ``"cache"`` carries a
list of alternative specs; ``constrain`` picks the first one that is
fully divisible and only then falls back to trimming.

Mesh axis roles (see ``launch/mesh.py``): batch over ``("pod", "data")``,
tensor/expert parallelism over ``"model"``, FSDP weight sharding over
``"data"`` (``fsdp=False`` disables it; ``fsdp="moe_only"`` keeps it for
the expert weights only, which dominate MoE parameter bytes).
"""

from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "make_mesh", "ShardingRules", "use_rules", "current_rules", "constrain",
    "param_sharding_rules", "batch_sharding", "opt_state_shardings",
    "ep_dispatch_sharding", "_trim_spec",
]


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """The repo's one mesh constructor: every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to Explicit axes, under which the rules'
    ``with_sharding_constraint`` layouts and the serving gathers/scatters
    raise ``ShardingTypeError``; this code relies on GSPMD propagation.
    ``devices`` defaults to the devices the process sees.
    """
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def ep_dispatch_sharding(mesh, axis: str = "model") -> NamedSharding:
    """Sharding for the slot-major ``(S, C, d)`` expert dispatch buffer.

    ``S`` is shard-contiguous: slot ``s*R + r`` lives in shard ``s``'s
    bank, so partitioning the leading dim over the expert-parallel axis
    keeps every slot's dispatch rows on the device that holds its
    weights — and the one-hot dispatch/combine einsums lower to the
    token all-to-all.  Replica-aware by construction: a replicated
    expert occupies one slot PER shard, so its split token streams land
    on their own shards with no extra collectives, however many replicas
    the placement plan assigns.
    """
    return NamedSharding(mesh, P(axis, None, None))


# ------------------------------------------------------------ spec trimming


def _rank_pad(shape, spec, pad_left: bool = False) -> P:
    """Pad (with None) or truncate ``spec`` to ``len(shape)`` entries."""
    entries = list(spec)
    rank = len(shape)
    if len(entries) < rank:
        pad = [None] * (rank - len(entries))
        entries = pad + entries if pad_left else entries + pad
    elif len(entries) > rank:
        entries = entries[len(entries) - rank:] if pad_left \
            else entries[:rank]
    return P(*entries)


def _trim_spec(shape, spec, mesh, pad_left: bool = False) -> P:
    """Rank-pad ``spec`` to ``shape`` and drop non-divisible mesh axes.

    Entries may be a single axis name or a tuple of names; names absent
    from the mesh (e.g. ``"pod"`` on the single-pod mesh) are filtered
    out, and an entry whose surviving axes do not divide the dim is
    replaced by None (replicated).  Single-name entries keep their
    string form so trimmed specs compare equal to hand-written ones.
    """
    sizes = dict(mesh.shape)
    out = []
    for dim, entry in zip(shape, _rank_pad(shape, spec, pad_left)):
        if entry is None:
            out.append(None)
            continue
        was_str = isinstance(entry, str)
        axes = (entry,) if was_str else tuple(entry)
        axes = tuple(a for a in axes if a in sizes)
        prod = 1
        for a in axes:
            prod *= sizes[a]
        if not axes or dim % prod != 0:
            out.append(None)
        elif was_str:
            out.append(axes[0])
        else:
            out.append(axes)
    return P(*out)


def _pick_spec(shape, spec, mesh) -> P:
    """Resolve a rule value: a plain spec, or a list of alternatives
    where the first fully-divisible one wins (``"cache"``)."""
    if isinstance(spec, list):
        for alt in spec:
            trimmed = _trim_spec(shape, alt, mesh)
            if trimmed == _rank_pad(shape, alt):
                return trimmed
        spec = spec[0]
    return _trim_spec(shape, spec, mesh)


# ------------------------------------------------------------ rules object


def _batch_entry(batch_axes):
    """Batch axes as a spec entry: str for one axis, tuple for several,
    None when the mesh has no batch axis at all."""
    return batch_axes[0] if len(batch_axes) == 1 else (batch_axes or None)


class ShardingRules:
    """Immutable bundle of (mesh, logical activation table, param patterns)."""

    def __init__(self, mesh, logical, param_patterns, batch_axes,
                 seq_shard: bool = False, fsdp: Any = True):
        self.mesh = mesh
        self.logical = logical
        self.param_patterns = param_patterns
        self.batch_axes = batch_axes          # e.g. ("pod", "data")
        self.seq_shard = seq_shard
        self.fsdp = fsdp
        self.batch_entry = _batch_entry(batch_axes)

    @classmethod
    def for_mesh(cls, mesh, *, seq_shard: bool = False, fsdp: Any = True):
        names = mesh.axis_names
        batch = tuple(a for a in ("pod", "data") if a in names)
        B = _batch_entry(batch)
        tp = "model" if "model" in names else None
        dp = "data" if "data" in names else None
        F = dp if fsdp is True else None              # dense-weight FSDP axis
        Fm = dp if fsdp in (True, "moe_only") else None   # expert-weight FSDP
        seq = tp if seq_shard else None

        logical = {
            # activations: (B, T, d) residual stream / (B, T, ff) MLP hidden /
            # (B, T, lru_width) recurrent widths / (B, T, vocab) logits
            "btd": P(B, seq, None),
            "btf": P(B, None, tp),
            "btw": P(B, None, tp),
            "btv": P(B, None, tp),
            # attention: heads over the tensor axis
            "bhsd": P(B, tp, None, None),
            "bkvsd": P(B, tp, None, None),
            # KV cache (B, Hkv, S, hd): head-sharded when Hkv divides the
            # tensor axis, else fall back to batch-only
            "cache": [P(B, tp, None, None), P(B, None, None, None)],
            # MoE dispatch buffers (E, C, d): expert-parallel over the
            # tensor axis — the one-hot dispatch/combine einsums then lower
            # to the token all-to-all (experts stay resident, tokens move)
            "ecd": P(tp, None, None),
            # paged-serving slot dispatch buffers (S, C, d): same layout,
            # S = shard-contiguous slot banks (see ep_dispatch_sharding)
            "scd": P(tp, None, None),
        }

        param_patterns = (
            # --- embeddings / head: vocab over tensor, d over FSDP
            (r"embed/tokens$",              P(tp, F)),
            (r"head/w$",                    P(F, tp)),
            # --- attention
            (r"attn/w[qkv]$",               P(F, tp)),
            (r"attn/wo$",                   P(tp, F)),
            (r"attn/b[qkv]$",               P(tp)),
            # --- dense MLP (swiglu/geglu/gelu)
            (r"mlp/(wg|wu|w1)$",            P(F, tp)),
            (r"mlp/(wd|w2)$",               P(tp, F)),
            (r"mlp/b1$",                    P(tp)),
            (r"mlp/b2$",                    P()),
            # --- MoE: gate replicated (tiny, read by every shard); expert
            # stacks sharded expert-dim over the tensor axis (resident
            # experts for ep_local) + FSDP over data
            (r"moe/gate$",                  P(None, None, None)),
            (r"moe/shared_w[gu]$",          P(F, tp)),
            (r"moe/shared_wd$",             P(tp, F)),
            (r"moe/(wg|wu|wd|w1|w2)$",      P(tp, Fm, None)),
            (r"moe/b[12]$",                 P(tp, None)),
            # --- RG-LRU (recurrentgemma)
            (r"rglru/w_up2?$",              P(F, tp)),
            (r"rglru/w_down$",              P(tp, F)),
            (r"rglru/conv$",                P(None, tp)),
            (r"rglru/gates$",               P(tp, None, None)),
            (r"rglru/lam$",                 P(tp)),
            # --- xLSTM (mlstm / slstm)
            (r"(mlstm|slstm)/w_(up|up2|gates|qkv)$", P(F, tp)),
            (r"(mlstm|slstm)/w_down$",      P(tp, F)),
            (r"mlstm/conv$",                P(None, tp)),
            (r"mlstm/w_if$",                P(F, None)),
            (r"slstm/r_gates$",             P(tp, None, None)),
            # --- quantized (QTensor) leaves: packed values + per-channel
            # scales flatten as <name>/q and <name>/scale.  Expert stacks
            # keep the expert-dim layout (scales' unit dims trim to
            # replicated); other quantized weights replicate — quantized
            # serving is memory-bound, not weight-gather-bound
            (r"moe/(wg|wu|wd|w1|w2)/(q|scale)$", P(tp, Fm, None)),
            (r"/(q|scale)$",                P()),
            # --- norms / small vectors: replicated
            (r"(scale|bias|b_if|b_gates|gn_scale|lam|pos)$", P()),
        )
        return cls(mesh, logical, param_patterns, batch,
                   seq_shard=seq_shard, fsdp=fsdp)


# ------------------------------------------------------------ rules context


_RULES: contextvars.ContextVar[Optional[ShardingRules]] = \
    contextvars.ContextVar("sharding_rules", default=None)


def current_rules() -> Optional[ShardingRules]:
    return _RULES.get()


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    """Activate ``rules`` for the dynamic extent (None is a valid no-op
    rules value, so step builders can pass their ``rules`` through
    unconditionally).  Nests: the previous value is restored on exit."""
    token = _RULES.set(rules)
    try:
        yield rules
    finally:
        _RULES.reset(token)


def constrain(x, name: str):
    """Constrain ``x`` to the logical rule ``name``.

    No-op (identity, same object) outside a ``use_rules`` context and for
    unknown rule names — model code calls this unconditionally."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = rules.logical.get(name)
    if spec is None:
        return x
    trimmed = _pick_spec(x.shape, spec, rules.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, trimmed))


# ------------------------------------------------------------ param tables


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _match_param_spec(pathstr: str, shape, rules: ShardingRules) -> P:
    for pattern, spec in rules.param_patterns:
        if re.search(pattern, pathstr):
            # pad LEFT: scanned stacks carry a leading n_periods dim that
            # the per-layer pattern spec doesn't mention
            return _trim_spec(shape, spec, rules.mesh, pad_left=True)
    raise ValueError(
        f"no sharding rule matches parameter {pathstr!r} (shape {shape}); "
        f"add a pattern to ShardingRules.for_mesh")


def param_sharding_rules(tree, rules: ShardingRules):
    """Parameter pytree (arrays or ShapeDtypeStructs) -> NamedSharding tree."""
    mesh = rules.mesh
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _match_param_spec(_path_str(path), leaf.shape, rules)),
        tree)


def batch_sharding(tree, rules: ShardingRules):
    """Batch/state trees: leading dim over the batch axes, scalars
    replicated, all other dims unsharded."""
    mesh = rules.mesh
    entry = rules.batch_entry

    def one(leaf):
        if leaf.ndim == 0 or entry is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _trim_spec(leaf.shape, P(entry), mesh))

    return jax.tree.map(one, tree)


def opt_state_shardings(opt_state, params, rules: ShardingRules):
    """AdamW state shardings derived from the parameter table.

    ``m``/``v`` mirror the parameter spec; factored stats drop the dim
    they average over: ``vr`` (row stats, shape ``p.shape[:-1]``) drops
    the last entry, ``vc`` (col stats, ``p.shape[:-2] + p.shape[-1:]``)
    drops the -2 entry.  ``params`` is accepted for signature symmetry
    with the other table builders; the ema tree mirrors its structure,
    so matching runs on the ema paths directly.
    """
    del params
    mesh = rules.mesh

    def one(path, leaf_state):
        spec = _match_param_spec(_path_str(path), leaf_state["m"].shape,
                                 rules)
        out = {"m": NamedSharding(mesh, spec)}
        if "v" in leaf_state:
            out["v"] = NamedSharding(mesh, spec)
        if "vr" in leaf_state:
            out["vr"] = NamedSharding(mesh, P(*spec[:-1]))
        if "vc" in leaf_state:
            out["vc"] = NamedSharding(mesh, P(*spec[:-2], spec[-1]))
        return out

    ema = jax.tree_util.tree_map_with_path(
        one, opt_state["ema"],
        is_leaf=lambda x: isinstance(x, dict) and "m" in x)
    return {"step": NamedSharding(mesh, P()), "ema": ema}
