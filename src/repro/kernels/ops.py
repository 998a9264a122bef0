"""Jit'd public wrappers for the Pallas kernels.

Each wrapper pads operands to kernel tile multiples (MXU-aligned: multiples
of 128 on matmul dims), invokes the raw ``*_call``, and slices the result.
On this CPU container kernels execute in ``interpret=True`` mode (the kernel
body runs as traced Python — bit-faithful to the TPU schedule, used by the
allclose tests); on a TPU backend they compile to Mosaic.

Model code does not call these directly: they are the ``"pallas"``
implementations behind the :mod:`repro.ops` registry, selected by the
ambient :class:`~repro.ops.ComputePolicy`.  Block sizes default to ``None``
= *resolve from the measured tile-schedule table*
(``repro/ops/schedules.json``, per op × shape bucket × backend, populated
by ``benchmarks/ops_autotune.py``); an explicit ``block_*=`` argument
pins them (kernel sweeps / the autotuner itself).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gelu import _cached_table, lut_table_lanes
from repro.kernels import decode_fused as _df
from repro.kernels import flash_attention as _fa
from repro.kernels import gelu_lut as _gl
from repro.kernels import moe_fused as _mf
from repro.kernels import moe_gemm as _mg
from repro.kernels import unified_linear as _ul
from repro.ops.schedules import schedule_for

__all__ = ["flash_attention", "unified_linear", "moe_gemm", "lut_activation",
           "fused_moe_ffn", "fused_decode_attention"]


def _pad_to(x, mult: int, axis: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _lut_rows(kind: str, step_log2: int, lut_range: float):
    """(table rows, entry count) of the δ table in the kernels' layout."""
    table = _cached_table(kind, step_log2, lut_range)
    return jnp.asarray(lut_table_lanes(table)), table.shape[0]


_NO_TABLE = (np.zeros((8, 128), np.float32), 1)


def _blocks(op: str, dims: dict, given: dict, impl: str = "pallas") -> dict:
    """Merge schedule-table blocks with explicitly pinned ones (non-None)."""
    out = schedule_for(op, impl, dims)
    out.update({k: v for k, v in given.items() if v is not None})
    return out


# ------------------------------------------------------------ attention


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "scale", "block_q",
                     "block_k", "interpret"),
)
def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    scale=None, block_q=None, block_k=None, interpret=None):
    """Tiled flash attention (paper technique ①+②).

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D).
    """
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    sched = _blocks("attention", {"sq": sq, "skv": skv, "d": d},
                    {"block_q": block_q, "block_k": block_k})
    bq = min(sched.get("block_q", 128), max(8, 1 << (sq - 1).bit_length()))
    bk = min(sched.get("block_k", 128), max(8, 1 << (skv - 1).bit_length()))
    qp = _pad_to(q, bq, 2)
    kp = _pad_to(k, bk, 2)
    vp = _pad_to(v, bk, 2)
    dp = (-d) % 128
    if dp:
        qp = _pad_to(qp, 128, 3)
        kp = _pad_to(kp, 128, 3)
        vp = _pad_to(vp, 128, 3)
    out = _fa.flash_attention_call(
        qp, kp, vp, causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=bq, block_k=bk, sq_orig=sq, skv_orig=skv,
        interpret=interpret)
    return out[:, :, :sq, :d]


# ------------------------------------------------------------ unified linear


@functools.partial(
    jax.jit,
    static_argnames=("activation", "use_lut", "step_log2", "lut_range",
                     "block_m", "block_n", "block_k", "interpret"),
)
def unified_linear(x, w, b=None, *, activation=None, use_lut=False,
                   step_log2=-8, lut_range=8.0,
                   block_m=None, block_n=None, block_k=None, interpret=None):
    """One blocked GEMM for every linear layer (technique ④, fused ③).

    x: (..., K); w: (K, N); b: (N,) f32 or None.  Leading dims are flattened
    into M (the paper's dense reader), padded to tile multiples, restored.
    """
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    n = w.shape[1]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    sched = _blocks("linear", {"m": m, "n": n, "k": kdim},
                    {"block_m": block_m, "block_n": block_n,
                     "block_k": block_k})
    bm = min(sched.get("block_m", 256), max(8, 1 << (m - 1).bit_length()))
    bn = min(sched.get("block_n", 256), max(128, 1 << (n - 1).bit_length()))
    bk = min(sched.get("block_k", 512), max(128, 1 << (kdim - 1).bit_length()))
    xp = _pad_to(_pad_to(x2, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    bp = None if b is None else _pad_to(b.astype(jnp.float32), bn, 0)
    table, lut_n = _lut_rows(activation, step_log2, lut_range) \
        if use_lut and activation in ("gelu", "silu") else _NO_TABLE
    y = _ul.unified_linear_call(
        xp, wp, bp, table, lut_n=lut_n, activation=activation,
        use_lut=use_lut, step_log2=step_log2,
        block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return y[:m, :n].reshape(*lead, n)


# ------------------------------------------------------------ moe grouped gemm


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_k",
                                             "interpret"))
def moe_gemm(buf, w, group_sizes, *, block_c=None, block_f=None, block_k=None,
             interpret=None):
    """Expert-by-expert grouped GEMM (technique ⑤): out[e] = buf[e] @ w[e].

    buf: (E, C, D); w: (E, D, F); group_sizes: (E,) int32 — experts with an
    empty queue are skipped (the metaqueue).
    """
    e, c, d = buf.shape
    f = w.shape[2]
    sched = _blocks("moe_grouped_gemm", {"e": e, "c": c, "d": d, "f": f},
                    {"block_c": block_c, "block_f": block_f,
                     "block_k": block_k})
    bc = min(sched.get("block_c", 128), max(8, 1 << (c - 1).bit_length()))
    bf = min(sched.get("block_f", 256), max(128, 1 << (f - 1).bit_length()))
    bk = min(sched.get("block_k", 512), max(128, 1 << (d - 1).bit_length()))
    bufp = _pad_to(_pad_to(buf, bc, 1), bk, 2)
    wp = _pad_to(_pad_to(w, bk, 1), bf, 2)
    out = _mg.moe_gemm_call(bufp, wp, group_sizes.astype(jnp.int32),
                            block_c=bc, block_f=bf, block_k=bk,
                            interpret=interpret)
    return out[:, :c, :f]


# ------------------------------------------------------------ lut activation


@functools.partial(jax.jit, static_argnames=("kind", "step_log2", "lut_range",
                                              "block_rows", "interpret"))
def lut_activation(x, kind="gelu", *, step_log2=-8, lut_range=8.0,
                   block_rows=None, interpret=None):
    """Standalone LUT activation kernel (technique ③).  Elementwise."""
    table, lut_n = _lut_rows(kind, step_log2, lut_range)
    flat = x.reshape(-1)
    n = flat.shape[0]
    lanes = 128
    rows = -(-n // lanes)
    sched = _blocks("activation", {"rows": rows},
                    {"block_rows": block_rows})
    br = min(sched.get("block_rows", 256),
             max(8, 1 << max(rows - 1, 0).bit_length()))
    rows_p = -(-rows // br) * br
    xp = jnp.zeros((rows_p * lanes,), x.dtype).at[:n].set(flat)
    y = _gl.lut_activation_call(xp.reshape(rows_p, lanes), table,
                                lut_n=lut_n, step_log2=step_log2,
                                block_rows=br,
                                interpret=interpret)
    return y.reshape(-1)[:n].reshape(x.shape)


# ------------------------------------------------------- fused moe megakernel


@functools.partial(
    jax.jit,
    static_argnames=("kind", "capacity", "use_lut", "step_log2", "lut_range",
                     "block_c", "interpret"),
)
def fused_moe_ffn(x, params, expert, gate, position, valid, group_sizes, *,
                  kind, capacity, use_lut=True, step_log2=-8, lut_range=8.0,
                  block_c=None, interpret=None):
    """Dispatch + expert MLPs + combine in ONE kernel (no (E, C, d) buffer).

    x: (T, d) token activations; params: expert weight dict (``w1/b1/w2/b2``
    or ``wg/wu/wd``, leading E axis); expert/gate/position/valid: the
    routing decision (T, k); group_sizes: (E,) int32 queue lengths.
    Returns the gate-combined (T, d) output in x.dtype.
    """
    t, k = expert.shape
    d = x.shape[-1]
    e_num = group_sizes.shape[0]
    c = capacity
    if kind == "swiglu":
        f = params["wg"].shape[2]
        weights = (params["wg"], params["wu"], params["wd"])
    else:
        f = params["w1"].shape[2]
        weights = (params["w1"], params["b1"], params["w2"], params["b2"])
    sched = _blocks("moe_ffn", {"e": e_num, "c": c, "d": d, "f": f, "t": t},
                    {"block_c": block_c}, impl="pallas_fused")
    bc = min(sched.get("block_c", 64), max(8, 1 << (c - 1).bit_length()))

    # per-expert queues as index/weight arrays (the queues of Fig. 9d,
    # by-reference): slot s of token tt lands at tok_idx[e, p]; dead slots
    # (capacity drops, unused rows) stay at −1 / gate 0 via the scrap column
    eidx = expert.reshape(-1)
    p = position.reshape(-1)
    v = valid.reshape(-1)
    gv = gate.reshape(-1).astype(jnp.float32) * v.astype(jnp.float32)
    tokids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    p_safe = jnp.where(v, p, c)
    tok_idx = jnp.full((e_num, c + 1), -1, jnp.int32) \
        .at[eidx, p_safe].set(tokids)[:, :c]
    gates = jnp.zeros((e_num, c + 1), jnp.float32) \
        .at[eidx, p_safe].set(gv)[:, :c]
    # (E, Cp, 1) columns: Mosaic blocks of (1, block_c, 1)
    tok_idx = _pad_to(tok_idx, bc, 1, value=-1)[..., None]
    gates = _pad_to(gates, bc, 1)[..., None]

    xp = _pad_to(_pad_to(x, 128, 0), 128, 1)
    wp = []
    for w in weights:
        if w.ndim == 2:                          # bias (E, n) -> (E, 1, n)
            w = w[:, None, :]
        else:
            w = _pad_to(w, 128, 1)               # d or f axis
        wp.append(_pad_to(w, 128, 2))
    table, lut_n = _lut_rows("silu" if kind == "swiglu" else "gelu",
                             step_log2, lut_range) if use_lut else _NO_TABLE
    out = _mf.fused_moe_call(
        tok_idx, gates, xp, tuple(wp), table,
        group_sizes.astype(jnp.int32), kind=kind, block_c=bc,
        use_lut=use_lut, step_log2=step_log2, lut_n=lut_n,
        interpret=interpret)
    return out[:t, :d].astype(x.dtype)


# ------------------------------------------------------- fused decode kernel


@functools.partial(jax.jit, static_argnames=("window", "scale", "block_k",
                                             "interpret"))
def fused_decode_attention(q, k_cache, v_cache, cache_len, *, window=None,
                           scale=None, block_k=None, interpret=None):
    """Single-pass decode attention; per-slot cache lengths read at run time.

    q: (B, Hq, 1, D); k/v_cache: (B, Hkv, Smax, D); cache_len: scalar or
    (B,) int32 — may be traced and non-uniform (continuous batching).
    """
    b, hq, _one, d = q.shape
    hkv = k_cache.shape[1]
    group = hq // hkv
    smax = k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    sched = _blocks("attention_decode", {"sq": 1, "skv": smax, "d": d},
                    {"block_k": block_k}, impl="pallas_fused")
    bk = min(sched.get("block_k", 128),
             max(128, 1 << (smax - 1).bit_length()))

    # GQA group as sublanes: query head h = hkv_idx * group + g reads kv
    # head hkv_idx, so the (B, Hq, 1, d) query regroups losslessly
    qg = q.reshape(b, hkv, group, d)
    qp = _pad_to(_pad_to(qg, 8, 2), 128, 3)
    kp = _pad_to(_pad_to(k_cache, bk, 2), 128, 3)
    vp = _pad_to(_pad_to(v_cache, bk, 2), 128, 3)
    cl = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    out = _df.fused_decode_call(
        qp, kp, vp, cl, window=window, scale=scale, block_k=bk,
        interpret=interpret)
    return out[:, :, :group, :d].reshape(b, hq, 1, d)
