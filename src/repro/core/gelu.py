"""Accurate low-cost activation approximation via a correction LUT (Edge-MoE §IV-C).

The paper approximates ``GELU(x) ~= ReLU(x) - delta(x)`` where the correction
``delta(x) = ReLU(x) - GELU(x)``:

  * delta is an **even** function (proved from erf being odd, Eq. 5-6), so only
    the x >= 0 half is tabulated;
  * 0 <= delta(x) < 1 for all x, so only fractional bits need storing (paper:
    22 fractional bits of a 32-bit fixed-point type);
  * the table is **truncated** where GELU rounds to ReLU (|x| beyond ~8 the
    correction underflows), outside that range ReLU(x) is returned directly;
  * the step is a **negative power of two**, so indexing is a bit shift.

TPU adaptation: the table is a tabulation of a closed form,
``delta(q) = q * Phi(-q) = q * erfc(q / sqrt(2)) / 2`` for GELU.  On the XLA
path ``lut_activation`` rounds |x| to the table's grid exactly as the lookup
does and evaluates that entry in registers: a few VPU operations that fuse
into the producing GEMM's epilogue, where an XLA gather from the table in HBM
runs element by element.  Pallas kernel bodies, which have no ``erf``, keep
the table in VMEM and read it with ``lut_correction_lanes``.  ``lut_correction``
reads the table too and is the oracle both forms are held to.  The same
construction generalizes to any activation that is a small correction on a
cheap base function: SwiGLU architectures use SiLU, whose correction
``ReLU(x) - SiLU(x) = |x| * sigmoid(-|x|)`` is even too, so the identical
half-table trick applies.

``max_abs_err`` for the default table (step 2^-8, range 8) is ~2e-5 for GELU —
validated by tests against the exact erf formulation, and by an end-task check
(paper Table V row 4: accuracy *improves* vs sigmoid approximations).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "exact_gelu",
    "exact_silu",
    "build_delta_table",
    "lut_correction",
    "delta_closed_form",
    "lut_gelu",
    "lut_silu",
    "lut_activation",
    "LUT_STEP_LOG2",
    "LUT_RANGE",
]

# Paper: "the look-up table step size is chosen to be a negative power of two"
# -> index computation is a bit shift.  2^-8 = 1/256 per entry.
LUT_STEP_LOG2 = -8
# Paper: "truncate the look-up table at the point where GELU(x) rounds to
# ReLU(x)".  For f32, |x| > 8 gives delta < 1e-14 -> ReLU is exact to ulp.
LUT_RANGE = 8.0


def exact_gelu(x):
    """Reference GELU, Eq. (1): x * 0.5 * (1 + erf(x / sqrt(2)))."""
    return x * 0.5 * (1.0 + jax.lax.erf(x / np.sqrt(2.0).astype(np.float32)))


def exact_silu(x):
    return x * jax.nn.sigmoid(x)


def _delta_table_f64(kind: str, step_log2: int, rng: float) -> np.ndarray:
    """The single source of the correction table, in float64 NumPy.

    ``build_delta_table`` and ``_cached_table`` both derive from this — they
    used to duplicate the computation, which risked the shipped table and
    the cached one drifting apart.
    """
    step = 2.0**step_log2
    n = int(rng / step)
    xs = np.arange(n, dtype=np.float64) * step
    if kind == "gelu":
        from math import erf

        base = xs * 0.5 * (1.0 + np.vectorize(erf)(xs / math.sqrt(2.0)))
    elif kind == "silu":
        base = xs / (1.0 + np.exp(-xs))
    else:
        raise ValueError(f"unknown LUT activation kind: {kind}")
    delta = np.maximum(xs, 0.0) - base
    assert (delta >= 0.0).all() and (delta < 1.0).all()
    return delta


def build_delta_table(
    kind: str = "gelu",
    step_log2: int = LUT_STEP_LOG2,
    rng: float = LUT_RANGE,
    dtype=jnp.float32,
) -> jax.Array:
    """Precompute the half-table of delta(x) for x in [0, rng) at step 2^step_log2.

    Entry i holds delta(i * 2^step_log2).  Evenness of delta means negative x
    reuse the same table (paper: "store only values where x >= 0").  The values
    are bounded in [0, 1) so on real fixed-point hardware only fractional bits
    are stored; in JAX we simply keep them in ``dtype``.
    """
    return jnp.asarray(_delta_table_f64(kind, step_log2, rng), dtype=dtype)


@functools.lru_cache(maxsize=None)
def _cached_table(kind: str, step_log2: int, rng: float) -> np.ndarray:
    # cache as NumPy (trace-safe); converted to a jnp constant at each use
    # site.  Host-side caching is load-bearing: an lru_cache over device
    # arrays would pin the value to first-call placement and go stale once
    # a mesh is active (see serve/engine._stub_embed_table)
    return _delta_table_f64(kind, step_log2, rng).astype(np.float32)


def lut_correction(y, table, step_log2: int):
    """ReLU(y) − δ(|y|) with non-finite inputs handled like the exact forms.

    The table read with a gather: the oracle that ``lut_activation``'s
    closed form and the kernels' ``lut_correction_lanes`` are held to.
    The index is clamped to the table (NaN/Inf used to flow through
    ``round().astype(int32)`` into an implementation-defined — possibly
    negative, wrapping — gather index); non-finite y bypass the table
    entirely and return
    ``y * 0.5 * (1 + sign(y))``, which reproduces the exact-activation
    limits: +inf → +inf, −inf → NaN (as ``exact_gelu``/``exact_silu`` give),
    NaN → NaN.  ``y`` and ``table`` must share a float dtype.
    """
    return _relu_minus_delta(y, step_log2, table.shape[0],
                             lambda idx: jnp.take(table, idx))


def _relu_minus_delta(y, step_log2: int, n: int, delta_at):
    """ReLU(y) − δ at the nearest of the n grid points k·2^step_log2.

    ``delta_at(idx)`` gives δ at int32 table indices; everything else —
    rounding, the clamp, truncation at the range, non-finite handling —
    is shared by the table and closed-form paths.
    """
    scale = 2.0 ** (-step_log2)
    ay = jnp.abs(y)
    finite = jnp.isfinite(y)
    # in-range decided in float (the int32 cast of a huge |y|·scale is
    # garbage); the clamped index only matters when in_range holds
    in_range = finite & (ay * scale < n)
    idx = jnp.clip(jnp.round(ay * scale).astype(jnp.int32), 0, n - 1)
    delta = jnp.where(in_range, delta_at(idx), 0.0)
    out = jnp.maximum(y, 0.0) - delta
    return jnp.where(finite, out, y * 0.5 * (1.0 + jnp.sign(y)))


def delta_closed_form(q, kind: str):
    """δ(q) for q >= 0 in q's float dtype: the closed form the table holds.

    GELU: q·Φ(−q) = ½·q·erfc(q/√2) (``erfc``, not 1 − erf, which cancels
    for large q); SiLU: q·σ(−q).
    """
    if kind == "gelu":
        return 0.5 * q * jax.lax.erfc(q * np.float32(1.0 / math.sqrt(2.0)))
    if kind == "silu":
        return q * jax.nn.sigmoid(-q)
    raise ValueError(f"unknown LUT activation kind: {kind}")


LANES = 128

# Eigen's / XLA's f32 rational erf fit on [-4, 4] (odd numerator over even
# denominator in x²); outside that range erf(x) rounds to ±1 in f32.
_ERF_ALPHA = (-2.72614225801306e-10, 2.77068142495902e-08,
              -2.10102402082508e-06, -5.69250639462346e-05,
              -7.34990630326855e-04, -2.95459980854025e-03,
              -1.60960333262415e-02)
_ERF_BETA = (-1.45660718464996e-05, -2.13374055278905e-04,
             -1.68282697438203e-03, -7.37332916720468e-03,
             -1.42647390514189e-02)


def erf_rational(x):
    """f32 erf from clamp, multiply, add and divide only.

    Pallas TPU has no lowering for ``lax.erf``; kernel epilogues use this
    instead.  Max abs error against float64 erf is below 1e-6 (tested).
    """
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    num = jnp.full_like(x2, _ERF_ALPHA[0])
    for c in _ERF_ALPHA[1:]:
        num = num * x2 + c
    den = jnp.full_like(x2, _ERF_BETA[0])
    for c in _ERF_BETA[1:]:
        den = den * x2 + c
    return x * num / den


def kernel_gelu(x):
    """Exact-form GELU for kernel bodies (``erf_rational`` in place of
    ``lax.erf``)."""
    return x * 0.5 * (1.0 + erf_rational(x * np.float32(1.0 / math.sqrt(2.0))))


def lut_table_lanes(table) -> np.ndarray:
    """The n-entry δ table as zero-padded (ceil(n/128), 128) f32 rows — the
    layout ``lut_correction_lanes`` reads (a VMEM-friendly 2-D block)."""
    t = np.asarray(table, np.float32).reshape(-1)
    rows = -(-t.shape[0] // LANES)
    out = np.zeros((rows * LANES,), np.float32)
    out[:t.shape[0]] = t
    return out.reshape(rows, LANES)


def lut_correction_lanes(y, table_rows, step_log2: int, n: int):
    """``lut_correction`` for Pallas kernel bodies, whose lowering has no
    general gather.

    ``y`` is (R, C) f32 with C a multiple of 128; ``table_rows`` is the
    ``lut_table_lanes`` layout of the n-entry table.  Entry ``idx`` sits at
    row ``idx >> 7``, lane ``idx & 127``: each table row is read with a
    within-lane-tile gather and the row is picked by compare-select, so
    the result equals ``lut_correction`` entry for entry.
    """
    scale = 2.0 ** (-step_log2)
    ay = jnp.abs(y)
    finite = jnp.isfinite(y)
    in_range = finite & (ay * scale < n)
    idx = jnp.clip(jnp.round(ay * scale).astype(jnp.int32), 0, n - 1)
    hi = jax.lax.shift_right_logical(idx, 7)
    lo = idx & (LANES - 1)
    chunks = []
    for j in range(y.shape[1] // LANES):
        lo_j = lo[:, j * LANES:(j + 1) * LANES]
        hi_j = hi[:, j * LANES:(j + 1) * LANES]
        delta = jnp.zeros(lo_j.shape, jnp.float32)
        for r in range(table_rows.shape[0]):
            row = jnp.broadcast_to(table_rows[r:r + 1, :], lo_j.shape)
            got = jnp.take_along_axis(row, lo_j, axis=1,
                                      mode="promise_in_bounds")
            delta = jnp.where(hi_j == r, got, delta)
        chunks.append(delta)
    delta = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, 1)
    delta = jnp.where(in_range, delta, 0.0)
    out = jnp.maximum(y, 0.0) - delta
    return jnp.where(finite, out, y * 0.5 * (1.0 + jnp.sign(y)))


def lut_activation(
    x: jax.Array,
    kind: str = "gelu",
    step_log2: int = LUT_STEP_LOG2,
    rng: float = LUT_RANGE,
) -> jax.Array:
    """ReLU(x) - delta(|x|) with delta the LUT's entry (paper Eq. 4).

    Index = |x| / 2^step_log2 = |x| * 2^-step_log2 — the bit-shift of the
    paper.  Values beyond the truncated range return ReLU(x) exactly (delta=0).
    Nearest-entry rounding matches the fixed-point hardware behaviour; the
    table is dense enough (2^-8 step) that linear interpolation is unneeded —
    tests quantify both.  The entry is evaluated in float32 from its closed
    form (``delta_closed_form``) rather than gathered from the table: it
    matches the float32 table to ~1e-7 and lowers with no gather.
    """
    n = int(rng / 2.0**step_log2)       # the table's length
    step = np.float32(2.0**step_log2)

    def delta_at(idx):
        d = delta_closed_form(idx.astype(jnp.float32) * step, kind)
        # δ < 1, so the clamp changes no value; it keeps δ's last product
        # from being contracted into ReLU(y) − δ as an FMA, which a fused
        # (jitted) program does and op-by-op evaluation does not: without
        # it the same y rounds differently in the two, as a table entry
        # never does
        return jnp.minimum(d, 1.0)

    y = _relu_minus_delta(x.astype(jnp.float32), step_log2, n, delta_at)
    return y.astype(x.dtype)


def lut_gelu(x, **kw):
    return lut_activation(x, kind="gelu", **kw)


def lut_silu(x, **kw):
    return lut_activation(x, kind="silu", **kw)


def get_activation(name: str, use_lut: bool = False):
    """Explicit exact-vs-LUT selection.  Model code does not call this —
    it goes through the policy-dispatched ``repro.ops.apply_activation``
    (op ``"activation"``: "xla" exact | "lut" | "pallas" LUT kernel);
    this helper remains for oracles and deliberate pinning in tests."""
    if name in (None, "none", "identity"):
        return lambda x: x
    if name == "relu":
        return jax.nn.relu
    if name == "gelu":
        return lut_gelu if use_lut else exact_gelu
    if name == "silu":
        return lut_silu if use_lut else exact_silu
    raise ValueError(f"unknown activation: {name}")
