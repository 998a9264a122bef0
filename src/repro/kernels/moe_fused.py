"""Fused Pallas MoE megakernel — dispatch + grouped GEMM + combine in one pass.

Edge-MoE §IV-D's full pipeline (gather each expert's token queue, run the
expert MLP, weighted-scatter the outputs) as ONE kernel: the ``(E, C, d)``
dispatch buffer **never exists** in HBM.  The staged path materializes it
three times per expert projection (write at dispatch, read per GEMM, write
per GEMM output); here tokens are gathered from the resident activation
block by routing indices, the whole expert MLP runs on VMEM intermediates,
and the gate-weighted combine accumulates straight into the output.

Mechanics
---------
  * Grid ``(E, nc)`` — expert-major, so each expert's weights are loaded
    once for its whole queue (the paper's reuse guarantee), queue-capacity
    blocks inner.  TPU grids are sequential, so the whole-array ``x`` input
    and ``out`` output (constant index maps) stay VMEM-resident across the
    sweep.
  * The metaqueue is the scalar-prefetch ``group_sizes``: experts with an
    empty queue — and capacity blocks past a queue's length — are skipped
    with ``pl.when`` before any of their weight tiles are touched.
  * Gather/scatter are one-hot matmuls (MXU-friendly, no dynamic indexing):
    ``G[c, t] = (tok_idx[c] == t)`` gathers ``xq = G @ x``; the combine is
    ``out += (G * gate[:, None])ᵀ @ y``.  Invalid slots hold ``tok = -1``
    (matches no token → zero G row) **and** gate 0, so garbage computed in
    dead queue rows (e.g. ``act(b1) @ w2``) is annihilated by the scatter
    weight — the megakernel form of the padded-tail zeroing contract.
  * Top-k > 1 combine weights come out exactly: a token appears in k
    experts' queues and its output accumulates across their grid steps.
  * The activation is fused: exact GELU/SiLU (``core.gelu.kernel_gelu`` —
    Mosaic has no ``erf`` lowering) or the §IV-C LUT correction
    (``core.gelu.lut_correction_lanes``) with the δ half-table riding along
    as a VMEM-resident (rows, 128) input.
  * Token indices and gates arrive as (E, Cp, 1) columns and biases as
    (E, 1, f) rows, so every block's last two dims are either full or
    (8, 128)-aligned, as Mosaic requires.

All math is f32 (queue intermediates included); the wrapper casts the
combined output back to the activation dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gelu import kernel_gelu, lut_correction_lanes
from repro.kernels.runtime import resolve_interpret

__all__ = ["fused_moe_kernel", "fused_moe_call"]


def _activate(h, kind: str, use_lut: bool, table, step_log2: int,
              lut_n: int):
    if use_lut:
        return lut_correction_lanes(h, table, step_log2, lut_n)
    if kind == "swiglu":                      # SiLU gate
        return h * jax.nn.sigmoid(h)
    return kernel_gelu(h)


def fused_moe_kernel(sizes_ref, tok_ref, gate_ref, x_ref, *rest,
                     kind: str, block_c: int, tpad: int,
                     use_lut: bool, step_log2: int, lut_n: int):
    if kind == "swiglu":
        wg_ref, wu_ref, wd_ref, t_ref, o_ref = rest
    else:
        w1_ref, b1_ref, w2_ref, b2_ref, t_ref, o_ref = rest

    e = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when((e == 0) & (ci == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    size = sizes_ref[e]
    # metaqueue skip (empty expert) + queue-tail block skip, both decided
    # from the prefetched scalar before any weight tile is read
    needed = (size > 0) & (ci * block_c < size)

    @pl.when(needed)
    def _compute():
        tok = tok_ref[0]                                     # (bc, 1) int32
        gate = gate_ref[0].astype(jnp.float32)               # (bc, 1)
        iota_t = jax.lax.broadcasted_iota(
            jnp.int32, (block_c, tpad), 1)
        # one-hot gather matrix; tok = -1 (dead slot) matches no column
        g = (tok == iota_t).astype(jnp.float32)              # (bc, T)
        xq = jax.lax.dot_general(
            g, x_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bc, d)

        table = t_ref[...]
        if kind == "swiglu":
            hg = jax.lax.dot_general(
                xq, wg_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            hu = jax.lax.dot_general(
                xq, wu_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            h = _activate(hg, kind, use_lut, table, step_log2, lut_n) * hu
            y = jax.lax.dot_general(
                h, wd_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (bc, d)
        else:
            h = jax.lax.dot_general(
                xq, w1_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            h = _activate(h + b1_ref[0].astype(jnp.float32),
                          kind, use_lut, table, step_log2, lut_n)
            y = jax.lax.dot_general(
                h, w2_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            y = y + b2_ref[0].astype(jnp.float32)            # (bc, d)

        # gate-weighted scatter-combine: dead rows carry gate 0, so their
        # bias garbage never reaches a token
        gw = g * gate                                        # (bc, T)
        o_ref[...] += jax.lax.dot_general(
            gw, y, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (T, d)


def fused_moe_call(tok_idx, gates, x, weights, table_rows, group_sizes, *,
                   kind: str, block_c: int, use_lut: bool, step_log2: int,
                   lut_n: int, interpret: bool | None = None):
    """Raw call on padded operands.  Use ``ops.fused_moe_ffn`` instead.

    tok_idx/gates: (E, Cp, 1) int32/f32 (−1 / 0 in dead slots); x: (Tp, dp);
    weights: tuple (wg, wu, wd) or (w1, b1, w2, b2) padded to (dp, fp),
    biases as (E, 1, fp) / (E, 1, dp); table_rows: the ``lut_table_lanes``
    layout of the lut_n-entry δ table; group_sizes: (E,) int32.
    Cp % block_c == 0, Tp % 128 == 0, dp/fp % 128 == 0.  Returns the
    combined (Tp, dp) f32.
    """
    interpret = resolve_interpret(interpret)
    e, cp, _ = tok_idx.shape
    tpad, dp = x.shape
    nc = cp // block_c
    fp = weights[0].shape[2]

    def _w3(_e, _ci, _sz):
        return (_e, 0, 0)

    def _const(_e, _ci, _sz):
        return (0, 0)

    if kind == "swiglu":
        w_specs = [
            pl.BlockSpec((1, dp, fp), _w3),      # wg
            pl.BlockSpec((1, dp, fp), _w3),      # wu
            pl.BlockSpec((1, fp, dp), _w3),      # wd
        ]
    else:
        w_specs = [
            pl.BlockSpec((1, dp, fp), _w3),      # w1
            pl.BlockSpec((1, 1, fp), _w3),       # b1
            pl.BlockSpec((1, fp, dp), _w3),      # w2
            pl.BlockSpec((1, 1, dp), _w3),       # b2
        ]

    kernel = functools.partial(
        fused_moe_kernel, kind=kind, block_c=block_c, tpad=tpad,
        use_lut=use_lut, step_log2=step_log2, lut_n=lut_n)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, nc),
            in_specs=[
                pl.BlockSpec((1, block_c, 1),
                             lambda _e, _ci, _sz: (_e, _ci, 0)),
                pl.BlockSpec((1, block_c, 1),
                             lambda _e, _ci, _sz: (_e, _ci, 0)),
                pl.BlockSpec((tpad, dp), _const),
                *w_specs,
                pl.BlockSpec(table_rows.shape, _const),
            ],
            out_specs=pl.BlockSpec((tpad, dp), _const),
        ),
        out_shape=jax.ShapeDtypeStruct((tpad, dp), jnp.float32),
        interpret=interpret,
    )(group_sizes, tok_idx, gates, x, *weights, table_rows)
