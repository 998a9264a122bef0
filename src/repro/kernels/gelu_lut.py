"""Pallas LUT-activation kernel — Edge-MoE §IV-C on the VPU.

GELU(x) ≈ ReLU(x) − δ(|x|) with δ tabulated on a power-of-two grid
(index = bit shift), even symmetry (half table), truncated support
(|x| > range ⇒ δ = 0 ⇒ exact ReLU).  On TPU the table is a small VMEM
resident (2048 f32 entries = 8 KiB at the default 2⁻⁸ step / range 8),
laid out as (16, 128) rows; the lookup is a within-lane-tile gather per
row plus a compare-select of the row (``core.gelu.lut_correction_lanes``).

The kernel is elementwise: the wrapper flattens/pads x to (rows, 128) and
tiles rows; the table rides along as a whole-block input replicated to every
grid step (it never leaves VMEM — the paper's "stored in ROM").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gelu import lut_correction_lanes
from repro.kernels.runtime import resolve_interpret

__all__ = ["lut_activation_kernel", "lut_activation_call"]


def lut_activation_kernel(x_ref, table_ref, o_ref, *, step_log2: int,
                          lut_n: int):
    y = lut_correction_lanes(x_ref[...].astype(jnp.float32), table_ref[...],
                             step_log2, lut_n)
    o_ref[...] = y.astype(o_ref.dtype)


def lut_activation_call(x2d, table_rows, *, lut_n: int, step_log2: int = -8,
                        block_rows: int = 256,
                        interpret: bool | None = None):
    """x2d: (R, 128) padded; table_rows: the (rows, 128) layout of the
    lut_n-entry table (``core.gelu.lut_table_lanes``).  Returns act(x2d)."""
    interpret = resolve_interpret(interpret)
    rows = x2d.shape[0]
    lanes = x2d.shape[1]
    nb = rows // block_rows
    kernel = functools.partial(lut_activation_kernel, step_log2=step_log2,
                               lut_n=lut_n)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec(table_rows.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, table_rows)
