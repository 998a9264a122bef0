"""Pallas unified-linear kernel — Edge-MoE §IV-E as one blocked GEMM.

The paper consolidates every linear layer into a single flexible compute
module (variable in/out dims, optional fused activation, widened f32 bias).
On TPU the FPGA resource argument becomes a schedule argument: one blocked
GEMM kernel = one tuned (block_m, block_n, block_k) tile schedule reused by
every projection in every model, with the bias + activation epilogue fused
into the final K step so the activation costs zero extra HBM round trips
(the paper's "flag controls whether the writer applies GELU").

Grid ``(nm, nn, nk)`` with K innermost; a float32 VMEM accumulator carries
across K tiles ("widened bias type" → f32 accumulate over bf16 operands).
The paper's manually flattened variable-bound loop maps to the Pallas grid:
M, N, K are call-time values, the kernel is shape-polymorphic by re-lowering.

The LUT-activation epilogue (§IV-C fused into §IV-E) takes the δ table as an
extra whole-block input (``core.gelu.lut_table_lanes`` rows), so the fused op
realizes techniques ③+④ together.  The exact GELU epilogue uses
``core.gelu.kernel_gelu``: Mosaic has no ``erf`` lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gelu import kernel_gelu, lut_correction_lanes
from repro.kernels.runtime import resolve_interpret

__all__ = ["unified_linear_kernel", "unified_linear_call"]


def _epilogue(y, activation: str | None, use_lut: bool, table, step_log2: int,
              lut_n: int):
    if activation in (None, "none"):
        return y
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if use_lut:
        return lut_correction_lanes(y, table, step_log2, lut_n)
    if activation == "gelu":
        return kernel_gelu(y)
    if activation == "silu":
        return y * jax.nn.sigmoid(y)
    raise ValueError(activation)


def unified_linear_kernel(x_ref, w_ref, b_ref, t_ref, o_ref, acc_scr, *,
                          activation, use_lut, step_log2, lut_n, has_bias):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epi():
        y = acc_scr[...]
        if has_bias:
            y = y + b_ref[0].astype(jnp.float32)      # widened f32 bias
        y = _epilogue(y, activation, use_lut, t_ref[...], step_log2, lut_n)
        o_ref[...] = y.astype(o_ref.dtype)


def unified_linear_call(
    x, w, b, table_rows, *,
    lut_n: int,
    activation: str | None = None,
    use_lut: bool = False,
    step_log2: int = -8,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Raw call on padded operands.  Use ``ops.unified_linear`` instead.

    x: (M, K), w: (K, N), b: (N,) f32 or None; table_rows: the
    ``lut_table_lanes`` layout of the lut_n-entry δ table (read only by a LUT
    epilogue).
    M % block_m == N % block_n == K % block_k == 0 (wrapper pads; zero pads
    contribute 0 to the accumulator so no masking is needed).
    """
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    n = w.shape[1]
    nm, nn, nk = m // block_m, n // block_n, k // block_k
    has_bias = b is not None
    if b is None:
        b = jnp.zeros((n,), jnp.float32)
    b2 = b[None, :]
    kernel = functools.partial(
        unified_linear_kernel, activation=activation, use_lut=use_lut,
        step_log2=step_log2, lut_n=lut_n, has_bias=has_bias)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
            pl.BlockSpec(table_rows.shape, lambda mi, ni, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, w, b2, table_rows)
