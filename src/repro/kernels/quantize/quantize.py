"""Pallas TPU kernel: blockwise absmax int8 quantize / dequantize.

Tiling: (TILE_ROWS, QBLOCK) f32 tiles staged in VMEM (TILE_ROWS x 4 KiB);
each row is one quantization block, reduced to its absmax scale and
rounded in-register (`ref.quantize_block`).  8 rows/tile keeps the
working set at 32 KiB + 8 KiB output — comfortably inside one TPU
core's VMEM while giving the VPU long contiguous lanes.  Any row count
is accepted: the grid is `cdiv(n, TILE_ROWS)`, and Pallas pads the
partial last tile on read and drops its rows past n on write (rows are
independent blocks).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default, tile_rows
from repro.kernels.quantize.ref import QBLOCK, quantize_block

TILE_ROWS = 8


def _quant_kernel(x_ref, q_ref, s_ref):
    q, scale = quantize_block(x_ref[...], jnp)       # (R, QBLOCK) f32 in
    q_ref[...] = q
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_pallas(blocks: jnp.ndarray, interpret: Optional[bool] = None):
    """(n, QBLOCK) f32 -> ((n, QBLOCK) int8, (n, 1) f32)."""
    if interpret is None:
        interpret = interpret_default()
    n = blocks.shape[0]
    rows = tile_rows(n, TILE_ROWS)
    return pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, QBLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, QBLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, QBLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret,
        name="quantize_int8",
    )(blocks)


def dequantize_pallas(q: jnp.ndarray, scale: jnp.ndarray,
                      interpret: Optional[bool] = None):
    if interpret is None:
        interpret = interpret_default()
    n = q.shape[0]
    rows = tile_rows(n, TILE_ROWS)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, QBLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, QBLOCK), jnp.float32),
        interpret=interpret,
        name="dequantize_int8",
    )(q, scale)
