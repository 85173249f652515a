"""Pallas TPU kernel: blockwise Fletcher partial sums for checkpoint
integrity (hot path: every checkpoint shard is checksummed at write and
at restore).

Tiling: the uint32 word stream is shaped (n_blocks, BLOCK); each grid
step stages a (ROWS, BLOCK) tile in VMEM (2 MiB at ROWS=256) and
reduces every row to its two uint32 partial sums, written as two
(ROWS, 1) columns.  The cross-block fold (tiny) stays in jnp.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default, tile_rows
from repro.kernels.checksum.ref import BLOCK

ROWS = 256  # blocks per grid step


def _block_sums_kernel(w_ref, s1_ref, s2_ref):
    # int32 words: Mosaic has no unsigned reductions, and two's
    # complement add/multiply wrap to the same bits as uint32
    w = jax.lax.bitcast_convert_type(w_ref[...], jnp.int32)   # (R, BLOCK)
    idx = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    s1_ref[...] = jnp.sum(w, axis=1, keepdims=True)
    s2_ref[...] = jnp.sum(w * idx, axis=1, keepdims=True)


def block_sums_pallas(words: jnp.ndarray, interpret: Optional[bool] = None):
    """words: (n_blocks, BLOCK) uint32 -> (n_blocks, 2) uint32."""
    if interpret is None:
        interpret = interpret_default()
    n = words.shape[0]
    rows = tile_rows(n, ROWS)
    col = jax.ShapeDtypeStruct((n, 1), jnp.int32)
    s1, s2 = pl.pallas_call(
        _block_sums_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[col, col],
        interpret=interpret,
        name="checksum_block_sums",
    )(words)
    sums = jnp.concatenate([s1, s2], axis=1)
    return jax.lax.bitcast_convert_type(sums, jnp.uint32)
