"""Pure-jnp oracle: blockwise absmax int8 quantization.

Checkpoint compression (2x for bf16 moments, 4x for f32) — MANA-2.0's
Fig-3 concern is checkpoint write time; shrinking bytes moves it
directly.  Error feedback is handled at the call site (optimizer moments
only by default; params stay exact).

The definition uses only operations that every backend rounds the same
way (IEEE f32 multiply, compare, select), so numpy, XLA:CPU and the
compiled TPU kernel agree bit for bit.  f32 division does not qualify:
XLA rewrites a division by a constant into a multiply by its reciprocal,
and the TPU computes quotients from an approximate reciprocal.  Nor do
subnormal numbers: the TPU flushes them to zero.  Per block of QBLOCK
elements:

    scale = amax * INV127, or 1.0 where that is below SCALE_MIN
    |q|   = the largest c in 0..127 with |x| >= (c - 0.5) * scale
    q     = sign(x) * |q|

SCALE_MIN keeps every scale and every threshold (c - 0.5) * scale a
normal number.  A block whose absmax is below 127 * SCALE_MIN (about
3e-36, an all-zero block included) is stored as zeros.

A quotient rounded by any backend lands within one of |q|; the two
threshold comparisons in `quantize_block` then move it onto |q|
exactly.  Dequantization is q * scale whatever rule chose q, so every
stored int8 image decodes, including those quantized by division.
"""
from __future__ import annotations

import numpy as np

QBLOCK = 1024  # elements per quantization block
INV127 = np.float32(1.0 / 127.0)
SCALE_MIN = np.float32(2.0 ** -125)  # twice the smallest normal f32

# jax imports are deferred into the jnp functions so `quantize_np` /
# `dequantize_np` (the host checkpoint path) stay importable from a
# jax-free process (see repro.kernels.delta.ref).


def quantize_block(x, xp):
    """(rows, QBLOCK) f32 -> ((rows, QBLOCK) int8, (rows, 1) f32 scales),
    with `xp` numpy or jax.numpy (also the Pallas kernel's body)."""
    ax = xp.abs(x)
    amax = xp.max(ax, axis=-1, keepdims=True)
    scale = amax * INV127
    scale = xp.where(scale >= SCALE_MIN, scale, xp.float32(1.0))
    q0 = xp.clip(xp.round(ax / scale), 0, 127)
    up = (q0 < 127) & (ax >= (q0 + 0.5) * scale)
    down = (q0 > 0) & (ax < (q0 - 0.5) * scale)
    qa = xp.where(up, q0 + 1, xp.where(down, q0 - 1, q0))
    return xp.where(x < 0, -qa, qa).astype(xp.int8), scale


def pad_to_blocks(x):
    import jax.numpy as jnp
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = (-flat.size) % QBLOCK
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, QBLOCK), pad


def quantize_ref(blocks):
    """(n, QBLOCK) f32 -> ((n, QBLOCK) int8, (n, 1) f32 scales)."""
    import jax.numpy as jnp
    return quantize_block(blocks, jnp)


def dequantize_ref(q, scale):
    import jax.numpy as jnp
    return q.astype(jnp.float32) * scale


def quantize_np(x: np.ndarray):
    flat = np.ravel(x).astype(np.float32)
    pad = (-flat.size) % QBLOCK
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    q, scale = quantize_block(flat.reshape(-1, QBLOCK), np)
    return q, scale, pad


def dequantize_np(q: np.ndarray, scale: np.ndarray, pad: int, shape, dtype):
    out = (q.astype(np.float32) * scale).ravel()
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)
