"""jit'd wrappers for blockwise int8 quantize/dequantize + the HOST
entry point the checkpoint pipeline calls for low-precision shadows."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quantize import ref
from repro.kernels.quantize.quantize import dequantize_pallas, quantize_pallas


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def quantize(x: jnp.ndarray, use_kernel: bool = True,
             interpret: Optional[bool] = None):
    """x: any shape/float dtype -> (int8 blocks, f32 scales, pad)."""
    blocks, pad = ref.pad_to_blocks(x)
    if use_kernel:
        q, s = quantize_pallas(blocks, interpret=interpret)
    else:
        q, s = ref.quantize_ref(blocks)
    return q, s


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def dequantize(q: jnp.ndarray, s: jnp.ndarray, use_kernel: bool = True,
               interpret: Optional[bool] = None):
    if use_kernel:
        return dequantize_pallas(q, s, interpret=interpret)
    return ref.dequantize_ref(q, s)


def quantize_host(x: np.ndarray, use_pallas: bool = False):
    """Blockwise int8 quantization on the host checkpoint path.

    Returns (q int8[n, QBLOCK], scales f32[n, 1], pad).  With use_pallas
    the blocks run through the Pallas kernel and a kernel failure
    raises; otherwise the numpy oracle quantizes.
    """
    if use_pallas:
        q, s = quantize(jnp.asarray(x))
        pad = (-int(np.asarray(x).size)) % ref.QBLOCK
        return np.asarray(q), np.asarray(s, np.float32).reshape(-1, 1), pad
    return ref.quantize_np(x)
