"""Pallas TPU kernel: XOR delta over uint32 word tiles.

Tiling: two (ROWS, DBLOCK) uint32 tiles (2 MiB each at ROWS=256) staged
in VMEM per grid step.  Pure VPU bit-op — the kernel exists
to keep the checkpoint hot path on device and fused with the DMA
pipeline rather than bouncing via host.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default, tile_rows
from repro.kernels.delta.ref import DBLOCK

ROWS = 256  # word tiles per grid step


def _xor_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] ^ b_ref[...]


def xor_pallas(a: jnp.ndarray, b: jnp.ndarray,
               interpret: Optional[bool] = None):
    """a, b: (n, DBLOCK) uint32 -> (n, DBLOCK) uint32."""
    if interpret is None:
        interpret = interpret_default()
    n = a.shape[0]
    rows = tile_rows(n, ROWS)
    spec = pl.BlockSpec((rows, DBLOCK), lambda i: (i, 0))
    return pl.pallas_call(
        _xor_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.uint32),
        interpret=interpret,
        name="delta_xor",
    )(a, b)
