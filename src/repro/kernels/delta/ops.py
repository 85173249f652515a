"""jit'd wrapper for XOR delta encode/apply + the HOST entry point the
incremental checkpoint pipeline calls per shard."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import host_words
from repro.kernels.delta import ref
from repro.kernels.delta.delta import xor_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_words(a: jnp.ndarray, b: jnp.ndarray,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """XOR of two (n, DBLOCK) uint32 word streams (the kernel path of
    `delta_host`, which builds the words on the host)."""
    return xor_pallas(a, b, interpret=interpret)


def delta_host(cur: np.ndarray, prev: np.ndarray,
               use_pallas: bool = False) -> np.ndarray:
    """XOR byte delta of two equal-shaped host arrays -> uint8[nbytes].

    With use_pallas the XOR runs through the Pallas word-tile kernel
    (the padded uint32 word stream is unpacked little-endian and
    trimmed back to the array's byte length — bit-exact with the numpy
    oracle) and a kernel failure raises; otherwise `ref.delta_np`
    computes it.
    """
    if use_pallas:
        assert cur.nbytes == prev.nbytes, (cur.nbytes, prev.nbytes)
        words = delta_words(jnp.asarray(host_words(cur, ref.DBLOCK)),
                            jnp.asarray(host_words(prev, ref.DBLOCK)))
        raw = np.asarray(words).astype("<u4", copy=False).reshape(-1)
        return raw.view(np.uint8)[:cur.nbytes]
    return ref.delta_np(cur, prev)


def apply_host(prev: np.ndarray, delta_bytes: np.ndarray, shape,
               dtype) -> np.ndarray:
    """Inverse of `delta_host` (XOR is its own inverse)."""
    return ref.apply_np(prev, delta_bytes, shape, dtype)
