"""jit'd wrapper for the checksum kernel and the HOST entry point the
checkpoint pipeline calls on every shard."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import host_words
from repro.kernels.checksum import ref
from repro.kernels.checksum.checksum import block_sums_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def checksum_words(words: jnp.ndarray,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """uint32 checksum of a (n_blocks, BLOCK) uint32 word stream (the
    kernel path of `checksum_host`, which builds the words on the host)."""
    return ref.fold(block_sums_pallas(words, interpret=interpret))


def checksum_host(data: np.ndarray, use_pallas: bool = False) -> int:
    """Shard digest on the host write/restore path (checkpoint pipeline).

    With use_pallas the digest runs through the Pallas kernel (bit-exact
    with the oracle by construction) and a kernel failure raises;
    otherwise the numpy oracle computes it.
    """
    if use_pallas:
        words = jnp.asarray(host_words(data, ref.BLOCK))
        return int(np.asarray(checksum_words(words)))
    return ref.checksum_np(data)
