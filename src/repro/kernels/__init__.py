# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# Package import stays jax-free: the numpy oracles in the `ref` modules
# run in jax-free rank processes.


def interpret_default() -> bool:
    """Pallas interpret mode only where the backend is the CPU; on a
    TPU every kernel is compiled (Mosaic `tpu_custom_call`)."""
    import jax
    return jax.default_backend() == "cpu"


def tile_rows(n: int, cap: int) -> int:
    """Rows per grid step for an n-row operand: the whole operand when
    it has fewer than 8 rows, else a multiple of 8 (the TPU's sublane
    tiling) of at most `cap`.  The grid is `cdiv(n, rows)`: Pallas pads
    a partial last tile on read and drops its rows past n on write,
    which is exact for these row-independent kernels."""
    return n if n < 8 else min(cap, n // 8 * 8)


def host_words(data, block: int):
    """Host array -> (n, block) little-endian uint32 words of its bytes,
    zero-padded to whole blocks: the word stream the kernels consume,
    built on the host (a zero-copy view when no padding is needed), so
    the device never lays out a byte array."""
    import numpy as np
    raw = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    pad = (-raw.size) % (4 * block)
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
    return raw.view("<u4").reshape(-1, block)
