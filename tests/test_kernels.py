"""Per-kernel validation (deliverable c): shape/dtype sweeps, Pallas
kernel (interpret mode) vs pure-jnp oracle vs numpy host twin."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal env: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

from repro.kernels import host_words
from repro.kernels.checksum import ops as cops
from repro.kernels.checksum import ref as cref
from repro.kernels.delta import ops as dops
from repro.kernels.delta import ref as dref
from repro.kernels.quantize import ops as qops
from repro.kernels.quantize import ref as qref

SHAPES = [(8,), (127,), (33, 65), (4, 8, 16), (2048,), (3, 2048)]
DTYPES = [np.float32, np.float16, np.int32, np.uint8]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_checksum_kernel_matches_oracle(shape, dtype):
    rng = np.random.RandomState(hash((shape, str(dtype))) % 2**31)
    if np.issubdtype(dtype, np.floating):
        x = rng.randn(*shape).astype(dtype)
    else:
        x = rng.randint(0, 100, shape).astype(dtype)
    k = cops.checksum_host(x, use_pallas=True)
    r = int(cref.checksum_ref(jnp.asarray(x)))
    n = cref.checksum_np(x)
    assert k == r == n


def test_checksum_detects_corruption():
    x = np.arange(10000, dtype=np.float32)
    a = cref.checksum_np(x)
    x[1234] += 1e-4
    assert cref.checksum_np(x) != a


@pytest.mark.parametrize("shape", [(1024,), (5000,), (16, 1024), (7, 333),
                                   (12, 1024)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_quantize_kernel_matches_oracle(shape, dtype):
    rng = np.random.RandomState(0)
    x = (rng.randn(*shape) * 10).astype(dtype)
    q1, s1 = qops.quantize(jnp.asarray(x), use_kernel=True)
    blocks, _ = qref.pad_to_blocks(jnp.asarray(x))
    q2, s2 = qref.quantize_ref(blocks)
    q3, s3, _ = qref.quantize_np(x)
    for q, s in ((q2, s2), (q3, s3)):   # bit for bit, scales included
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s))
    # roundtrip error bounded by scale/2 per block
    deq = np.asarray(qops.dequantize(q1, s1)).ravel()[:x.size]
    scale_per_elem = np.repeat(np.asarray(s1).ravel(),
                               qref.QBLOCK)[:x.size]
    assert (np.abs(deq - x.ravel().astype(np.float32))
            <= scale_per_elem * 0.5 + 1e-7).all()


def test_quantize_np_twin_matches_jnp():
    x = np.random.RandomState(1).randn(777).astype(np.float32)
    qn, sn, pad = qref.quantize_np(x)
    qj, sj = qref.quantize_ref(qref.pad_to_blocks(jnp.asarray(x))[0])
    np.testing.assert_array_equal(qn, np.asarray(qj))
    np.testing.assert_array_equal(sn, np.asarray(sj))
    out = qref.dequantize_np(qn, sn, pad, x.shape, x.dtype)
    assert out.shape == x.shape


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32])
def test_delta_kernel_roundtrip(dtype):
    rng = np.random.RandomState(2)
    prev = (rng.randn(3, 2048) * 5).astype(dtype)
    cur = prev.copy()
    cur[1, ::7] += np.asarray(1, dtype)
    words = dops.delta_words(jnp.asarray(host_words(cur, dref.DBLOCK)),
                             jnp.asarray(host_words(prev, dref.DBLOCK)))
    d_ref = np.asarray(dref.delta_ref(jnp.asarray(cur), jnp.asarray(prev)))
    np.testing.assert_array_equal(np.asarray(words), d_ref)
    d_np = dref.delta_np(cur, prev)
    np.testing.assert_array_equal(dops.delta_host(cur, prev, use_pallas=True),
                                  d_np)
    # host-side apply restores exactly
    back = dref.apply_np(prev, d_np, cur.shape, cur.dtype)
    np.testing.assert_array_equal(back, cur)
    # identical arrays -> all-zero delta
    z = dref.delta_np(prev, prev)
    assert not z.any()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4000), st.integers(0, 2**31 - 1))
def test_checksum_property_any_length(n, seed):
    """Checksum is deterministic and single-bit sensitive at any length."""
    rng = np.random.RandomState(seed % 2**31)
    x = rng.randint(0, 256, n).astype(np.uint8)
    a = cref.checksum_np(x)
    assert a == cref.checksum_np(x.copy())
    y = x.copy()
    y[rng.randint(n)] ^= 1
    assert cref.checksum_np(y) != a


# host entry points with use_pallas: the kernel path the checkpoint
# codecs take, bit for bit against the numpy oracles (block counts that
# leave a partial last tile included)
HOST_BYTES = [1, 4095, 8192, 8192 * 9 + 3, 8192 * 300 + 17]


@pytest.mark.parametrize("n", HOST_BYTES)
def test_checksum_host_kernel_matches_np(n):
    x = np.random.RandomState(n % 997).randint(0, 256, n).astype(np.uint8)
    assert cops.checksum_host(x, use_pallas=True) == cref.checksum_np(x)


@pytest.mark.parametrize("n", HOST_BYTES)
def test_delta_host_kernel_matches_np(n):
    rng = np.random.RandomState(n % 991)
    cur = rng.randint(0, 256, n).astype(np.uint8)
    prev = rng.randint(0, 256, n).astype(np.uint8)
    np.testing.assert_array_equal(dops.delta_host(cur, prev, use_pallas=True),
                                  dref.delta_np(cur, prev))


# 1e-37: a block whose scale would be subnormal, which the TPU flushes
QUANT_HOST_N = [5, 12 * 1024 + 7, 13 * 1024, 300 * 1024]
QUANT_HOST_SCALES = [1e-37, 1e-8, 1.0, 1e3]


def _quant_host_input(n, scale):
    x = (np.random.RandomState(n % 983).randn(n) * scale).astype(np.float32)
    x[::17] = 0.0
    return x


@pytest.mark.parametrize("n", QUANT_HOST_N)
@pytest.mark.parametrize("scale", QUANT_HOST_SCALES)
def test_quantize_host_kernel_matches_np(n, scale):
    x = _quant_host_input(n, scale)
    (q1, s1, p1), (q2, s2, p2) = (qops.quantize_host(x, use_pallas=True),
                                  qref.quantize_np(x))
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_array_equal(s1, s2)
    assert p1 == p2


@pytest.mark.parametrize("n", QUANT_HOST_N)
@pytest.mark.parametrize("scale", QUANT_HOST_SCALES)
def test_quantize_host_kernel_meets_definition(n, scale):
    """What the kernel writes, checked without `quantize_block` (which
    the kernel and both oracles share): the definition in the module
    docstring of `quantize.ref`, and the division rule it replaced."""
    x = _quant_host_input(n, scale)
    q, s, pad = qops.quantize_host(x, use_pallas=True)
    xb = np.concatenate([x, np.zeros(pad, np.float32)])
    xb = xb.reshape(-1, qref.QBLOCK)
    amax = np.abs(xb).max(axis=1, keepdims=True)
    live = (amax * qref.INV127 >= qref.SCALE_MIN)[:, 0]
    np.testing.assert_array_equal(
        s, np.where(live[:, None], amax * qref.INV127, np.float32(1.0)))
    assert not q[~live].any()            # absmax below 127 * SCALE_MIN
    # within half a step of x; slack: one f32 ulp of the top threshold
    s64, x64 = s.astype(np.float64)[live], xb.astype(np.float64)[live]
    err = np.abs(q[live] * s64 - x64)
    assert (err <= s64 * (0.5 + 2.0 ** -16)).all()
    # the old rule, round(x / (amax / 127)) half to even: at most one
    # code apart, and only where x sits on a tie between two codes
    old_s = amax[live] / np.float32(127.0)
    ratio = x64 / old_s.astype(np.float64)
    old_q = np.clip(np.round(xb[live] / old_s), -127, 127)
    apart = np.abs(q[live].astype(np.int32) - old_q)
    assert apart.max(initial=0) <= 1
    tie = np.abs(np.abs(ratio) % 1.0 - 0.5)
    assert (tie[apart == 1] < 1e-4).all()


def test_quantize_block_below_scale_min_is_zeros():
    x = np.full(qref.QBLOCK, 2.7e-37, np.float32)
    x[1] = -1e-38
    q, s, _ = qref.quantize_np(x)
    assert s[0, 0] == 1.0 and not q.any()
    qk, sk, _ = qops.quantize_host(x, use_pallas=True)
    np.testing.assert_array_equal(qk, q)
    np.testing.assert_array_equal(sk, s)
