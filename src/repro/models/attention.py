"""Attention: GQA, sliding-window, cross-attention — flash-style chunked
attention in pure jnp with a custom VJP, and single-token decode against
KV caches.

Why custom_vjp: reverse-mode AD through a scan saves every step's
residuals, i.e. the full (S, T) attention weights — exactly what flash
attention exists to avoid.  The custom backward recomputes probabilities
blockwise from the saved log-sum-exp, so both forward and backward run in
O(block) memory.  This lowers on every backend (dry-run requirement); the
paper under reproduction (MANA-2.0) contributes no attention kernels —
its Pallas kernels live on the checkpoint data path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, apply_rope

NEG_INF = -1e9


def head_mask(cfg) -> jnp.ndarray:
    """(H_pad,) 0/1 mask of real heads in the padded (K_pad, G_pad) grid.

    Dummy heads exist only so head dims tile evenly over the model axis;
    multiplying attention output by this mask zeroes their contribution
    AND their gradient (wo sees zero activations), keeping padded and
    unpadded models mathematically identical.
    """
    kp, gp = cfg.padded_heads()
    K, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    k_idx = jnp.arange(kp)[:, None]
    g_idx = jnp.arange(gp)[None, :]
    return ((k_idx < K) & (g_idx < G)).astype(jnp.float32).reshape(-1)


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    params = {
        "wq": _dense_init(ks[0], (d_model, n_heads, head_dim)),
        "wk": _dense_init(ks[1], (d_model, n_kv_heads, head_dim)),
        "wv": _dense_init(ks[2], (d_model, n_kv_heads, head_dim)),
        "wo": _dense_init(ks[3], (n_heads, head_dim, d_model), in_axis=0),
    }
    logical = {
        "wq": (None, "heads", None),
        "wk": (None, "kv_heads", None),
        "wv": (None, "kv_heads", None),
        "wo": ("heads", None, None),
    }
    if qkv_bias:
        params["bq"] = jnp.zeros((n_heads, head_dim), jnp.float32)
        params["bk"] = jnp.zeros((n_kv_heads, head_dim), jnp.float32)
        params["bv"] = jnp.zeros((n_kv_heads, head_dim), jnp.float32)
        logical["bq"] = ("heads", None)
        logical["bk"] = ("kv_heads", None)
        logical["bv"] = ("kv_heads", None)
    return params, logical


def qkv_proj(p, x, rope_theta: float, positions):
    """x: (B,S,d) -> q (B,S,H,hd), k,v (B,S,K,hd) with RoPE applied."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def out_proj(p, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


def _group(q, n_kv: int):
    """(B,S,H,hd) -> (B,S,K,G,hd) grouped query heads."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


# ==========================================================================
# Flash attention (chunked over KV, online softmax, custom VJP)
# Covers: full causal self-attention, non-causal encoder self-attention,
# cross attention (T != S).
# ==========================================================================


def _causal_mask(S: int, T: int, j: int, chunk: int):
    """Mask block j of keys against all S queries (key offset = T - S ... no:
    queries are positions [0,S) and keys [0,T); for self-attn T == S."""
    qpos = jnp.arange(S)
    kpos = j * chunk + jnp.arange(chunk)
    return qpos[:, None] >= kpos[None, :]


def _flash_fwd_impl(q, k, v, causal: bool, chunk: int):
    B, S, K, G, hd = q.shape
    T = k.shape[1]
    n = T // chunk
    kb = k.reshape(B, n, chunk, K, hd).swapaxes(0, 1)
    vb = v.reshape(B, n, chunk, K, v.shape[-1]).swapaxes(0, 1)

    def body(carry, xs):
        m, l, acc = carry
        kx, vx, j = xs
        s = jnp.einsum("bskgh,bckh->bskgc", q, kx,
                       preferred_element_type=jnp.float32)
        if causal:
            mask = _causal_mask(S, T, j, chunk)
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # store p in the compute dtype (bf16 in production): the (S, c)
        # probability tensors dominate HBM traffic in jnp-flash; the MXU
        # consumes bf16 and l/acc keep f32 accumulation
        p = jnp.exp(s - m_new[..., None]).astype(q.dtype)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.astype(jnp.float32).sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgc,bckh->bskgh", p, vx,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, K, G), jnp.float32)
    a0 = jnp.zeros((B, S, K, G, v.shape[-1]), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(n)))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal: bool, chunk: int):
    out, _ = _flash_fwd_impl(q, k, v, causal, chunk)
    return out


def _flash_fwd(q, k, v, causal, chunk):
    out, lse = _flash_fwd_impl(q, k, v, causal, chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, chunk, res, dout):
    q, k, v, out, lse = res
    B, S, K, G, hd = q.shape
    T = k.shape[1]
    n = T // chunk
    kb = k.reshape(B, n, chunk, K, hd).swapaxes(0, 1)
    vb = v.reshape(B, n, chunk, K, v.shape[-1]).swapaxes(0, 1)
    dout_f = dout.astype(jnp.float32)
    # delta = rowsum(dout * out)
    delta = jnp.sum(dout_f * out.astype(jnp.float32), axis=-1)  # (B,S,K,G)

    def body(dq, xs):
        kx, vx, j = xs
        s = jnp.einsum("bskgh,bckh->bskgc", q, kx,
                       preferred_element_type=jnp.float32)
        if causal:
            mask = _causal_mask(S, T, j, chunk)
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None]).astype(q.dtype)       # (B,S,K,G,c)
        dv = jnp.einsum("bskgc,bskgh->bckh", p, dout,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bskgh,bckh->bskgc", dout, vx,
                        preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - delta[..., None])).astype(q.dtype)
        dq = dq + jnp.einsum("bskgc,bckh->bskgh", ds, kx,
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bskgc,bskgh->bckh", ds, q,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, S, K, G, hd), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(n)))
    dk = dkb.swapaxes(0, 1).reshape(B, T, K, hd).astype(k.dtype)
    dv = dvb.swapaxes(0, 1).reshape(B, T, K, v.shape[-1]).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_chunk(total: int, chunk: int) -> int:
    """Largest divisor of `total` that is <= `chunk` (trace-time only)."""
    chunk = min(chunk, total)
    while total % chunk:
        chunk -= 1
    return chunk


def flash_attention(q, k, v, *, causal: bool, chunk: int = 128):
    """q: (B,S,H,hd); k: (B,T,K,hd); v: (B,T,K,hv) -> (B,S,H,hv).  The
    softmax scale is hd ** -0.5 (hv may differ from hd, as in MLA)."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    T = k.shape[1]
    chunk = _fit_chunk(T, chunk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    qg = _group(q * scale, K)
    o = _flash(qg, k, v, causal, chunk)
    return o.reshape(B, S, H, v.shape[-1])


# ==========================================================================
# Sliding-window attention (scan over query blocks, custom VJP)
# ==========================================================================


def _swa_mask(start, window: int, chunk: int, span: int):
    qpos = start + jnp.arange(chunk)
    tpos = start - window + jnp.arange(span)
    diff = qpos[:, None] - tpos[None, :]
    return (diff >= 0) & (diff < window) & (tpos[None, :] >= 0)


def _swa_fwd_impl(q, kp, vp, window: int, chunk: int):
    """q: (B,S,K,G,hd); kp/vp: (B,S+window,K,hd) front-padded."""
    B, S, K, G, hd = q.shape
    n = S // chunk
    span = window + chunk
    qb = q.reshape(B, n, chunk, K, G, hd).swapaxes(0, 1)

    def body(_, xs):
        qx, i = xs
        start = i * chunk
        kx = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vx = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        s = jnp.einsum("bckgh,btkh->bckgt", qx, kx,
                       preferred_element_type=jnp.float32)
        mask = _swa_mask(start, window, chunk, span)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        mx = s.max(axis=-1)
        p = jnp.exp(s - mx[..., None]).astype(qx.dtype)
        l = p.astype(jnp.float32).sum(axis=-1)
        o = jnp.einsum("bckgt,btkh->bckgh", p, vx,
                       preferred_element_type=jnp.float32)
        o = (o / jnp.maximum(l, 1e-30)[..., None]).astype(qx.dtype)
        return None, (o, mx + jnp.log(jnp.maximum(l, 1e-30)))

    _, (ob, lseb) = jax.lax.scan(body, None, (qb, jnp.arange(n)))
    out = ob.swapaxes(0, 1).reshape(B, S, K, G, hd)
    lse = lseb.swapaxes(0, 1).reshape(B, S, K, G)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _swa(q, kp, vp, window: int, chunk: int):
    out, _ = _swa_fwd_impl(q, kp, vp, window, chunk)
    return out


def _swa_fwd(q, kp, vp, window, chunk):
    out, lse = _swa_fwd_impl(q, kp, vp, window, chunk)
    return out, (q, kp, vp, out, lse)


def _swa_bwd(window, chunk, res, dout):
    q, kp, vp, out, lse = res
    B, S, K, G, hd = q.shape
    n = S // chunk
    span = window + chunk
    qb = q.reshape(B, n, chunk, K, G, hd).swapaxes(0, 1)
    doutb = dout.reshape(B, n, chunk, K, G, hd).swapaxes(0, 1)
    outb = out.reshape(B, n, chunk, K, G, hd).swapaxes(0, 1)
    lseb = lse.reshape(B, n, chunk, K, G).swapaxes(0, 1)
    Tp = kp.shape[1]

    def body(carry, xs):
        dkp, dvp = carry
        qx, dox, ox, lx, i = xs
        start = i * chunk
        kx = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vx = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        s = jnp.einsum("bckgh,btkh->bckgt", qx, kx,
                       preferred_element_type=jnp.float32)
        mask = _swa_mask(start, window, chunk, span)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lx[..., None]).astype(qx.dtype)
        delta = jnp.sum(dox.astype(jnp.float32) * ox.astype(jnp.float32),
                        axis=-1)
        dv = jnp.einsum("bckgt,bckgh->btkh", p, dox,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bckgh,btkh->bckgt", dox, vx,
                        preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - delta[..., None])).astype(qx.dtype)
        dq = jnp.einsum("bckgt,btkh->bckgh", ds, kx,
                        preferred_element_type=jnp.float32)
        dk = jnp.einsum("bckgt,bckgh->btkh", ds, qx,
                        preferred_element_type=jnp.float32)
        # accumulate into the (overlapping) kv span: slice, add, write back
        dks = jax.lax.dynamic_slice_in_dim(dkp, start, span, axis=1)
        dvs = jax.lax.dynamic_slice_in_dim(dvp, start, span, axis=1)
        dkp = jax.lax.dynamic_update_slice_in_dim(dkp, dks + dk, start, axis=1)
        dvp = jax.lax.dynamic_update_slice_in_dim(dvp, dvs + dv, start, axis=1)
        return (dkp, dvp), dq

    dkp0 = jnp.zeros(kp.shape, jnp.float32)
    dvp0 = jnp.zeros(vp.shape, jnp.float32)
    (dkp, dvp), dqb = jax.lax.scan(
        body, (dkp0, dvp0), (qb, doutb, outb, lseb, jnp.arange(n)))
    dq = dqb.swapaxes(0, 1).reshape(B, S, K, G, hd).astype(q.dtype)
    return dq, dkp.astype(kp.dtype), dvp.astype(vp.dtype)


_swa.defvjp(_swa_fwd, _swa_bwd)


def sliding_window_attention(q, k, v, *, window: int, chunk: int = 128):
    """Causal SWA: O(S * window) compute, O(block) memory."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    chunk = _fit_chunk(S, chunk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    qg = _group(q * scale, K)
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    o = _swa(qg, kp, vp, window, chunk)
    return o.reshape(B, S, H, hd)


# ==========================================================================
# Single-token decode against a KV cache
# ==========================================================================


def decode_attention(q, k_cache, v_cache, pos, window: int = 0):
    """q: (B,1,H,hd); caches: (B,T,K,hd) (T = capacity; ring iff window>0).

    `pos` is the position of the new token (already written to the cache).
    Keys in the cache are stored *post-RoPE*.
    """
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    T = k_cache.shape[1]
    qg = _group(q, K)[:, 0]  # (B,K,G,hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    s = jnp.einsum("bkgh,btkh->bkgt", qg * scale, k_cache,
                   preferred_element_type=jnp.float32)
    slots = jnp.arange(T)
    if window:
        # ring buffer: slot s holds position pos - ((pos - s) mod T)
        slot_pos = pos - jnp.mod(pos - slots, T)
        valid = (slot_pos >= 0) & (slot_pos > pos - window)
    else:
        valid = slots <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgt,btkh->bkgh", w, v_cache)
    return o.reshape(B, 1, H, hd)


def cache_write(k_cache, v_cache, k_new, v_new, pos, window: int = 0):
    """Write one token's (already-RoPE'd) K/V at `pos` (ring slot iff SWA)."""
    T = k_cache.shape[1]
    slot = jnp.mod(pos, T) if window else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    return k_cache, v_cache
