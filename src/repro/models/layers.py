"""Shared model layers: norms, RoPE, MLP, embeddings.

Every init_* returns a pair of pytrees: (params, logical_axes).  The
logical-axes tree mirrors params with tuples of logical axis names that
`repro.sharding.rules` maps to mesh axes.  Params are plain jnp arrays —
no framework objects — so the whole tree is upper-half state in the
MANA-2.0 sense (host-serializable, mesh-free).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _norm_init(shape):
    return jnp.ones(shape, jnp.float32)


def _dense_init(key, shape, in_axis: int = -2):
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.normal(key, shape, jnp.float32) * scale)


def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale).astype(dt)


def head_rms_norm(x, eps: float = 1e-5):
    """Per-head RMS norm (rwkv group-norm analogue). x: (..., H, hd)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) or (S,) int32."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    angles = angles[..., None, :]                              # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, d_model: int):
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    half = d_model // 2
    div = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos * div
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# --------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "wi": _dense_init(k1, (d_model, d_ff)),
        "wg": _dense_init(k2, (d_model, d_ff)),
        "wo": _dense_init(k3, (d_ff, d_model)),
    }
    logical = {
        "wi": (None, "ffn"),
        "wg": (None, "ffn"),
        "wo": ("ffn", None),
    }
    return params, logical


def mlp_apply(p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
    g = jax.nn.silu(h)
    u = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", g * u, p["wo"].astype(x.dtype))


# --------------------------------------------------------------------------
# Embedding / LM head
# --------------------------------------------------------------------------


def init_embed(key, vocab: int, d_model: int, tie: bool):
    k1, k2 = jax.random.split(key)
    params = {"embedding": _dense_init(k1, (vocab, d_model), in_axis=-1)}
    logical = {"embedding": ("vocab", None)}
    if not tie:
        params["head"] = _dense_init(k2, (d_model, vocab))
        logical["head"] = (None, "vocab")
    return params, logical


def embed_apply(p, tokens, dtype):
    return p["embedding"].astype(dtype)[tokens]


def head_matrix(p):
    if "head" in p:
        return p["head"]
    return p["embedding"].T


def vocab_logit_mask(v_padded: int, v_real: int):
    """Additive mask (-1e9 on TP-padding vocab columns), or None."""
    if v_padded == v_real:
        return None
    return jnp.where(jnp.arange(v_padded) < v_real, 0.0, -1e9).astype(
        jnp.float32)


def chunked_softmax_xent(h, head, labels, mask, chunk: int,
                         valid_vocab: int = 0):
    """Sequence-chunked cross entropy: never materializes (B,S,V) logits.

    h: (B,S,d) activations; head: (d,V) (vocab-sharded); labels: (B,S);
    mask: (B,S) float; valid_vocab: real vocab size (columns beyond it
    are TP padding, excluded from the softmax).  Returns (sum, count).
    """
    B, S, d = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    assert S % chunk == 0, (S, chunk)
    hc = h.reshape(B, n, chunk, d).swapaxes(0, 1)          # (n,B,c,d)
    lc = labels.reshape(B, n, chunk).swapaxes(0, 1)        # (n,B,c)
    mc = mask.reshape(B, n, chunk).swapaxes(0, 1)
    vmask = vocab_logit_mask(head.shape[-1], valid_vocab or head.shape[-1])

    def body(carry, xs):
        hx, lx, mx = xs
        logits = jnp.einsum("bcd,dv->bcv", hx, head.astype(hx.dtype))
        logits = logits.astype(jnp.float32)
        if vmask is not None:
            logits = logits + vmask
        lse = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction, not take_along_axis: gather/scatter on the
        # vocab-sharded axis makes GSPMD replicate (observed in the HLO);
        # the one-hot einsum partitions cleanly and reduces over shards.
        oh = jax.nn.one_hot(lx, logits.shape[-1], dtype=logits.dtype)
        tgt = jnp.einsum("bcv,bcv->bc", logits, oh)
        loss = (lse - tgt) * mx
        return (carry[0] + loss.sum(), carry[1] + mx.sum()), None

    # rematerialized: without it autodiff keeps every chunk's f32 logits
    # and one-hot as scan residuals, i.e. the whole (B,S,V) tensor twice
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body),
                                 (jnp.zeros(()), jnp.zeros(())), (hc, lc, mc))
    return tot, cnt
