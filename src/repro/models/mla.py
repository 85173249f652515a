"""Multi-head latent attention (MLA; DeepSeek-V2/V3, arXiv:2412.19437),
for training over full sequences.

Per layer, without a query LoRA (Moonlight's `q_lora_rank` is null):

* q = x Wq, split per head into a no-position part (qk_nope_dim) and a
  rotary part (qk_rope_dim);
* [c, k_rope] = x Wkv_a: the compressed kv (kv_lora_rank) and one rotary
  key shared by all heads; c passes its own RMSNorm (`kv_norm`, epsilon
  1e-6);
* [k_nope, v] = c Wkv_b, per head;
* the rotary dims use interleaved RoPE: pairs (2i, 2i+1) rotate by
  frequency i (`rope_interleave`, the transformers default for
  deepseek_v3, which de-interleaves and rotates halves: the same scores);
* softmax scale (qk_nope_dim + qk_rope_dim) ** -0.5; causal attention
  whose q/k head size (192) differs from its v head size (128).

The attention itself is the chunked flash attention of `attention.py`.
Decode through a latent cache is not built.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.layers import _dense_init, _norm_init, apply_rope, rms_norm

# the compressed kv's RMSNorm takes RMSNorm's default epsilon in
# DeepSeek-V3's code (and transformers'), not the config's rms_norm_eps
KV_NORM_EPS = 1e-6


def init_mla(key, d_model: int, n_heads: int, kv_lora_rank: int,
             qk_nope_dim: int, qk_rope_dim: int, v_head_dim: int):
    ks = jax.random.split(key, 4)
    params = {
        "wq": _dense_init(ks[0], (d_model, n_heads, qk_nope_dim + qk_rope_dim)),
        "wkv_a": _dense_init(ks[1], (d_model, kv_lora_rank + qk_rope_dim)),
        "kv_norm": _norm_init((kv_lora_rank,)),
        "wkv_b": _dense_init(ks[2], (kv_lora_rank, n_heads,
                                     qk_nope_dim + v_head_dim), in_axis=0),
        "wo": _dense_init(ks[3], (n_heads, v_head_dim, d_model), in_axis=0),
    }
    logical = {
        "wq": (None, "heads", None),
        "wkv_a": (None, "kv_latent"),
        "kv_norm": ("kv_latent",),
        "wkv_b": ("kv_latent", "heads", None),
        "wo": ("heads", None, None),
    }
    return params, logical


def rope_interleaved(x, positions, theta: float):
    """RoPE on adjacent pairs: x (..., S, H, r), pair (2i, 2i+1) rotated
    by frequency i; the result holds the rotated even members, then the
    odd ones (the layout transformers' `apply_rotary_pos_emb_interleave`
    returns)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def mla_attention(p, x, cfg, positions, *, chunk: int):
    """x: (B, S, d) -> (B, S, d), causal."""
    with jax.named_scope("mla"):
        dt = x.dtype
        nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        kv_a = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"].astype(dt))
        c = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"],
                     KV_NORM_EPS)
        k_rope = rope_interleaved(kv_a[..., None, cfg.kv_lora_rank:],
                                  positions, cfg.rope_theta)
        kv = jnp.einsum("bsc,chk->bshk", c, p["wkv_b"].astype(dt))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], positions,
                                             cfg.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rope,))],
            axis=-1)
        o = attn.flash_attention(q, k, v, causal=True, chunk=chunk)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
