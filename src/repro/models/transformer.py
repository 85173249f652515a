"""Model assembly: decoder-only / enc-dec / vision-cross-attn / hybrid /
attention-free, with scan-over-layers (stacked params), remat policies,
and train / prefill / decode entry points.

All params are plain jnp arrays with a mirrored logical-axes tree —
pure "upper-half" state in the MANA-2.0 sense.  Layers are scanned
(stacked on axis 0) so compile time is depth-independent: essential for
the 80-compile dry-run matrix.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

from jax.ad_checkpoint import checkpoint_name as _ckpt_name

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba as mam
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod


# ==========================================================================
# Init
# ==========================================================================


def _init_dense_block(key, cfg: ModelConfig, cross: bool,
                      dense_ffn: bool = False):
    """One block; `dense_ffn` gives a leading dense layer of an MoE model
    its MLP."""
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {"ln1": L._norm_init((cfg.d_model,)),
                              "ln2": L._norm_init((cfg.d_model,))}
    logical: Dict[str, Any] = {"ln1": (None,), "ln2": (None,)}
    if cfg.kv_lora_rank:
        params["attn"], logical["attn"] = mla.init_mla(
            ks[0], cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim)
    else:
        params["attn"], logical["attn"] = attn.init_attention(
            ks[0], cfg.d_model, cfg.n_heads_padded, cfg.n_kv_heads_padded,
            cfg.head_dim, cfg.qkv_bias)
    if cfg.ssm_state:
        params["mamba"], logical["mamba"] = mam.init_mamba(
            ks[1], cfg.d_model, cfg.ssm_state, cfg.ssm_expand)
    if cross:
        params["lnx"] = L._norm_init((cfg.d_model,))
        logical["lnx"] = (None,)
        params["xattn"], logical["xattn"] = attn.init_attention(
            ks[2], cfg.d_model, cfg.n_heads_padded, cfg.n_kv_heads_padded,
            cfg.head_dim)
    if cfg.moe is None or dense_ffn:
        params["mlp"], logical["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff)
    else:
        params["moe"], logical["moe"] = moe_mod.init_layer(
            ks[3], cfg, moe_split(cfg))
    return params, logical


def moe_split(cfg: ModelConfig, model_axis: int = 16) -> int:
    """Virtual-expert split so E*split % model_axis == 0 (DESIGN.md §3)."""
    if cfg.moe is None:
        return 1
    import math
    e = cfg.moe.num_experts
    if e % model_axis == 0:
        return 1
    g = math.gcd(e, model_axis)
    return model_axis // g


def _init_rwkv_block(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    params = {"ln1": L._norm_init((cfg.d_model,)),
              "ln2": L._norm_init((cfg.d_model,))}
    logical = {"ln1": (None,), "ln2": (None,)}
    params["tm"], logical["tm"] = rwkv_mod.init_rwkv_time_mix(
        k1, cfg.d_model, cfg.n_heads_padded, cfg.head_dim)
    params["cm"], logical["cm"] = rwkv_mod.init_rwkv_channel_mix(
        k2, cfg.d_model, cfg.d_ff)
    return params, logical


def _stack_init(fn, keys):
    """vmap an init over a batch of keys -> stacked (L, ...) params."""
    params, logical = jax.vmap(lambda k: fn(k)[0])(keys), fn(keys[0])[1]
    logical = jax.tree.map(lambda lg: ("layers",) + lg, logical,
                           is_leaf=lambda x: isinstance(x, tuple))
    return params, logical


def init_params(cfg: ModelConfig, key) -> Tuple[Dict, Dict]:
    """Returns (params, logical_axes) pytrees."""
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    logical: Dict[str, Any] = {}

    params["embed"], logical["embed"] = L.init_embed(
        keys[0], cfg.vocab_padded, cfg.d_model, cfg.tie_embeddings)
    params["ln_f"] = L._norm_init((cfg.d_model,))
    logical["ln_f"] = (None,)

    if cfg.rwkv:
        bkeys = jax.random.split(keys[1], cfg.n_layers)
        params["blocks"], logical["blocks"] = _stack_init(
            lambda k: _init_rwkv_block(k, cfg), bkeys)
    elif cfg.cross_attn_every:
        # groups of (cross_attn_every - 1) self layers + 1 cross layer
        per = cfg.cross_attn_every
        n_groups = cfg.n_layers // per
        skeys = jax.random.split(keys[1], n_groups * (per - 1)).reshape(
            n_groups, per - 1, *keys[1].shape)
        ckeys = jax.random.split(keys[2], n_groups)
        self_init = lambda k: _init_dense_block(k, cfg, cross=False)
        p_self = jax.vmap(jax.vmap(lambda k: self_init(k)[0]))(skeys)
        lg_self = jax.tree.map(lambda lg: ("layers", "layers") + lg,
                               self_init(skeys[0, 0])[1],
                               is_leaf=lambda x: isinstance(x, tuple))
        params["self_blocks"], logical["self_blocks"] = p_self, lg_self
        params["cross_blocks"], logical["cross_blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, cross=True), ckeys)
    else:
        if cfg.n_dense_layers:
            # leading dense layers: a stack (and a scan) of their own, as
            # their leaves differ in shape from the expert layers'
            dkeys = jax.random.split(keys[4], cfg.n_dense_layers)
            params["dense_blocks"], logical["dense_blocks"] = _stack_init(
                lambda k: _init_dense_block(k, cfg, cross=cfg.enc_dec,
                                            dense_ffn=True), dkeys)
        bkeys = jax.random.split(keys[1], cfg.n_layers - cfg.n_dense_layers)
        params["blocks"], logical["blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, cross=cfg.enc_dec), bkeys)

    if cfg.enc_dec:
        ekeys = jax.random.split(keys[3], cfg.n_enc_layers)
        params["enc_blocks"], logical["enc_blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, cross=False), ekeys)
        params["enc_ln_f"] = L._norm_init((cfg.d_model,))
        logical["enc_ln_f"] = (None,)
    return params, logical


# ==========================================================================
# Full-sequence block application (train / prefill)
# ==========================================================================


def _self_attention_seq(cfg: ModelConfig, rc: RunConfig, p, h, positions,
                        causal: bool):
    if cfg.kv_lora_rank:
        # no kv cache is kept: MLA has no prefill / decode here
        return mla.mla_attention(p, h, cfg, positions,
                                 chunk=rc.attn_chunk), (None, None)
    q, k, v = attn.qkv_proj(p, h, cfg.rope_theta, positions)
    S = h.shape[1]
    if cfg.sliding_window and causal and cfg.sliding_window < S:
        o = attn.sliding_window_attention(
            q, k, v, window=cfg.sliding_window, chunk=rc.attn_chunk)
    else:
        o = attn.flash_attention(q, k, v, causal=causal, chunk=rc.attn_chunk)
    o = o * attn.head_mask(cfg)[None, None, :, None].astype(o.dtype)
    return attn.out_proj(p, o), (k, v)


def _cross_attention_seq(cfg, rc, p, h, enc_out):
    dt = h.dtype
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    o = attn.flash_attention(q, k, v, causal=False, chunk=rc.attn_chunk)
    o = o * attn.head_mask(cfg)[None, None, :, None].astype(o.dtype)
    return attn.out_proj(p, o), (k, v)


def _mixer_block_seq(cfg, rc, rules, p, x, positions, enc_out, causal=True):
    """One dense/moe/hybrid block over a full sequence.

    Returns (x, aux, cache) — cache holds what prefill must keep.
    """
    cache: Dict[str, Any] = {}
    aux = {}
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    a_out, (k, v) = _self_attention_seq(cfg, rc, p["attn"], h, positions,
                                        causal)
    a_out = _ckpt_name(a_out, "attn_out")
    if cfg.ssm_state:
        m_out, ssm_state, conv_tail = mam.mamba_apply(p["mamba"], h,
                                                  chunk=rc.la_chunk)
        a_out = (a_out + m_out) * 0.5
        a_out = _ckpt_name(a_out, "mixer_out")
        cache["ssm"] = ssm_state
        cache["conv"] = conv_tail
    x = x + a_out
    if "xattn" in p and enc_out is not None:
        hx = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        x_out, (ck, cv) = _cross_attention_seq(cfg, rc, p["xattn"], hx, enc_out)
        x = x + x_out
        cache["xk"], cache["xv"] = ck, cv
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, aux = moe_mod.apply_layer(p["moe"], h2, cfg, moe_split(cfg), rules)
    else:
        y = L.mlp_apply(p["mlp"], h2)
    y = _ckpt_name(y, "mlp_out")
    x = x + y
    # prefill KV cache: SWA keeps the last `window` positions (ring layout)
    if cfg.sliding_window and causal:
        cache["k"], cache["v"] = (k[:, -cfg.sliding_window:],
                                  v[:, -cfg.sliding_window:])
    else:
        cache["k"], cache["v"] = k, v
    return x, aux, cache


def _rwkv_block_seq(cfg, rc, rules, p, x):
    cache: Dict[str, Any] = {}
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    tm_out, la_state, shift_a = rwkv_mod.rwkv_time_mix(
        p["tm"], h, chunk=rc.la_chunk, mask=attn.head_mask(cfg))
    x = x + tm_out
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    cm_out, shift_c = rwkv_mod.rwkv_channel_mix(p["cm"], h2)
    x = x + cm_out
    cache.update(la=la_state, shift_a=shift_a, shift_c=shift_c)
    return x, {}, cache


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        # save every dot output (incl. the TP partial sums whose
        # all-reduces would otherwise run again during recompute)
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if policy == "comm":
        # save ONLY the post-collective block outputs: backward recompute
        # then never re-runs the TP all-reduces (the §Perf "comm" policy)
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mixer_out", "mlp_out"))
    return jax.checkpoint(fn)  # "full": save only layer boundaries


def _constrain(x, rules, logical):
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules.named(logical, x.shape))


def _encode(params, cfg, rc, rules, frames):
    """Whisper encoder over stub frame embeddings. frames: (B,Te,d)."""
    Te = frames.shape[1]
    x = frames + L.sinusoidal_positions(Te, cfg.d_model).astype(frames.dtype)
    positions = jnp.arange(Te)

    def body(x, p):
        x = _constrain(x, rules, ("batch", "seq", None))
        x, _, _ = _mixer_block_seq(cfg, rc, rules, p, x, positions, None,
                                   causal=False)
        return x, None

    x, _ = jax.lax.scan(_remat(body, rc.remat_policy), x, params["enc_blocks"])
    return L.rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, rc: RunConfig, rules, batch,
            want_cache: bool = False):
    """Full-sequence forward.  batch: tokens (B,S) [+ frames | patches].

    Returns (hidden (B,S,d), aux-losses, caches | None).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    dtype = jnp.dtype(rc.dtype)
    x = L.embed_apply(params["embed"], tokens, dtype)
    x = _constrain(x, rules, ("batch", "seq", None))
    positions = jnp.arange(S)

    enc_out = None
    if cfg.enc_dec:
        enc_out = _encode(params, cfg, rc, rules, batch["frames"].astype(dtype))
    if cfg.cross_attn_every:
        enc_out = batch["patches"].astype(dtype)

    aux_acc = {"moe_aux": jnp.zeros((), jnp.float32)}

    if cfg.rwkv:
        def body(x, p):
            x = _constrain(x, rules, ("batch", "seq", None))
            x, _, cache = _rwkv_block_seq(cfg, rc, rules, p, x)
            return x, (cache if want_cache else 0)
        x, caches = jax.lax.scan(_remat(body, rc.remat_policy), x,
                                 params["blocks"])
    elif cfg.cross_attn_every:
        def self_body(x, p):
            x = _constrain(x, rules, ("batch", "seq", None))
            x, _, cache = _mixer_block_seq(cfg, rc, rules, p, x, positions,
                                           None)
            return x, (cache if want_cache else 0)

        def group_body(carry, ps):
            x, aux = carry
            p_self, p_cross = ps
            x, self_caches = jax.lax.scan(
                _remat(self_body, rc.remat_policy), x, p_self)
            x = _constrain(x, rules, ("batch", "seq", None))
            x, a, ccache = _mixer_block_seq(cfg, rc, rules, p_cross, x,
                                            positions, enc_out)
            aux = aux + a.get("moe_aux", 0.0)
            return (x, aux), ({"self": self_caches, "cross": ccache}
                              if want_cache else 0)

        (x, moe_aux), caches = jax.lax.scan(
            _remat(group_body, rc.remat_policy), (x, jnp.zeros((), jnp.float32)),
            (params["self_blocks"], params["cross_blocks"]))
        aux_acc["moe_aux"] = moe_aux
    else:
        def body(carry, p):
            x, aux = carry
            x = _constrain(x, rules, ("batch", "seq", None))
            x, a, cache = _mixer_block_seq(cfg, rc, rules, p, x, positions,
                                           enc_out)
            aux = aux + a.get("moe_aux", 0.0)
            # per-layer counters of a dropless expert layer (none else)
            stats = {k: v for k, v in a.items() if k != "moe_aux"}
            return (x, aux), (cache if want_cache else 0, stats)

        carry = (x, jnp.zeros((), jnp.float32))
        if "dense_blocks" in params:
            carry, _ = jax.lax.scan(_remat(body, rc.remat_policy), carry,
                                    params["dense_blocks"])
        (x, moe_aux), (caches, stats) = jax.lax.scan(
            _remat(body, rc.remat_policy), carry, params["blocks"])
        aux_acc["moe_aux"] = moe_aux
        if stats:
            # assignments to held experts summed over the expert layers;
            # the fullest held expert's load, worst layer
            aux_acc["moe_tokens_held"] = stats["tokens_held"].sum()
            aux_acc["moe_max_load"] = stats["max_load"].max()

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, aux_acc, (caches if want_cache else None)


def forward_loss(params, cfg, rc, rules, batch):
    """Next-token cross entropy (sequence-chunked; no (B,S,V) tensor)."""
    x, aux, _ = forward(params, cfg, rc, rules, batch)
    head = L.head_matrix(params["embed"])
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["labels"].shape, jnp.float32)
    tot, cnt = L.chunked_softmax_xent(x, head, batch["labels"], mask,
                                      rc.loss_chunk,
                                      valid_vocab=cfg.vocab_size)
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.moe is not None:
        loss = loss + moe_mod.objective(cfg, aux["moe_aux"])
    return loss, {"xent": tot / jnp.maximum(cnt, 1.0), **aux}


# ==========================================================================
# Decode state + single-token decode
# ==========================================================================


def _kv_capacity(cfg: ModelConfig, seq_len: int) -> int:
    return min(cfg.sliding_window, seq_len) if cfg.sliding_window else seq_len


def no_mla_serving(cfg: ModelConfig) -> None:
    """Prefill and decode through a latent (MLA) cache are not built."""
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{cfg.arch_id}: serving (prefill / decode) is not built for "
            "multi-head latent attention; this family trains only")


def init_decode_state(cfg: ModelConfig, shape: ShapeConfig, rc: RunConfig):
    """Zero-initialized decode caches for a (arch, shape) cell.

    Layout is (L, B, ...) — layer-stacked for the decode layer scan.
    """
    no_mla_serving(cfg)
    B = shape.global_batch
    T = _kv_capacity(cfg, shape.seq_len)
    dt = jnp.dtype(rc.dtype)
    Lh = cfg.n_layers
    Kp = cfg.n_kv_heads_padded
    state: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    layers: Dict[str, Any] = {}
    if cfg.rwkv:
        layers["la"] = jnp.zeros((Lh, B, cfg.n_heads_padded, cfg.head_dim,
                                  cfg.head_dim), jnp.float32)
        layers["shift_a"] = jnp.zeros((Lh, B, cfg.d_model), dt)
        layers["shift_c"] = jnp.zeros((Lh, B, cfg.d_model), dt)
    else:
        kv_shape = (Lh, B, T, Kp, cfg.head_dim)
        layers["k"] = jnp.zeros(kv_shape, dt)
        layers["v"] = jnp.zeros(kv_shape, dt)
        if cfg.ssm_state:
            d_in = cfg.ssm_expand * cfg.d_model
            nh = mam.mamba_heads(d_in)
            layers["ssm"] = jnp.zeros(
                (Lh, B, nh, cfg.ssm_state, d_in // nh), jnp.float32)
            layers["conv"] = jnp.zeros((Lh, B, mam.CONV_W - 1, d_in), dt)
        if cfg.enc_dec:
            xkv = (Lh, B, cfg.enc_positions, Kp, cfg.head_dim)
            layers["xk"] = jnp.zeros(xkv, dt)
            layers["xv"] = jnp.zeros(xkv, dt)
    if cfg.cross_attn_every:
        per = cfg.cross_attn_every
        G = cfg.n_layers // per
        kv_shape = (G, per - 1, B, T, Kp, cfg.head_dim)
        layers = {"k": jnp.zeros(kv_shape, dt), "v": jnp.zeros(kv_shape, dt)}
        ckv = (G, B, cfg.vision_tokens, Kp, cfg.head_dim)
        layers["xk"] = jnp.zeros(ckv, dt)
        layers["xv"] = jnp.zeros(ckv, dt)
    state["layers"] = layers
    return state


def decode_state_logical(cfg: ModelConfig):
    """Logical axes for the decode state (for shardings/checkpoint)."""
    lay: Dict[str, Any] = {}
    if cfg.rwkv:
        lay = {"la": (None, "batch", "heads", None, None),
               "shift_a": (None, "batch", None),
               "shift_c": (None, "batch", None)}
    else:
        kv = (None, "batch", "cache_time", "kv_heads", None)
        lay = {"k": kv, "v": kv}
        if cfg.ssm_state:
            lay["ssm"] = (None, "batch", "heads", None, None)
            lay["conv"] = (None, "batch", None, "d_inner")
        if cfg.enc_dec:
            lay["xk"] = kv
            lay["xv"] = kv
    if cfg.cross_attn_every:
        kv6 = (None, None, "batch", "cache_time", "kv_heads", None)
        lay = {"k": kv6, "v": kv6,
               "xk": (None, "batch", None, "kv_heads", None),
               "xv": (None, "batch", None, "kv_heads", None)}
    return {"pos": (), "layers": lay}


def _decode_mixer_block(cfg, rc, rules, p, x, lcache, pos):
    """One block, one token. lcache: this layer's cache slice."""
    new_cache = dict(lcache)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q, k, v = attn.qkv_proj(p["attn"], h, cfg.rope_theta, positions)
    kc, vc = attn.cache_write(lcache["k"], lcache["v"], k, v, pos,
                              cfg.sliding_window)
    o = attn.decode_attention(q, kc, vc, pos, cfg.sliding_window)
    o = o * attn.head_mask(cfg)[None, None, :, None].astype(o.dtype)
    a_out = attn.out_proj(p["attn"], o)
    new_cache["k"], new_cache["v"] = kc, vc
    if cfg.ssm_state:
        m_out, conv, ssm = mam.mamba_decode_step(
            p["mamba"], h, lcache["conv"], lcache["ssm"])
        a_out = (a_out + m_out) * 0.5
        new_cache["conv"], new_cache["ssm"] = conv, ssm
    x = x + a_out
    if "xattn" in p and "xk" in lcache:
        hx = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        dt = hx.dtype
        qx = jnp.einsum("bsd,dhk->bshk", hx, p["xattn"]["wq"].astype(dt))
        Te = lcache["xk"].shape[1]
        ox = attn.decode_attention(qx, lcache["xk"], lcache["xv"], Te - 1)
        ox = ox * attn.head_mask(cfg)[None, None, :, None].astype(ox.dtype)
        x = x + attn.out_proj(p["xattn"], ox)
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, _ = moe_mod.apply_layer(p["moe"], h2, cfg, moe_split(cfg), rules)
    else:
        y = L.mlp_apply(p["mlp"], h2)
    return x + y, new_cache


def _decode_rwkv_block(cfg, rc, p, x, lcache):
    new_cache = dict(lcache)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    tm_out, la, sa = rwkv_mod.rwkv_time_mix_step(
        p["tm"], h, lcache["la"], lcache["shift_a"].astype(h.dtype),
        mask=attn.head_mask(cfg))
    x = x + tm_out
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    cm_out, sc = rwkv_mod.rwkv_channel_mix_step(
        p["cm"], h2, lcache["shift_c"].astype(h.dtype))
    x = x + cm_out
    new_cache.update(la=la, shift_a=sa.astype(lcache["shift_a"].dtype),
                     shift_c=sc.astype(lcache["shift_c"].dtype))
    return x, new_cache


def decode_step(params, cfg: ModelConfig, rc: RunConfig, rules, state, token):
    """One decode step. token: (B,1) int32 -> (logits (B,1,V), new state)."""
    no_mla_serving(cfg)
    dtype = jnp.dtype(rc.dtype)
    x = L.embed_apply(params["embed"], token, dtype)
    pos = state["pos"]
    layers = state["layers"]

    if cfg.rwkv:
        def body(x, xs):
            p, lc = xs
            return _decode_rwkv_block(cfg, rc, p, x, lc)
        x, new_layers = jax.lax.scan(body, x, (params["blocks"], layers))
    elif cfg.cross_attn_every:
        def self_body(x, xs):
            p, lc = xs
            return _decode_mixer_block(cfg, rc, rules, p, x, lc, pos)

        def group_body(x, xs):
            p_self, p_cross, lc = xs
            x, kv_new = jax.lax.scan(
                self_body, x, (p_self, {"k": lc["k"], "v": lc["v"]}))
            # cross layer: self-attn uses no cache here (treat as pure cross)
            hx = L.rms_norm(x, p_cross["lnx"], cfg.norm_eps)
            qx = jnp.einsum("bsd,dhk->bshk", hx,
                            p_cross["xattn"]["wq"].astype(x.dtype))
            Tv = lc["xk"].shape[1]
            ox = attn.decode_attention(qx, lc["xk"], lc["xv"], Tv - 1)
            ox = ox * attn.head_mask(cfg)[None, None, :, None].astype(ox.dtype)
            x = x + attn.out_proj(p_cross["xattn"], ox)
            h2 = L.rms_norm(x, p_cross["ln2"], cfg.norm_eps)
            x = x + L.mlp_apply(p_cross["mlp"], h2)
            return x, {"k": kv_new["k"], "v": kv_new["v"],
                       "xk": lc["xk"], "xv": lc["xv"]}

        x, new_layers = jax.lax.scan(
            group_body, x,
            (params["self_blocks"], params["cross_blocks"], layers))
    else:
        def body(x, xs):
            p, lc = xs
            return _decode_mixer_block(cfg, rc, rules, p, x, lc, pos)
        x, new_layers = jax.lax.scan(body, x, (params["blocks"], layers))

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = L.head_matrix(params["embed"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dtype))
    vmask = L.vocab_logit_mask(head.shape[-1], cfg.vocab_size)
    if vmask is not None:
        logits = logits + vmask.astype(logits.dtype)
    new_state = {"pos": pos + 1, "layers": new_layers}
    return logits, new_state


# ==========================================================================
# Prefill: full forward that also emits decode caches
# ==========================================================================


def prefill(params, cfg: ModelConfig, rc: RunConfig, rules, batch):
    """Process a full prompt; return (last-token logits, decode state)."""
    no_mla_serving(cfg)
    x, _, caches = forward(params, cfg, rc, rules, batch, want_cache=True)
    head = L.head_matrix(params["embed"])
    logits = jnp.einsum("bd,dv->bv", x[:, -1], head.astype(x.dtype))
    vmask = L.vocab_logit_mask(head.shape[-1], cfg.vocab_size)
    if vmask is not None:
        logits = logits + vmask.astype(logits.dtype)
    S = batch["tokens"].shape[1]

    layers: Dict[str, Any] = {}
    if cfg.rwkv:
        layers = {"la": caches["la"], "shift_a": caches["shift_a"],
                  "shift_c": caches["shift_c"]}
    elif cfg.cross_attn_every:
        layers = {"k": caches["self"]["k"], "v": caches["self"]["v"],
                  "xk": caches["cross"]["xk"], "xv": caches["cross"]["xv"]}
    else:
        layers = {"k": caches["k"], "v": caches["v"]}
        if cfg.ssm_state:
            layers["ssm"] = caches["ssm"]
            layers["conv"] = caches["conv"]
        if cfg.enc_dec:
            layers["xk"] = caches["xk"]
            layers["xv"] = caches["xv"]
    if not cfg.rwkv and not cfg.sliding_window:
        # full-attention KV caches need headroom for subsequent decodes
        # (SWA ring buffers wrap; rwkv/ssm state is fixed-size).  The
        # time axis is always ndim-3 in the (..., B, T, K, hd) layouts.
        for key in ("k", "v"):
            if key in layers:
                nd = layers[key].ndim
                pad = [(0, 0)] * nd
                pad[nd - 3] = (0, rc.decode_margin)
                layers[key] = jnp.pad(layers[key], pad)
    if cfg.sliding_window:
        assert S % min(cfg.sliding_window, S) == 0, (
            "prefill length must be a multiple of the SWA window so ring "
            "slots align (slot = pos % window)")
    state = {"pos": jnp.asarray(S, jnp.int32), "layers": layers}
    return logits, state
