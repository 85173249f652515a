"""The DeepSeek-V3 family (arXiv:2412.19437; Moonlight-16B-A3B is its
block at 2,048 wide): its plain training reference as one chip's share
of an expert-parallel layer, the program's model settings for it, and
its operation counts.

Straightforward jax.numpy in float32 with every matmul at
`Precision.HIGHEST`, following transformers' `modeling_deepseek_v3.py`:

* RMSNorm before attention and before the MLP, and on MLA's compressed
  kv (epsilon 1e-6 there, RMSNorm's default in that code);
* multi-head latent attention without a query LoRA: q = x Wq split into
  a no-position part and a rotary part; [c, k_rope] = x Wkv_a;
  [k_nope, v] = RMSNorm(c) Wkv_b; one rotary key for all heads; RoPE on
  adjacent pairs (`rope_interleave`); softmax scale (nope + rope)^-1/2;
  a causal softmax;
* the first `first_k_dense_replace` layers have a SwiGLU MLP, the rest an
  expert layer: router logits in float32, sigmoid scores, the top-k of
  them (the selection bias is zero), renormalised and scaled by
  `routed_scaling_factor`; each held expert computed densely over every
  token and weighted by its gate (zero where the token did not choose
  it); the shared experts as one MLP `n_shared_experts` times an
  expert's width;
* an untied LM head and mean next-token cross entropy, plus
  DeepSeek-V3's sequence-wise balance loss (2.1.2) times
  `balance_loss_alpha`, summed over the expert layers and averaged over
  the sequences.

The expert share: the router keeps its published width
(`layout.router_experts`) and top-k; the layer holds `n_routed_experts`
of them from `layout.first_held_expert`, and computes only their part
of the routed output, as the program does.  What the absent experts
would add is left out of both.  The vocabulary is the configuration's
slice: the traffic draws ids from it and the loss is over it.

Departures, each because of the program's stored layout or a chip's
memory, not its maths: layers are stacked on a leading axis (dense and
expert layers apart) and run by `lax.scan`; attention runs one batch
row and a block of queries at a time, every MLP, the expert layer and
the loss a block of tokens at a time, each rematerialised.

`quant="fp8"` is the precision control: every matmul operand is first
rounded to float8_e4m3fn with a per-tensor scale (straight-through
gradient), the step below the bfloat16 compute the configuration states.
It imports nothing of the program under test.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
TOKEN_BLOCK = 4096         # tokens per block of the MLPs and the loss
QUERY_BLOCK = 1024         # queries per block of attention
KV_NORM_EPS = 1e-6         # the compressed kv's RMSNorm (its default)
F8_MAX = 448.0             # largest finite float8_e4m3fn


def layout(cfg: Dict) -> Dict[str, int]:
    n_dense = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "hv": cfg["v_head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "e": cfg["layout"]["router_experts"],
            "held": cfg["n_routed_experts"],
            "first": cfg["layout"]["first_held_expert"],
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"], "ld": n_dense,
            "le": cfg["num_hidden_layers"] - n_dense,
            "v": cfg["vocab_size"]}


def program_model(cfg: Dict) -> Dict:
    """The program's model settings (`repro.configs.base.ModelConfig`
    keywords, the expert layer's as a mapping) for a configuration, as
    plain values.  The program's dropless routing is DeepSeek-V3's
    (sigmoid scores, the top-k renormalised); another is refused."""
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError(f"{cfg['name']}: the program routes by sigmoid "
                         "scores with the top-k renormalised")
    x = layout(cfg)
    return {"family": "moe", "n_layers": x["ld"] + x["le"],
            "n_dense_layers": x["ld"], "d_model": x["d"], "n_heads": x["h"],
            "n_kv_heads": x["h"], "head_dim": x["nope"] + x["rope"],
            "d_ff": x["f"], "vocab_size": x["v"],
            "kv_lora_rank": x["r"], "qk_nope_dim": x["nope"],
            "qk_rope_dim": x["rope"], "v_head_dim": x["hv"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"], "pad_to": 1,
            "moe": {"num_experts": x["e"], "top_k": x["k"], "router": "deepseek_v3",
                    "d_expert": x["fe"], "n_shared": x["shared"],
                    "routed_scale": cfg["routed_scaling_factor"],
                    "held": x["held"], "first_held": x["first"],
                    "balance_alpha": cfg["balance_loss_alpha"]}}


def _attn_params(x) -> int:
    d, h = x["d"], x["h"]
    return (d * h * (x["nope"] + x["rope"]) + d * (x["r"] + x["rope"])
            + x["r"] * h * (x["nope"] + x["hv"]) + h * x["hv"] * d)


def matmul_params(cfg: Dict) -> float:
    """Parameters that enter a matmul once per token on this chip, at
    published widths: every layer's attention projections; the dense
    layers' MLP; each expert layer's router, shared experts and
    k x held / router_experts routed experts (a token's expected use of
    the held ones); the LM head over the vocabulary slice."""
    x = layout(cfg)
    d = x["d"]
    expert = 3 * d * x["fe"]
    dense = _attn_params(x) + 3 * d * x["f"]
    moe = (_attn_params(x) + d * x["e"] + x["shared"] * expert
           + x["k"] * x["held"] / x["e"] * expert)
    return x["ld"] * dense + x["le"] * moe + x["v"] * d


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward matmul FLOPs of one token of this chip's
    share: 6 x `matmul_params`, plus causal attention at half the square
    (QK^T over the q/k head size and PV over the v head size, 2 FLOPs a
    multiply-add).  Rematerialised work does not count.

    moonlight-16b-a3b-l5 at 8,192: attention projections 13,762,560 a
    layer; dense layer + 3 x 2048 x 11264 = 82,968,576; an expert layer
    + 2048 x 64 (router) + 2 x 8,650,752 (shared) + 6 x 8 / 64 x
    8,650,752 (0.75 routed experts) = 37,683,200; head 2048 x 20480 =
    41,943,040; matmul total 275,644,416.  Attention 2 x 4096 x 16 x
    (192 + 128) = 41,943,040 a layer, 5 layers.  3 x (2 x 275,644,416 +
    209,715,200) = 2,283,012,096 FLOPs a token."""
    x = layout(cfg)
    attn_fwd = (x["ld"] + x["le"]) * 2 * (seq_len / 2) * x["h"] * (
        x["nope"] + x["rope"] + x["hv"])
    return 3.0 * (2.0 * matmul_params(cfg) + attn_fwd)


def param_shapes(cfg: Dict) -> Dict:
    """The stored parameter tree: path -> shape (layers stacked first,
    the leading dense layers apart from the expert layers)."""
    x = layout(cfg)
    d, h = x["d"], x["h"]

    def block(n):
        return {"ln1": (n, d), "ln2": (n, d),
                "attn": {"wq": (n, d, h, x["nope"] + x["rope"]),
                         "wkv_a": (n, d, x["r"] + x["rope"]),
                         "kv_norm": (n, x["r"]),
                         "wkv_b": (n, x["r"], h, x["nope"] + x["hv"]),
                         "wo": (n, h, x["hv"], d)}}

    def mlp(n, f):
        return {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)}

    le, fe = x["le"], x["fe"]
    moe = {"router": (le, d, x["e"]), "wi": (le, x["held"], d, fe),
           "wg": (le, x["held"], d, fe), "wo": (le, x["held"], fe, d)}
    if x["shared"]:
        moe["shared"] = mlp(le, x["shared"] * fe)
    out = {"embed": {"embedding": (x["v"], d), "head": (d, x["v"])},
           "ln_f": (d,), "blocks": dict(block(le), moe=moe)}
    if x["ld"]:
        out["dense_blocks"] = dict(block(x["ld"]), mlp=mlp(x["ld"], x["f"]))
    return out


def init_params(cfg: Dict, seed) -> Dict:
    """Random weights from a seed (jittable): matrices
    N(0, initializer_range), norm scales 1 + N(0, initializer_range)."""
    std = cfg["initializer_range"]
    shapes = param_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    tree = jax.tree.structure(shapes,
                              is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.key(seed)
    out = []
    for i, (path, shape) in enumerate(leaves):
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        name = jax.tree_util.keystr(path)
        out.append(1.0 + w if "ln" in name or "norm" in name else w)
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _q8(x):
    """Round to float8_e4m3fn with a per-tensor scale; identity gradient."""
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(y - x)


def _mm(eq, a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """x: (S, heads, r); each adjacent pair (2i, 2i+1) rotated by
    position x theta^(-2i/r); the rotated even members, then the odd."""
    S, r = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin], -1)


def _attention_row(p, hrow, cfg, x, quant):
    """One sequence: h (S, d) -> MLA output (S, d)."""
    S, nope, r = hrow.shape[0], x["nope"], x["r"]
    theta = float(cfg["rope_theta"])
    q = _mm("sd,dhk->shk", hrow, p["wq"], quant)
    kv_a = _mm("sd,dc->sc", hrow, p["wkv_a"], quant)
    c = _rms(kv_a[:, :r], p["kv_norm"], KV_NORM_EPS)
    kvb = _mm("sc,chk->shk", c, p["wkv_b"], quant)
    k_rope = _rope_pairs(kv_a[:, None, r:], theta)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_rope, (S, x["h"], x["rope"]))],
        -1)
    v = kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], theta)],
                        -1)
    blk = math.gcd(S, QUERY_BLOCK)
    scale = 1.0 / math.sqrt(nope + x["rope"])

    def block(args):
        qx, i = args
        s = _mm("qhk,thk->hqt", qx, k, quant) * scale
        causal = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(S)[None]
        s = jnp.where(causal, s, -jnp.inf)
        return _mm("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v, quant)

    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(S // blk, blk, x["h"], -1),
                     jnp.arange(S // blk)))
    return _mm("shk,hkd->sd", o.reshape(S, x["h"], x["hv"]), p["wo"], quant)


def _by_tokens(fn, m):
    """fn over blocks of tokens of m (B, S, d), each rematerialised."""
    B, S, d = m.shape
    n = B * S
    blk = math.gcd(n, TOKEN_BLOCK)
    out = jax.lax.map(jax.checkpoint(fn), m.reshape(n // blk, blk, d))
    return out.reshape(B, S, -1)


def _mlp(p, m, quant):
    """SwiGLU over a block of tokens m (t, d); p's leaves (d, f), (f, d)."""
    g = jax.nn.silu(_mm("td,df->tf", m, p["wg"], quant))
    return _mm("tf,fd->td", g * _mm("td,df->tf", m, p["wi"], quant),
               p["wo"], quant)


def _expert_layer(p, m, x, cfg, quant):
    """m (B, S, d) -> (routed part of the held experts + shared experts,
    the sequence-wise balance loss)."""
    e, k = x["e"], x["k"]
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", m, p["router"], quant))
    top = jax.lax.top_k(scores, k)[1]
    chosen = jax.nn.one_hot(top, e).sum(-2)                    # (B, S, e)
    w = scores * chosen
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    gates = w[..., x["first"]:x["first"] + x["held"]]          # held ones
    f = chosen.sum(1) * (e / (k * m.shape[1]))
    share = (scores / scores.sum(-1, keepdims=True)).mean(1)
    balance = jnp.mean(jnp.sum(f * share, -1))

    def block(args):
        mt, gt = args
        h = jax.nn.silu(_mm("td,jdf->jtf", mt, p["wg"], quant)) * \
            _mm("td,jdf->jtf", mt, p["wi"], quant)
        y = _mm("jtd,tj->td", _mm("jtf,jfd->jtd", h, p["wo"], quant), gt,
                quant)
        if "shared" in p:
            y = y + _mlp(p["shared"], mt, quant)
        return y

    B, S, d = m.shape
    n = B * S
    blk = math.gcd(n, TOKEN_BLOCK)
    y = jax.lax.map(jax.checkpoint(block),
                    (m.reshape(n // blk, blk, d),
                     gates.reshape(n // blk, blk, x["held"])))
    return y.reshape(B, S, d), balance


def _layers(cfg, x, quant, expert: bool):
    eps = cfg["rms_norm_eps"]

    def body(h, p):
        a = jax.lax.map(
            jax.checkpoint(lambda r: _attention_row(p["attn"], r, cfg, x,
                                                    quant)),
            _rms(h, p["ln1"], eps))
        h = h + a
        m = _rms(h, p["ln2"], eps)
        if expert:
            y, balance = _expert_layer(p["moe"], m, x, cfg, quant)
        else:
            y = _by_tokens(lambda t: _mlp(p["mlp"], t, quant), m)
            balance = jnp.zeros(())
        return h + y, balance

    return jax.checkpoint(body)


def hidden(params: Dict, tokens, cfg: Dict, quant: Optional[str] = None):
    """Final hidden states (B, S, d) after `ln_f`, and the balance loss
    of each expert layer."""
    x = layout(cfg)
    h = params["embed"]["embedding"][tokens]                    # (B, S, d)
    if "dense_blocks" in params:
        h, _ = jax.lax.scan(_layers(cfg, x, quant, False), h,
                            params["dense_blocks"])
    h, balance = jax.lax.scan(_layers(cfg, x, quant, True), h,
                              params["blocks"])
    return _rms(h, params["ln_f"], cfg["rms_norm_eps"]), balance


def logits(params: Dict, tokens, cfg: Dict) -> jnp.ndarray:
    """(B, S, vocab) next-token logits, float32 (small sizes only)."""
    h, _ = hidden(params, tokens, cfg)
    return _mm("bsd,dv->bsv", h, params["embed"]["head"], None)


def loss(params: Dict, tokens, labels, cfg: Dict,
         quant: Optional[str] = None):
    """Mean next-token cross entropy over every position, plus the
    balance loss times `balance_loss_alpha`, summed over the expert
    layers."""
    h, balance = hidden(params, tokens, cfg, quant)
    head = params["embed"]["head"]
    n = h.shape[0] * h.shape[1]
    blk = math.gcd(n, TOKEN_BLOCK)
    hb = h.reshape(n // blk, blk, -1)
    lb = labels.reshape(n // blk, blk)

    def block_loss(args):
        hx, lx = args
        z = _mm("td,dv->tv", hx, head, quant)
        tgt = jnp.take_along_axis(z, lx[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, -1) - tgt)

    xent = jnp.sum(jax.lax.map(jax.checkpoint(block_loss), (hb, lb))) / n
    return xent + cfg["balance_loss_alpha"] * jnp.sum(balance)
