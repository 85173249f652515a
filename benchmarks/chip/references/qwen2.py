"""Plain reference of a Qwen2 decoder (arXiv:2407.10671) for training.

Straightforward jax.numpy in float32 with every matmul at
`Precision.HIGHEST`: RMSNorm, rotary embeddings (rotate-half), grouped
query attention with q/k/v biases and a causal softmax, a SwiGLU MLP and
a tied LM head, mean next-token cross entropy.  It imports nothing of
the program under test.

Departures, each because of the program's stored layout, not its maths:

* Query heads are held padded to a multiple of `layout.head_pad_to`
  (the stored parameter layout); the dummy heads' outputs are
  multiplied by zero, so they change nothing but their own weight decay.
* Layers are stacked on a leading axis and run by `lax.scan`; attention
  runs one batch row at a time and the loss a block of tokens at a
  time, each rematerialised, so that a full-width step fits on one chip.

`quant="fp8"` is the precision control: every matmul operand is first
rounded to float8_e4m3fn with a per-tensor scale (straight-through
gradient), the step below the bfloat16 compute the configuration states.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LOSS_BLOCK = 1024          # tokens per block of the loss
F8_MAX = 448.0             # largest finite float8_e4m3fn


def layout(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    g, pad = h // k, cfg["layout"]["head_pad_to"]
    kp, gp = min(((kp * gp, kp != k, kp, gp)
                  for kp in range(k, 4 * k + 1) for gp in range(g, 4 * g + 1)
                  if (kp * gp) % pad == 0))[2:]
    return {"d": d, "hd": d // h, "k": k, "g": g, "kp": kp, "gp": gp,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "l": cfg["num_hidden_layers"]}


def param_shapes(cfg: Dict) -> Dict:
    """The stored parameter tree: path -> shape (layers stacked first)."""
    x = layout(cfg)
    L, d, hd, kp, f = x["l"], x["d"], x["hd"], x["kp"], x["f"]
    hp = kp * x["gp"]
    attn = {"wq": (L, d, hp, hd), "wk": (L, d, kp, hd), "wv": (L, d, kp, hd),
            "wo": (L, hp, hd, d)}
    if cfg["layout"]["qkv_bias"]:
        attn.update(bq=(L, hp, hd), bk=(L, kp, hd), bv=(L, kp, hd))
    embed = {"embedding": (x["v"], d)}
    if not cfg["tie_word_embeddings"]:
        embed["head"] = (d, x["v"])
    return {"embed": embed, "ln_f": (d,),
            "blocks": {"ln1": (L, d), "ln2": (L, d), "attn": attn,
                       "mlp": {"wi": (L, d, f), "wg": (L, d, f),
                               "wo": (L, f, d)}}}


def init_params(cfg: Dict, seed) -> Dict:
    """Random weights from a seed (jittable): matrices and biases
    N(0, initializer_range), norm scales 1 + N(0, initializer_range)."""
    std = cfg["initializer_range"]
    shapes = param_shapes(cfg)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    key = jax.random.key(seed)
    out = []
    for i, (path, shape) in enumerate(zip(paths, flat)):
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out.append(1.0 + w if "ln" in path else w)
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


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half rotary embedding by position."""
    S, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_row(p, h, cfg, x, quant):
    """One sequence: h (S, d) -> attention output (S, d)."""
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, p["wq"], quant)
    k = _mm("sd,dhk->shk", h, p["wk"], quant)
    v = _mm("sd,dhk->shk", h, p["wv"], quant)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q, cfg["rope_theta"]).reshape(S, x["kp"], x["gp"], x["hd"])
    k = _rope(k, cfg["rope_theta"])
    s = _mm("skgh,tkh->kgst", q, k, quant) / math.sqrt(x["hd"])
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("kgst,tkh->skgh", jax.nn.softmax(s, axis=-1), v, quant)
    real = (jnp.arange(x["kp"])[:, None] < x["k"]) & \
        (jnp.arange(x["gp"])[None, :] < x["g"])
    o = (o * real[None, :, :, None]).reshape(S, -1, x["hd"])
    return _mm("shk,hkd->sd", o, p["wo"], quant)


def _block(cfg, x, quant):
    eps = cfg["rms_norm_eps"]

    def body(h, p):
        a = jax.lax.map(
            jax.checkpoint(lambda r: _attention_row(p["attn"], r, cfg, x,
                                                    quant)),
            _rms(h, p["ln1"], eps))
        h = h + a
        m = _rms(h, p["ln2"], eps)
        y = jax.nn.silu(_mm("bsd,df->bsf", m, p["mlp"]["wg"], quant)) * \
            _mm("bsd,df->bsf", m, p["mlp"]["wi"], quant)
        return h + _mm("bsf,fd->bsd", y, p["mlp"]["wo"], quant), None

    return jax.checkpoint(body)


def loss(params: Dict, tokens, labels, cfg: Dict,
         quant: Optional[str] = None):
    """Mean next-token cross entropy over every position."""
    x = layout(cfg)
    h = params["embed"]["embedding"][tokens]                    # (B, S, d)
    h, _ = jax.lax.scan(_block(cfg, x, quant), h, params["blocks"])
    h = _rms(h, params["ln_f"], cfg["rms_norm_eps"])
    head = params["embed"].get("head")
    if head is None:
        head = params["embed"]["embedding"].T
    n = h.shape[0] * h.shape[1]
    blk = math.gcd(n, LOSS_BLOCK)
    hb = h.reshape(n // blk, blk, -1)
    lb = labels.reshape(n // blk, blk)

    def block_loss(args):
        hx, lx = args
        logits = _mm("td,dv->tv", hx, head, quant)
        tgt = jnp.take_along_axis(logits, lx[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt)

    return jnp.sum(jax.lax.map(jax.checkpoint(block_loss), (hb, lb))) / n
