"""Operation and byte counts, from a configuration file's published shapes.

These are the yardstick's own arithmetic: nothing here reads the program.

* `train_flops_per_token`: forward + backward matmul FLOPs of one token
  (6 x the matmul parameters, the tied LM head included), plus causal
  attention at half the square.  Rematerialised work and the padded
  heads the program stores do not count.
* `state_bytes`: the f32 params + AdamW moments + the two int32 scalars
  the program holds, in its stored layout (query heads padded so that
  they tile by `layout.head_pad_to`).
"""
from __future__ import annotations

from typing import Dict, Tuple


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "k": cfg["num_key_value_heads"], "hd": d // h,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "l": cfg["num_hidden_layers"]}


def padded_heads(cfg: Dict) -> Tuple[int, int]:
    """(kv heads, query groups) as stored: the smallest grid, kv heads
    unpadded where possible, whose head count is a multiple of
    `layout.head_pad_to`; extra query groups hold masked dummy heads."""
    x = dims(cfg)
    k, g, pad = x["k"], x["h"] // x["k"], cfg["layout"]["head_pad_to"]
    best = min((kp * gp, kp != k, kp, gp)
               for kp in range(k, 4 * k + 1) for gp in range(g, 4 * g + 1)
               if (kp * gp) % pad == 0)
    return best[2], best[3]


def matmul_params(cfg: Dict) -> int:
    """Parameters that enter a matmul once per token, at published sizes."""
    x = dims(cfg)
    d, hd = x["d"], x["hd"]
    per_layer = (2 * d * x["h"] * hd          # q and o projections
                 + 2 * d * x["k"] * hd        # k and v projections
                 + 3 * d * x["f"])            # gated MLP
    return x["l"] * per_layer + x["v"] * d    # + LM head


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    x = dims(cfg)
    # QK^T and PV, 2 FLOPs per multiply-add each, over half the square
    attn_fwd = x["l"] * 2 * 2 * (seq_len / 2) * x["h"] * x["hd"]
    return 3.0 * (2.0 * matmul_params(cfg) + attn_fwd)


def stored_param_count(cfg: Dict) -> int:
    x = dims(cfg)
    d, hd = x["d"], x["hd"]
    kp, gp = padded_heads(cfg)
    hp = kp * gp
    attn = d * hp * hd + 2 * d * kp * hd + hp * hd * d
    if cfg["layout"]["qkv_bias"]:
        attn += hp * hd + 2 * kp * hd
    per_layer = attn + 3 * d * x["f"] + 2 * d      # + two RMSNorm scales
    embed = x["v"] * d * (1 if cfg["tie_word_embeddings"] else 2)
    return x["l"] * per_layer + embed + d          # + final norm


def state_bytes(cfg: Dict) -> int:
    """Params, AdamW m and v (f32 each), step and count (int32)."""
    return 3 * 4 * stored_param_count(cfg) + 2 * 4
