"""Mixture-of-Experts: top-k routing with GShard-style dispatch einsums,
and dropless routing over an expert-parallel share (`dropless_moe_apply`).

Expert parallelism under a fixed (data, model) mesh: expert weights are
stored in a *virtual-expert* layout — each real expert's gated-MLP is
split column-wise into `split` virtual experts (SwiGLU decomposes exactly:
out = sum_h (silu(x Wg_h) * (x Wi_h)) Wo_h) so that E_virtual = E * split
divides the model-axis size (mixtral: 8e x split 2 = 16; phi-3.5: 16e x 1).
A token routed to real expert e is dispatched to all of e's virtual
experts with the same gate weight.

Sharding: activations are batch-sharded and model-replicated, so the
dispatch one-hots and per-expert buffers shard over ("batch", "expert")
with *local* dispatch contraction; the only collective is the all-reduce
of the combined output over the model axis (same pattern as TP attention).
moe_mode="tp" instead shards the ffn dim (Megatron-style) — §Perf
comparison point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, init_mlp, mlp_apply


def init_moe(key, d_model: int, d_ff: int, num_experts: int, split: int):
    ev = num_experts * split
    fv = d_ff // split
    ks = jax.random.split(key, 4)
    params = {
        "router": _dense_init(ks[0], (d_model, num_experts)),
        "wi": _dense_init(ks[1], (ev, d_model, fv), in_axis=1),
        "wg": _dense_init(ks[2], (ev, d_model, fv), in_axis=1),
        "wo": _dense_init(ks[3], (ev, fv, d_model), in_axis=1),
    }
    logical = {
        "router": (None, None),
        "wi": ("expert", None, "expert_ffn"),
        "wg": ("expert", None, "expert_ffn"),
        "wo": ("expert", "expert_ffn", None),
    }
    return params, logical


def _topk_by_argmax(logits, k: int):
    """(..., E) -> (vals (..., k), idx (..., k)); descending, stable."""
    vals, idxs = [], []
    cur = logits
    for _ in range(k):
        i = jnp.argmax(cur, axis=-1)
        v = jnp.max(cur, axis=-1)
        vals.append(v)
        idxs.append(i)
        sel = jax.nn.one_hot(i, logits.shape[-1], dtype=jnp.float32) > 0
        cur = jnp.where(sel, -jnp.inf, cur)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def moe_apply(p, x, *, num_experts: int, top_k: int, split: int,
              capacity_factor: float, rules=None, group_size: int = 512):
    """x: (B,S,d) -> (B,S,d), aux-loss dict."""
    B, S, d = x.shape
    ev = num_experts * split
    kv = top_k * split  # virtual choices per token
    N = B * S

    # ---- routing over *real* experts --------------------------------------
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    # iterated-argmax top-k: jax.lax.top_k lowers to a sort that GSPMD
    # replicates (observed: per-layer all-gather of the full router
    # logits); argmax+mask partitions cleanly over batch.
    gate_vals, gate_idx = _topk_by_argmax(logits, top_k)        # (B,S,k)
    gate_w = jax.nn.softmax(gate_vals, axis=-1)                 # renormalized
    # Switch-style load-balance aux loss
    probs = jax.nn.softmax(logits, axis=-1)
    sel_real = jax.nn.one_hot(gate_idx, num_experts,
                              dtype=jnp.float32).sum(axis=2)    # (B,S,E)
    aux_loss = num_experts * jnp.sum(
        probs.mean(axis=(0, 1)) * sel_real.mean(axis=(0, 1)) / top_k)

    # ---- virtual-expert selection and gates, per token ---------------------
    v_idx = gate_idx[..., None] * split + jnp.arange(split)     # (B,S,k,split)
    v_oh = jax.nn.one_hot(v_idx.reshape(B, S, kv), ev, dtype=jnp.float32)
    sel = v_oh.sum(axis=2)                                      # (B,S,Ev) 0/1
    gates = jnp.einsum("bske,bsk->bse", v_oh,
                       jnp.repeat(gate_w, split, axis=-1))      # (B,S,Ev)

    # ---- group tokens, assign capacity positions ---------------------------
    T = min(group_size, N)
    G = N // T
    assert N % T == 0, (N, T)
    sel = sel.reshape(G, T, ev)
    gates = gates.reshape(G, T, ev)
    cap = int(capacity_factor * kv * T / ev)
    cap = max(4, ((cap + 3) // 4) * 4)
    pos = jnp.cumsum(sel, axis=1) - sel                         # exclusive
    keep = sel * (pos < cap).astype(sel.dtype)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype)
    disp = pos_oh * keep[..., None].astype(x.dtype)             # (G,T,Ev,C)
    combine = disp * gates[..., None].astype(x.dtype)
    if rules is not None:
        from jax.lax import with_sharding_constraint as wsc
        disp = wsc(disp, rules.named(("batch", None, "expert", None)))
        combine = wsc(combine, rules.named(("batch", None, "expert", None)))

    # ---- dispatch -> expert MLP -> combine ----------------------------------
    xg = x.reshape(G, T, d)
    xin = jnp.einsum("gtec,gtd->gecd", disp, xg)                # local per shard
    if rules is not None:
        # pin the per-expert buffers to the expert (model) shards: without
        # this, small-token cells (decode) tempt GSPMD into all-gathering
        # the expert WEIGHTS per layer instead (observed: ~78 GB/step)
        from jax.lax import with_sharding_constraint as wsc
        xin = wsc(xin, rules.named(("batch", "expert", None, None)))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["wg"].astype(x.dtype)))
    u = jnp.einsum("gecd,edf->gecf", xin, p["wi"].astype(x.dtype))
    yout = jnp.einsum("gecf,efd->gecd", h * u, p["wo"].astype(x.dtype))
    if rules is not None:
        from jax.lax import with_sharding_constraint as wsc
        yout = wsc(yout, rules.named(("batch", "expert", None, None)))
    y = jnp.einsum("gtec,gecd->gtd", combine, yout)             # all-reduce(model)
    return y.reshape(B, S, d), {"moe_aux": aux_loss}


# ==========================================================================
# Dropless routing over the experts this chip holds (DeepSeek-V3 style)
# ==========================================================================
#
# The router keeps its full width and its top-k; the layer holds experts
# [first_held, first_held + held) of an expert-parallel layer and computes
# their part of the result for the tokens routed to them.  What the
# absent experts would add is not computed: on one chip there is no
# exchange, and nothing stands in for it.  Every assignment to a held
# expert is computed (no capacity, no dropped token): the token-expert
# assignments are sorted by expert and the held experts run as grouped
# matmuls over their contiguous rows (`jax.lax.ragged_dot`).


def init_dropless_moe(key, d_model: int, d_expert: int, num_experts: int,
                      held: int, n_shared: int):
    ks = jax.random.split(key, 5)
    params = {
        "router": _dense_init(ks[0], (d_model, num_experts)),
        "wi": _dense_init(ks[1], (held, d_model, d_expert), in_axis=1),
        "wg": _dense_init(ks[2], (held, d_model, d_expert), in_axis=1),
        "wo": _dense_init(ks[3], (held, d_expert, d_model), in_axis=1),
    }
    logical = {
        "router": (None, None),
        "wi": ("expert_held", None, None),
        "wg": ("expert_held", None, None),
        "wo": ("expert_held", None, None),
    }
    if n_shared:
        # the shared experts as one MLP n_shared times an expert's width
        params["shared"], logical["shared"] = init_mlp(
            ks[4], d_model, n_shared * d_expert)
    return params, logical


def route(logits, moe):
    """f32 router logits (..., E) -> (weights (..., k), expert ids
    (..., k), scores (..., E)).  Scores are the sigmoid of the logits;
    the top-k of the scores are chosen (the selection bias held at
    zero), renormalised and scaled."""
    scores = jax.nn.sigmoid(logits)
    w, idx = _topk_by_argmax(scores, moe.top_k)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * moe.routed_scale, idx, scores


def balance_loss(scores, idx, num_experts: int, top_k: int):
    """DeepSeek-V3's sequence-wise balance loss (arXiv:2412.19437,
    2.1.2), averaged over the sequences: sum_i f_i P_i with
    f_i = E / (k T) * (tokens of the sequence that chose expert i) and
    P_i the mean over its T tokens of the scores normalised over the E
    experts.  scores: (B, T, E); idx: (B, T, k)."""
    T = scores.shape[1]
    p = (scores / scores.sum(axis=-1, keepdims=True)).mean(axis=1)
    chosen = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)
    f = chosen.sum(axis=(1, 2)) * (num_experts / (top_k * T))
    return jnp.mean(jnp.sum(f * p, axis=-1))


@jax.custom_vjp
def _permute(x, perm, inv):
    """x[perm] for a permutation of rows; the gradient is a gather by
    the inverse permutation, not a scatter."""
    return x[perm]


def _permute_fwd(x, perm, inv):
    return x[perm], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _dispatch(x, order, inv):
    """Row a of the result is x[order[a] // k], k = rows / len(x): each
    token's k assignments, in the sorted order.  The gradient gathers
    each token's k rows back by the inverse order and sums them."""
    k = order.shape[0] // x.shape[0]
    return x[order // k]


def _dispatch_fwd(x, order, inv):
    k = order.shape[0] // x.shape[0]
    return x[order // k], (order, inv, x.shape[0])


def _dispatch_bwd(res, g):
    order, inv, n = res
    k = order.shape[0] // n
    # the sum over a token's k rows as a contraction: accumulated in
    # f32 without an f32 copy of the (n * k, d) rows
    dx = jnp.einsum("nkd,k->nd", g[inv].reshape(n, k, -1),
                    jnp.ones((k,), g.dtype),
                    preferred_element_type=jnp.float32)
    return dx.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def held_experts(p, x, w, idx, moe):
    """The held experts' part of the routed output.  x: (N, d); w, idx:
    (N, k) weights and expert ids over the router's experts.  Returns
    (y (N, d), per-layer counters)."""
    N, d = x.shape
    k = idx.shape[-1]
    local = idx - moe.first_held
    held = (local >= 0) & (local < moe.n_held)
    key = jnp.where(held, local, moe.n_held).reshape(N * k)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    sizes = jnp.sum(key[:, None] == jnp.arange(moe.n_held), axis=0,
                    dtype=jnp.int32)
    total = jnp.sum(sizes)
    # rows past the held assignments belong to no group: zero them, in
    # the results and (through `where`) in every gradient
    valid = (jnp.arange(N * k) < total)[:, None]

    def gdot(a, wt):
        return jnp.where(valid, jax.lax.ragged_dot(a, wt.astype(a.dtype),
                                                   sizes), 0)

    xs = jnp.where(valid, _dispatch(x, order, inv), 0)
    h = gdot(xs, p["wg"])
    u = gdot(xs, p["wi"])
    ys = gdot(jax.nn.silu(h) * u, p["wo"])
    ya = _permute(ys, inv, order).reshape(N, k, d)
    # gate-weighted sum over a token's k assignments, a contraction in
    # the compute dtype (the gates rounded to it like the expert outputs):
    # no f32 copy of the (N, k, d) rows forward or backward
    wh = jnp.where(held, w, 0.0).astype(x.dtype)
    y = jnp.einsum("nkd,nk->nd", ya, wh)
    sizes_f = sizes.astype(jnp.float32)
    stats = {"tokens_held": total.astype(jnp.float32),
             "max_load": jnp.max(sizes_f) / jnp.maximum(
                 jnp.mean(sizes_f), 1e-30)}
    return y, stats


def dropless_moe_apply(p, x, moe):
    """x: (B, S, d) -> (B, S, d), {moe_aux (the balance loss),
    tokens_held, max_load}.  Router logits in float32."""
    B, S, d = x.shape
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"],
                            precision=jax.lax.Precision.HIGHEST)
        w, idx, scores = route(logits, moe)
        aux = balance_loss(scores, idx, moe.num_experts, moe.top_k)
    with jax.named_scope("moe.experts"):
        y, stats = held_experts(p, x.reshape(B * S, d),
                                w.reshape(B * S, -1), idx.reshape(B * S, -1),
                                moe)
        y = y.reshape(B, S, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp_apply(p["shared"], x)
    return y, {"moe_aux": aux, **stats}


# ---------------------------------------------------------------------------
# the routing family a configuration names (`MoEConfig.router`)
# ---------------------------------------------------------------------------

def init_layer(key, cfg, split: int):
    """An expert layer's (params, logical) for a ModelConfig; `split` is
    the GShard layout's virtual-expert split."""
    m = cfg.moe
    if m.router == "deepseek_v3":
        return init_dropless_moe(key, cfg.d_model, m.d_expert, m.num_experts,
                                 m.n_held, m.n_shared)
    return init_moe(key, cfg.d_model, cfg.d_ff, m.num_experts, split)


def apply_layer(p, x, cfg, split: int, rules=None):
    """x: (B, S, d) -> (B, S, d), aux dict (`moe_aux`, and a dropless
    layer's counters)."""
    m = cfg.moe
    if m.router == "deepseek_v3":
        return dropless_moe_apply(p, x, m)
    return moe_apply(p, x, num_experts=m.num_experts, top_k=m.top_k,
                     split=split, capacity_factor=m.capacity_factor,
                     rules=rules)


def objective(cfg, moe_aux):
    """The term the expert layers add to the loss, from `moe_aux` summed
    over them: DeepSeek-V3's balance loss times its alpha, or GShard's
    load loss at 0.01 a layer."""
    if cfg.moe.router == "deepseek_v3":
        return cfg.moe.balance_alpha * moe_aux
    return 0.01 * moe_aux / cfg.n_layers
