"""Plain training reference: a model reference's loss, its gradient and
AdamW with global-norm clipping and a warmup-cosine learning rate, as
the configuration's `run.optimizer` states them, in float32.

`follow` runs the first steps from the seed's weights on the given
batches and returns what the comparison reads: each step's loss, the
norm of each leaf of the first (clipped) gradient, and the norm of each
leaf's change over all the steps.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def lr_at(step: int, opt: Dict) -> float:
    warm = (step + 1.0) / max(1.0, opt["warmup_steps"])
    prog = min(max((step - opt["warmup_steps"])
                   / max(1.0, opt["total_steps"] - opt["warmup_steps"]), 0.0),
               1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + np.cos(np.pi * prog))
    return opt["lr"] * min(warm, cos)


def leaf_norms(tree) -> Dict[str, float]:
    """path -> f32 L2 norm of every floating leaf."""
    return _host(_jit_norms(_floating(tree)))


def _floating(tree) -> Dict:
    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]
            if jnp.issubdtype(leaf.dtype, jnp.floating)}


def _norms(flat):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


_jit_norms = jax.jit(_norms)


def _host(norms) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def _step(ref, cfg, quant, params, m, v, tokens, labels, count, lr):
    opt = cfg["run"]["optimizer"]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(ref.loss)(params, tokens, labels,
                                                   cfg, quant)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, loss, _norms(_floating(grads))


def follow(ref, cfg: Dict, seed: int, batches: List[Dict[str, np.ndarray]],
           quant: Optional[str] = None, rows: Optional[int] = None) -> Dict:
    """Run len(batches) reference steps from the seed's weights.

    `rows` keeps only the first rows of every batch: the half-batch
    fault, put in the program's place."""
    frozen = _Frozen(cfg)
    init = jax.jit(ref.init_params, static_argnums=0)
    params = init(frozen, np.uint32(seed))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(functools.partial(_step, ref, frozen, quant),
                   donate_argnums=(0, 1, 2))
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        tok, lab = b["tokens"][:rows], b["labels"][:rows]
        params, m, v, loss, gn = step(
            params, m, v, jnp.asarray(tok), jnp.asarray(lab),
            jnp.float32(i + 1), jnp.float32(lr_at(i, cfg["run"]["optimizer"])))
        losses.append(float(loss))
        if i == 0:
            grad_norms = _host(gn)
    del m, v
    change = leaf_norms(subtract(params, init(frozen, np.uint32(seed))))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


subtract = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        import json
        return hash(json.dumps(self, sort_keys=True))

    def __eq__(self, other):
        return dict.__eq__(self, other)
