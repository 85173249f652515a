"""Operation and byte counts, from a configuration file's published shapes.

These are the yardstick's own arithmetic: nothing here reads the program.
What depends on the model family comes from the family module that the
configuration's `reference` key names (`references/<family>.py`):

* `train_flops_per_token`: the family's forward + backward matmul FLOPs
  of one token.  Rematerialised work and the padded heads the program
  stores do not count.
* `state_bytes`: the f32 params + AdamW moments + the two int32 scalars
  the program holds, in its stored layout: the family's `param_shapes`.
"""
from __future__ import annotations

import math
from typing import Dict

import jax

import harness


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    return harness.reference(cfg).train_flops_per_token(cfg, seq_len)


def stored_param_count(cfg: Dict) -> int:
    shapes = harness.reference(cfg).param_shapes(cfg)
    return sum(math.prod(s) for s in jax.tree.leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)))


def state_bytes(cfg: Dict) -> int:
    """Params, AdamW m and v (f32 each), step and count (int32)."""
    return 3 * 4 * stored_param_count(cfg) + 2 * 4
