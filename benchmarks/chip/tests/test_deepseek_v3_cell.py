"""CPU tests of the `moonlight-16b-a3b-l5.save_sparse_8k` cell.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests

The configuration's counts pinned; the cell's `save_sparse_8k` mix run
through `run.run_cell` at a tiny size of the same family, clean and
with the shared experts left out of the step underneath.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

import counts  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402

CELL = "moonlight-16b-a3b-l5.save_sparse_8k"

# limits at the tiny size (two 128-token sequences a step, as the cell
# takes two), set as the cell's are: the program reads loss 8.4e-5 ..
# 1.95e-4 and change 5.9e-4 .. 2.65e-3 (seeds 2**31 + 7, 5, 11,
# 3150000901); the fp8 control 5.8e-4 .. 1.30e-3 and 4.1e-3 .. 4.8e-3,
# the half batch 6.8e-3 .. 2.0e-2 and 0.19 .. 0.20 (seeds 1-3): each
# fails both on every seed
TINY_LIMITS = {"loss_gap": 3.5e-4, "change_gap": 3.3e-3}


def tiny(cfg):
    """1 dense + 2 expert layers, 4 of 8 routed experts held, top-3."""
    return dict(cfg, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3,
                n_routed_experts=4, num_experts_per_tok=3, vocab_size=256,
                layout=dict(cfg["layout"], router_experts=8),
                limits=TINY_LIMITS)


def test_counts():
    """2,283,012,096 FLOPs a token at 8,192 (the family module's
    docstring shows the sum), 568,484,352 stored parameters."""
    _, cfg, traffic, _ = bench_run.cell_files(CELL, ROOT)
    tokens = traffic["batch"] * traffic["seq_len"]
    per_step = counts.train_flops_per_token(cfg, traffic["seq_len"]) * tokens
    assert (traffic["batch"], traffic["seq_len"]) == (2, 8192)
    assert per_step == 37_404_870_180_864
    assert counts.stored_param_count(cfg) == 568_484_352
    assert counts.state_bytes(cfg) == 6_821_812_232
    from repro.training.step import abstract_train_state
    model, rc = harness.program_config(cfg, traffic)
    st = abstract_train_state(model, rc)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st)) == \
        counts.state_bytes(cfg)
    assert model.moe.num_experts == 64 and model.moe.n_held == 8


def rehearse(fault=None, seed=2 ** 31 + 7):
    _, cfg, traffic, _ = bench_run.cell_files(CELL, ROOT)
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=3.0,
                              trace=0)
    return bench_run.run_cell(
        args, require_tpu=False, cfg_override=tiny(cfg),
        traffic_override=dict(traffic, seq_len=128), fault=fault,
        t_start=time.monotonic())


def test_the_cell_runs_tiny_and_is_correct():
    res = rehearse()
    assert res["correct"], res["checks"]
    assert res["checks"]["image_mismatch"]["value"] == 0
    m = res["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["save_stall_s"]["value"] > 0
    _, cfg, _, _ = bench_run.cell_files(CELL, ROOT)
    assert m["save_bytes"]["value"] == counts.state_bytes(tiny(cfg))


def _with_shared(state, shared):
    p = state["params"]
    blocks = dict(p["blocks"], moe=dict(p["blocks"]["moe"], shared=shared))
    return dict(state, params=dict(p, blocks=blocks))


def _no_shared_experts(rt):
    """The step computes the expert layers without their shared experts
    and leaves those weights as they were."""
    inner = rt.lower.train_step

    def step(state, b):
        shared = state["params"]["blocks"]["moe"]["shared"]
        off = jax.tree.map(jnp.zeros_like, shared)
        new, metrics = inner(_with_shared(state, off), b)
        return _with_shared(new, shared), metrics

    rt.lower.train_step = step


@pytest.mark.parametrize("fault", [_no_shared_experts],
                         ids=lambda f: f.__name__[1:])
def test_a_broken_step_is_not_correct(fault):
    res = rehearse(fault=fault)
    assert res["correct"] is False, res["checks"]
