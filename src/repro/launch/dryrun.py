import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (docstring below; the two lines above MUST precede any other import —
# jax locks the device count at first init)
_DOC = """Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, and extract the roofline terms.

This is the proof that the distribution config is coherent: a sharding
mismatch, compile-time OOM, or unsupported collective fails the cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json

Output (per cell): memory_analysis summary, cost_analysis FLOPs/bytes,
per-collective byte totals parsed from the partitioned HLO — consumed by
EXPERIMENTS.md §Dry-run and §Roofline.
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES_BY_NAME, shape_applicable
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.sharding.rules import ShardingRules
from repro.training.step import (abstract_params, abstract_train_state,
                                 batch_specs, decode_state_specs,
                                 make_serve_steps, make_train_step,
                                 train_state_specs)

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|"
                       r"u16|u8|pred)\[([0-9,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    key = dtype if dtype in _DTYPE_BYTES else dtype[:3]
    return n * _DTYPE_BYTES.get(key, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand sizes of every collective op in the partitioned HLO.

    Shapes in the post-GSPMD module are per-device, so these are
    per-device wire bytes (see EXPERIMENTS.md §Roofline for the model).
    """
    out: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "start" in stripped.split("(")[0]:
            # count the -start of async pairs once; skip -done lines
            pass
        for op in COLLECTIVE_OPS:
            token = f" {op}("
            token_start = f" {op}-start("
            if token in stripped or token_start in stripped:
                # operand types are inside the parens; result type before '='
                try:
                    args = stripped.split("(", 1)[1]
                except IndexError:
                    continue
                nbytes = sum(_type_bytes(m.group(1), m.group(2))
                             for m in _SHAPE_RE.finditer(args))
                if nbytes == 0:
                    # operands may be bare %refs; fall back to result type
                    head = stripped.split("=", 1)[0] + "=" + \
                        stripped.split("=", 1)[1].split(op)[0]
                    nbytes = sum(_type_bytes(m.group(1), m.group(2))
                                 for m in _SHAPE_RE.finditer(head))
                out[op] += nbytes
                out["count"] += 1
                break
    out["total"] = sum(out[op] for op in COLLECTIVE_OPS)
    return out


def _shard_tree(mesh, spec_tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_abstract(cfg: ModelConfig, shape: ShapeConfig, dtype):
    from repro.data.pipeline import make_batch_specs
    return make_batch_specs(cfg, shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, rc: RunConfig,
                rules: ShardingRules, mesh):
    """ShapeDtypeStruct stand-ins (+ shardings) for every model input."""
    dtype = jnp.dtype(rc.dtype)
    specs = _batch_abstract(cfg, shape, dtype)
    sh = batch_specs(cfg, shape, rules)
    return specs, _shard_tree(mesh, sh)


def _serving_dtype(params_abs, rc):
    """Inference serves bf16 weights (production choice; the f32 masters
    live with the trainer).  Forward casts per-use, so only the argument
    dtype changes."""
    dt = jnp.dtype(rc.dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dt if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
        params_abs)


def production_rc(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Per-cell production defaults (the §Perf-validated choices):
    inference shapes shard the KV cache over time and serve bf16 weights;
    big trains shard f32 masters over data (FSDP/ZeRO-3)."""
    over: Dict[str, Any] = {}
    if shape.kind in ("decode", "prefill"):
        over["kv_time_shard"] = True
    if shape.kind == "train" and cfg.param_count() * 4 / 16 > 2e9:
        over["fsdp"] = True
    if (cfg.sliding_window and cfg.sliding_window < shape.seq_len
            and shape.kind == "train"):
        # SWA span traffic ∝ window+chunk (§Perf A4): small chunks win in
        # training (scores dominate, fwd+bwd); prefill is forward-only
        # and re-reads the KV span per q-block, so large chunks win there
        over["attn_chunk"] = 128
    return over


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rc_overrides: Optional[Dict] = None) -> Dict[str, Any]:
    """Lower + compile one (arch x shape x mesh) cell; return analysis."""
    cfg = ARCHS[arch]
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    cell = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        cell.update(status="skip", reason=why)
        return cell
    t0 = time.monotonic()
    mesh = make_production_mesh(multi_pod=multi_pod)
    over = production_rc(cfg, shape)
    over.update(rc_overrides or {})
    cell["rc"] = dict(over)
    rc = RunConfig(model=cfg, shape=shape, **over)
    rules = ShardingRules(mesh, moe_mode=rc.moe_mode,
                          seq_shard=rc.seq_shard,
                          kv_time_shard=rc.kv_time_shard)

    if shape.kind == "train":
        state_shapes = abstract_train_state(cfg, rc)
        state_specs = train_state_specs(cfg, rc, rules)
        batch_abs, batch_sh = input_specs(cfg, shape, rc, rules, mesh)
        fn = make_train_step(cfg, rc, rules)
        jitted = jax.jit(fn,
                         in_shardings=(_shard_tree(mesh, state_specs),
                                       batch_sh),
                         out_shardings=(_shard_tree(mesh, state_specs), None))
        args = (state_shapes, batch_abs)
    elif shape.kind == "prefill":
        params_abs, _ = abstract_params(cfg)
        params_abs = _serving_dtype(params_abs, rc)
        p_specs = train_state_specs(cfg, rc, rules)["params"]
        batch_abs, batch_sh = input_specs(cfg, shape, rc, rules, mesh)
        prefill_step, _ = make_serve_steps(cfg, rc, rules)
        d_specs = decode_state_specs(cfg, rc, rules, shape)
        jitted = jax.jit(prefill_step,
                         in_shardings=(_shard_tree(mesh, p_specs), batch_sh),
                         out_shardings=(None, _shard_tree(mesh, d_specs)))
        args = (params_abs, batch_abs)
    else:  # decode
        from repro.models.transformer import init_decode_state
        params_abs, _ = abstract_params(cfg)
        params_abs = _serving_dtype(params_abs, rc)
        p_specs = train_state_specs(cfg, rc, rules)["params"]
        state_abs = jax.eval_shape(lambda: init_decode_state(cfg, shape, rc))
        d_specs = decode_state_specs(cfg, rc, rules, shape)
        tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tok_spec = rules.spec(("batch", None), (shape.global_batch, 1))
        _, serve_step = make_serve_steps(cfg, rc, rules)
        jitted = jax.jit(
            serve_step,
            in_shardings=(_shard_tree(mesh, p_specs),
                          _shard_tree(mesh, d_specs),
                          NamedSharding(mesh, tok_spec)),
            out_shardings=(None, _shard_tree(mesh, d_specs)))
        args = (params_abs, state_abs, tok_abs)

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze_hlo
    colls = collective_bytes(hlo)
    trip_aware = analyze_hlo(hlo)
    trip_aware.pop("entry", None)
    cell.update(
        hlo=trip_aware,
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        cost={
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        collectives=colls,
        params=cfg.param_count(),
        params_active=cfg.param_count(active_only=True),
    )
    return cell


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "pod", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rc", default=None,
                    help="JSON RunConfig overrides (perf experiments)")
    args = ap.parse_args()
    rc_over = json.loads(args.rc) if args.rc else None

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES_BY_NAME:
                for mp in ([False, True] if args.mesh == "both"
                           else [args.mesh == "pod"]):
                    cells.append((arch, shape, mp))
    else:
        for mp in ([False, True] if args.mesh == "both"
                   else [args.mesh == "pod"]):
            cells.append((args.arch, args.shape, mp))

    results = []
    if args.out and os.path.exists(args.out):
        results = json.load(open(args.out))
        done = {(r["arch"], r["shape"], r["mesh"]) for r in results}
        cells = [c for c in cells
                 if (c[0], c[1], "2x16x16" if c[2] else "16x16") not in done]

    for arch, shape, mp in cells:
        label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
        print(f"=== {label}", flush=True)
        try:
            cell = run_cell(arch, shape, mp)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            cell = {"arch": arch, "shape": shape,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:]}
        print(json.dumps({k: v for k, v in cell.items() if k != "trace"}),
              flush=True)
        results.append(cell)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skip")
    n_err = sum(1 for r in results if r["status"] == "error")
    print(f"DONE ok={n_ok} skip={n_skip} error={n_err}", flush=True)


if __name__ == "__main__":
    main()
