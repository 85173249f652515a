"""Readings of the control and of the planted faults, at a cell's size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \
        [--program-seeds 4 5 6 ...]

For each seed the plain reference (float32, `Precision.HIGHEST`) runs
the traffic's first steps, then takes the program's place computed two
wrong ways, and each is compared with the float32 run by the numbers
that decide `correct`:

* `fp8`: the precision control, every matmul operand rounded to
  float8_e4m3fn (one step below the bfloat16 the configuration states);
* `half_batch`: the fault of a step that leaves out half of the batch
  and takes the mean over the rest.

On a configuration with a mesh, every run places its weights and
moments with the program's own state shardings on the cell's chips, as
the benchmark's check does.  A step that returns its state unchanged
reads `change_gap` = 1 by construction and needs no run.

`--program-seeds` first reads the program's own numbers on many seeds
in one process, for a `train` cell: each seed's set-up and first steps
through the benchmark's `Run` (no window), then the benchmark's check.

The benchmark's own runs never run this; the control's readings set the
upper end of each limit, the program's the lower (PERF.md).  Each line
of standard output is one reading as JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def program_shardings(cfg, traffic):
    """The program's state shardings and batch placement for a
    configuration, from a runtime built on its mesh and closed at once;
    (None, None) without a mesh."""
    import harness
    mesh = harness.make_mesh(cfg)
    if mesh is None:
        return None, None
    model, rc = harness.program_config(cfg, traffic)
    d = tempfile.mkdtemp(prefix="bench_control_")
    try:
        rt = harness.new_runtime(model, rc, cfg, d, 0, mesh)
        out = harness.state_shardings(rt)
        harness.free_runtime(rt)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out, harness.batch_sharding(mesh)


def readings(cfg, traffic, seed: int, faults, shardings=None,
             batch_sharding=None) -> list:
    sys.path.insert(0, HERE)
    import compare
    import harness
    s = harness.seed32(seed)
    ref_mod = harness.reference(cfg)
    follow = harness.train_reference().follow
    n = traffic["setup_steps"] + (1 if traffic["kind"] == "resume" else 0)
    batches = [harness.batch(cfg, traffic, s, i) for i in range(n)]
    t0 = time.monotonic()
    placed = {"shardings": shardings, "batch_sharding": batch_sharding}
    ref = follow(ref_mod, cfg, s, batches, **placed)
    out = [{"seed": seed, "fault": "none", "ref_s": time.monotonic() - t0,
            "losses": ref["losses"]}]
    for fault in faults:
        t0 = time.monotonic()
        if fault == "fp8":
            got = follow(ref_mod, cfg, s, batches, quant="fp8", **placed)
        elif fault == "half_batch":
            got = follow(ref_mod, cfg, s, batches,
                         rows=traffic["batch"] // 2, **placed)
        else:
            raise ValueError(fault)
        nums = compare.training(got, ref)
        out.append({"seed": seed, "fault": fault,
                    "s": time.monotonic() - t0,
                    **{k: v["value"] for k, v in nums.items()},
                    **compare.readings(got, ref),
                    "worst": {k: v["detail"] for k, v in nums.items()}})
    return out


def program_readings(cell, cfg, traffic, seed: int) -> dict:
    """The numbers the benchmark compares, for the program's set-up and
    first steps on one seed (a `train` cell; no window)."""
    import harness
    import run as bench_run
    if traffic["kind"] != "train":
        raise ValueError("program readings are taken on train cells")
    t0 = time.monotonic()
    run = harness.Run(cell, cfg, traffic, seed, 0.0, False, t0)
    try:
        rt = run._setup_runtime()
        run._first_steps(rt, traffic["setup_steps"])
        harness.free_runtime(rt)
        rt = None
        _, judged, _, extra = bench_run.checks(run, run.rec, cfg, traffic)
    finally:
        run.cleanup()
    return {"seed": seed, "fault": "program", "s": time.monotonic() - t0,
            **{k: v["value"] for k, v in judged.items()}, **extra}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="+", default=["fp8", "half_batch"])
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run as bench_run
    cell, cfg, traffic, _ = bench_run.cell_files(args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"control: needs {cell['chips']} TPU chip(s)", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    for seed in args.program_seeds:
        print(json.dumps(program_readings(cell, cfg, traffic, seed)),
              flush=True)
    shardings, rows = program_shardings(cfg, traffic)
    for seed in args.seeds:
        for r in readings(cfg, traffic, seed, args.faults, shardings, rows):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
