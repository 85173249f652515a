"""Readings of the control and of the planted faults, at a cell's size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed the plain reference (float32, `Precision.HIGHEST`) runs
the traffic's first steps, then takes the program's place computed two
wrong ways, and each is compared with the float32 run by the numbers
that decide `correct`:

* `fp8`: the precision control, every matmul operand rounded to
  float8_e4m3fn (one step below the bfloat16 the configuration states);
* `half_batch`: the fault of a step that leaves out half of the batch
  and takes the mean over the rest.

A step that returns its state unchanged reads `change_gap` = 1 by
construction and needs no run.  The benchmark's own runs never run
this; its readings set the upper end of each limit (PERF.md).  Each
line of standard output is one reading as JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def readings(cfg, traffic, seed: int, faults) -> list:
    sys.path.insert(0, HERE)
    import compare
    import harness
    s = harness.seed32(seed)
    ref_mod = harness.reference(cfg)
    follow = harness.train_reference().follow
    n = traffic["setup_steps"] + (1 if traffic["kind"] == "resume" else 0)
    batches = [harness.batch(cfg, traffic, s, i) for i in range(n)]
    t0 = time.monotonic()
    ref = follow(ref_mod, cfg, s, batches)
    out = [{"seed": seed, "fault": "none", "ref_s": time.monotonic() - t0,
            "losses": ref["losses"]}]
    for fault in faults:
        t0 = time.monotonic()
        if fault == "fp8":
            got = follow(ref_mod, cfg, s, batches, quant="fp8")
        elif fault == "half_batch":
            got = follow(ref_mod, cfg, s, batches,
                         rows=traffic["batch"] // 2)
        else:
            raise ValueError(fault)
        nums = compare.training(got, ref)
        out.append({"seed": seed, "fault": fault,
                    "s": time.monotonic() - t0,
                    **{k: v["value"] for k, v in nums.items()},
                    **compare.readings(got, ref),
                    "worst": {k: v["detail"] for k, v in nums.items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=["fp8", "half_batch"])
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run as bench_run
    cell, cfg, traffic, _ = bench_run.cell_files(args.workload, ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU chip", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", bench_run.CACHE_DIR)
    for seed in args.seeds:
        for r in readings(cfg, traffic, seed, args.faults):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
