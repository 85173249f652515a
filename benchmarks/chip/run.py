"""The chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the TPU chips the
cell asks for; with none it exits 1 and prints no result.  `--trace 0`
reports the cell's end-to-end metrics, `--trace 1` its per-layer ones
from a profiled run.  The last line of standard output is the JSON
result; the numbers that decide `correct` close standard error and the
result line.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".bench_jax_cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_files(name: str, root: str = ROOT):
    """(cell, configuration, traffic, benchmark) for a cell name."""
    sys.path.insert(0, HERE)
    import harness
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = harness.load_json("configs", cell["config"] + ".json")
    traffic = dict(harness.load_json("traffic", cell["traffic"] + ".json"),
                   name=cell["traffic"])
    return cell, cfg, traffic, bench


def metrics_for(bench, cell_name: str, trace: bool):
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def end_to_end(rec: dict, tokens_per_step: int) -> dict:
    import harness
    out = {"setup_s": rec["setup_s"]}
    if rec["steps"]:
        t0, t1 = rec["window"]
        out["tokens_per_s"] = (len(rec["steps"]) - 1) * tokens_per_step / (
            t1 - t0)
    if rec["saves"]:
        out["save_stall_s"] = harness.mean(s["safe_point_s"]
                                           for s in rec["saves"])
        out["save_bytes"] = harness.mean(s["bytes"]
                                         for s in rec["ckpt_stats"])
    if rec["resumes"]:
        out["resume_s"] = harness.mean(
            r["read_s"] + r["to_device_s"] + r["first_step_s"]
            for r in rec["resumes"])
    return out


def checks(run, rec: dict, cfg: dict, traffic: dict) -> tuple:
    """Run the plain reference over the traffic's first steps and set
    every compared number beside its limit."""
    import compare
    import harness
    n = traffic["setup_steps"] + (1 if traffic["kind"] == "resume" else 0)
    batches = [harness.batch(cfg, traffic, run.seed, i) for i in range(n)]
    ref = harness.train_reference().follow(run.ref, cfg, run.seed, batches)
    if traffic["kind"] == "resume":
        numbers = {}
        for r in rec["resumes"] or [None]:
            prog = {"losses": rec["losses"][:n - 1]
                    + ([r["loss"]] if r else []),
                    "grad_norms": rec["grad_norms"],
                    "change_norms": r["change_norms"] if r else {}}
            one = compare.training(prog, ref)
            for k, v in one.items():
                if k not in numbers or v["value"] > numbers[k]["value"]:
                    numbers[k] = v
        extra = compare.readings(prog, ref)
        want = rec["save_fp"][max(rec["save_fp"])]
        bad = sum(sum(r["fp"].get(k) != v for k, v in want.items())
                  + (r["start"] != max(rec["save_fp"]))
                  for r in rec["resumes"])
        numbers["restore_mismatch"] = {"value": bad, "detail": "exact"}
        failed = sum(1 for r in rec["resumes"]
                     if r["start"] != max(rec["save_fp"])
                     or any(r["fp"].get(k) != v for k, v in want.items()))
    else:
        prog = {"losses": rec["losses"][:n],
                "grad_norms": rec["grad_norms"],
                "change_norms": rec["change_norms"]}
        numbers = compare.training(prog, ref)
        extra = compare.readings(prog, ref)
        failed = 0
        if rec["save_fp"]:
            numbers["image_mismatch"] = {"value": rec["image_mismatch"],
                                         "detail": "exact"}
            failed = int(rec["image_mismatch"] > 0)
    limits = dict(cfg["limits"], image_mismatch=0, restore_mismatch=0)
    ok, judged = compare.judge(numbers, limits)
    return ok, judged, failed, extra


def per_layer(rec: dict, wanted, trace_summary, device_kind: str,
              cfg: dict, traffic: dict) -> dict:
    import counts
    import harness
    r = dict(rec)
    r["trace"] = trace_summary
    r["peaks"] = harness.peaks(device_kind)
    r["flops_per_token"] = counts.train_flops_per_token(cfg,
                                                        traffic["seq_len"])
    r["tokens_per_step"] = traffic["batch"] * traffic["seq_len"]
    r["e2e"] = end_to_end(rec, r["tokens_per_step"])
    out = {}
    for m in wanted:
        v = harness.metric_reader(m["name"]).read(r)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(args, require_tpu: bool = True, cfg_override=None,
             traffic_override=None, fault=None, root: str = ROOT,
             t_start: float = T_START) -> dict:
    """One run; returns the result object (also the tests' entry)."""
    cell, cfg, traffic, bench = cell_files(args.workload, root)
    cfg = cfg_override or cfg
    traffic = dict(traffic_override, name=traffic["name"]) \
        if traffic_override else traffic
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s), "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(1)
    sys.path.insert(0, os.path.join(root, "src"))
    import harness
    dev = devices[0]
    run = harness.Run(cell, cfg, traffic, args.seed, args.seconds,
                      bool(args.trace), t_start, fault=fault)
    try:
        rec = run.run()
        summary = None
        if args.trace:
            import glob
            import traces as trace_mod
            path = glob.glob(os.path.join(run.trace_dir, "**",
                                          "*.xplane.pb"), recursive=True)[0]
            summary = trace_mod.reduce(trace_mod.load(path),
                                       kernels=harness.KERNELS)
        ok, judged, failed, extra = checks(run, rec, cfg, traffic)
    finally:
        run.cleanup()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": ok,
              "attempted": max(len(rec["steps"]) - 1, 0) + len(rec["saves"])
              + len(rec["resumes"]),
              "failed": failed}
    if args.trace:
        result["metrics"] = per_layer(rec, metrics_for(bench, cell["name"],
                                                       True),
                                      summary, dev.device_kind, cfg, traffic)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = summary["breakdown"]
    else:
        e2e = end_to_end(rec, traffic["batch"] * traffic["seq_len"])
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in metrics_for(bench, cell["name"], False)
            if m["name"] in e2e}
    result["device"] = device
    result["window"] = {"compiles": rec["compiles_in_window"],
                        "setup_compiles": rec["setup_compiles"],
                        "drain_s": rec.get("drain_s")}
    result["readings"] = extra
    result["checks"] = judged
    return result


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(args)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
