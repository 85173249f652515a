"""One run of a cell with the program's own span recording on.

    python3 benchmarks/chip/trace_program.py --workload <cell> \\
        --seed <n> --seconds <s> [--profile 0|1]

The run is the benchmark's (`run.py`): the same set-up, window and
drain, on a machine with the TPU chips the cell asks for.  The window
and the drain run inside `repro.core.tracing.recording()`, and the
result line holds the program's phases beside the harness's own
host-clock numbers they split: per write, the `write_*` readers of
`ckpt.stats`; per resume, `program_spans.restore_readings`.  With
`--profile 1` (default) the run is profiled as a `--trace 1` run is;
the line then holds the cell's per-layer metrics, its `breakdown`, and
the idle gaps labelled by the program's spans.  `--profile 0` gives the
end-to-end metrics of a run with recording on, to set beside a
`run.py --trace 0` run of the same seed: the cost of recording.  The
correctness check is not made here.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402

WRITE_METRICS = ("write_base_read_s", "write_encode_s", "write_digest_s",
                 "write_file_s", "write_commit_s")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    return ap.parse_args(argv)


def measure(args, require_tpu: bool = True, cfg_override=None,
            traffic_override=None, t_start: float = T_START) -> dict:
    cell, cfg, traffic, bch = bench.cell_files(args.workload)
    cfg = cfg_override or cfg
    if traffic_override:
        traffic = dict(traffic_override, name=traffic["name"])
    import jax
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        print(f"trace_program: {args.workload} needs a TPU chip",
              file=sys.stderr)
        raise SystemExit(1)
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    import harness
    import program_spans
    from repro.core import tracing

    class RecordedRun(harness.Run):
        def _train_window(self, rt):
            with tracing.recording() as self.recording:
                super()._train_window(rt)

        def _resume_window(self):
            with tracing.recording() as self.recording:
                super()._resume_window()

    run = RecordedRun(cell, cfg, traffic, args.seed, args.seconds,
                      bool(args.profile), t_start)
    try:
        rec = run.run()
        tokens = traffic["batch"] * traffic["seq_len"]
        spans = run.recording.spans
        out = {"workload": args.workload, "seed": args.seed,
               "profile": args.profile,
               "device": {"platform": dev.platform,
                          "kind": dev.device_kind},
               "end_to_end": bench.end_to_end(rec, tokens),
               "harness": _harness_numbers(rec),
               "program": _program_numbers(rec, run.recording)}
        if args.profile:
            import traces
            path = glob.glob(os.path.join(run.trace_dir, "**",
                                          "*.xplane.pb"), recursive=True)[0]
            pd = traces.load(path)
            summary = traces.reduce(pd, kernels=harness.KERNELS)
            out["metrics"] = bench.per_layer(
                rec, bench.metrics_for(bch, cell["name"], True), summary,
                dev.device_kind, cfg, traffic, cell["chips"])
            out["breakdown"] = summary["breakdown"]
            out["busy_s"], out["window_s"] = (summary["busy_s"],
                                              summary["window_s"])
            idle = program_spans.idle_by_span(pd, spans)
            out["breakdown"]["idle_gaps_by_program_span"] = \
                idle["idle_gaps_by_program_span"]
            out["program"]["untraced_idle_share"] = \
                idle["untraced_idle_share"]
    finally:
        run.cleanup()
    return out


def _harness_numbers(rec: dict) -> dict:
    """The host-clock numbers the program's phases split."""
    import harness
    out = {"write_s": harness.mean(s["write_s"]
                                   for s in rec.get("ckpt_stats") or [])}
    if rec["resumes"]:
        for key in ("read_s", "to_device_s", "first_step_s"):
            out["restore_" + key] = harness.mean(r[key]
                                                 for r in rec["resumes"])
    return out


def _program_numbers(rec: dict, recording) -> dict:
    """Per write, the `write_*` readers and what is left of `write_s`;
    per resume, the restore's phases from the recording."""
    import harness
    import program_spans
    out = {"span_records": len(recording.spans)}
    if rec.get("ckpt_stats"):
        for m in WRITE_METRICS:
            out[m] = harness.metric_reader(m).read(rec)
        write_s = harness.mean(s["write_s"] for s in rec["ckpt_stats"])
        out["write_own_s"] = write_s - sum(out[m] for m in WRITE_METRICS)
    out.update(program_spans.restore_readings(recording.summary()))
    return out


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench.CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", bench.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(json.dumps(measure(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
