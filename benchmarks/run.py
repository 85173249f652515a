"""Benchmark harness: one benchmark per paper table/figure, on the
simulated fabric.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).

Flags:
  --quick           smaller rank counts / fewer steps everywhere
  --smoke           protocol-only benchmark subset for CI: fig4 + barrier
                    at {4, 8, 64} ranks plus the 512-rank scale arms
                    (collective rates + checkpoint pipeline), drain
                    scaling, the durable-store arms (store-attached
                    ckpt stall, compaction throughput, tiered restore
                    latency), and the wire/image codec throughput
                    records — skips the jax-heavy fig2/fig3 suites
  --transport T     which fabric backend(s) to benchmark: "inproc"
                    (default; the guarded baseline records), "socket"
                    (one-process-per-rank collective rates through the
                    world harness), or "all"
  --json PATH       additionally write machine-readable results
                    (BENCH_protocol.json schema; consumed by
                    benchmarks/check_regression.py in CI)
"""
from __future__ import annotations

import sys


def main() -> None:
    argv = sys.argv[1:]
    quick = "--quick" in argv
    smoke = "--smoke" in argv
    transport = "inproc"
    if "--transport" in argv:
        try:
            transport = argv[argv.index("--transport") + 1]
        except IndexError:
            sys.exit("error: --transport requires a backend name")
        if transport not in ("inproc", "socket", "all"):
            sys.exit(f"error: unknown transport {transport!r} "
                     "(inproc | socket | all)")
    json_path = None
    if "--json" in argv:
        try:
            json_path = argv[argv.index("--json") + 1]
        except IndexError:
            sys.exit("error: --json requires a path argument")

    from benchmarks import protocol_benchmarks

    results: list = []
    rows = []
    if transport in ("socket", "all"):
        # per-transport collective rates: one OS process per rank over
        # loopback TCP; virtual rates must match inproc at the same n
        rows += protocol_benchmarks.transport_collective_rates(
            "socket", ranks=(4, 8), results=results)
        # supervised rank-failure recovery over real processes
        rows += protocol_benchmarks.recovery_latency(
            "socket", results=results)
        # async incremental checkpoint pipeline over real processes
        # (forked writers); small n — the guarded arm is inproc n=64
        rows += protocol_benchmarks.checkpoint_pipeline(
            "socket", ranks=(8,), results=results)
    if transport == "socket":
        pass  # socket-only run: skip the inproc suites below
    elif smoke:
        rows += protocol_benchmarks.fig4_collective_rates(
            ranks=(4, 8, 64, 512), results=results)
        rows += protocol_benchmarks.barrier_latency(
            ranks=(8, 64), iters=20, results=results)
        rows += protocol_benchmarks.drain_scaling(
            ranks=(4, 8, 64), results=results)
        rows += protocol_benchmarks.recovery_latency(
            "inproc", results=results)
        # the ISSUE-6 guarded record: same-world restore via the
        # unified restore_world path (64,64) + elastic N!=M pairs
        rows += protocol_benchmarks.elastic_restore_latency(
            results=results)
        # the ISSUE-4 guarded records: stall sync vs async + image
        # bytes full vs delta at the 64-rank guard point.  steps=12
        # gives three request windows — on a slow host the sync arm's
        # step-6 request can coalesce into the still-open first round,
        # and the delta-bytes record needs a second round to exist
        rows += protocol_benchmarks.checkpoint_pipeline(
            "inproc", ranks=(64,), steps=12, results=results)
        # the 512-rank scale arm (ISSUE 5): one checkpoint round per
        # mode, smaller shards — the records prove the pipeline closes
        # and commits at 512 GIL-bound ranks, the guards ride on n=64
        rows += protocol_benchmarks.checkpoint_pipeline(
            "inproc", ranks=(512,), shard_kb=16, steps=4, every=2,
            results=results)
        # the ISSUE-10 guarded records: sync stall with the durable
        # store + background compactor attached (must stay in family
        # with the plain sync stall above, same run), compaction
        # throughput with the bit-identical restore proof, and the
        # chain/compacted/fallback store restore tiers
        rows += protocol_benchmarks.store_checkpoint_stall(
            "inproc", n=64, steps=12, results=results)
        rows += protocol_benchmarks.image_store_benchmarks(
            results=results)
        # the ISSUE-5 codec guards: frame v2 vs pickle, binary image
        # containers vs JSON/base64
        rows += protocol_benchmarks.wire_codec_throughput(results=results)
        rows += protocol_benchmarks.image_codec_throughput(results=results)
    else:
        rows += protocol_benchmarks.fig2_interposition_overhead(
            ranks=(4, 8) if quick else (4, 8, 16))
        rows += protocol_benchmarks.table2_2pc_variants(
            n=4 if quick else 8, steps=30 if quick else 60)
        rows += protocol_benchmarks.fig3_ckpt_restart()
        rows += protocol_benchmarks.fig4_collective_rates(
            ranks=(4, 8, 16) if quick else (4, 8, 16, 64, 128, 256, 512),
            results=results)
        rows += protocol_benchmarks.barrier_latency(
            ranks=(8,) if quick else (8, 64), results=results)
        rows += protocol_benchmarks.drain_scaling(
            ranks=(4, 8) if quick else (4, 8, 16, 32, 64, 128, 256),
            results=results)
        rows += protocol_benchmarks.recovery_latency(
            "inproc", results=results)
        rows += protocol_benchmarks.elastic_restore_latency(
            pairs=((8, 8), (8, 3)) if quick
            else ((64, 64), (64, 61), (61, 64), (8, 3)),
            results=results)
        rows += protocol_benchmarks.checkpoint_pipeline(
            "inproc", ranks=(8,) if quick else (64, 256),
            results=results)
        if not quick:
            rows += protocol_benchmarks.checkpoint_pipeline(
                "inproc", ranks=(512,), shard_kb=16, steps=4, every=2,
                results=results)
        rows += protocol_benchmarks.store_checkpoint_stall(
            "inproc", n=8 if quick else 64, steps=12, results=results)
        rows += protocol_benchmarks.image_store_benchmarks(
            n=4 if quick else 16, chain_len=4 if quick else 6,
            results=results)
        rows += protocol_benchmarks.wire_codec_throughput(results=results)
        rows += protocol_benchmarks.image_codec_throughput(results=results)

    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if json_path:
        transports = {r.get("transport", "inproc") for r in results}
        protocol_benchmarks.write_results(
            json_path, results,
            meta={"quick": quick, "smoke": smoke,
                  "transports": sorted(transports),
                  "msg_cost_us": protocol_benchmarks.MSG_COST_US})
        print(f"# wrote {json_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
