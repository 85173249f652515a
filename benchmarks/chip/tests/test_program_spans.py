"""CPU tests of the readers of the program's own spans: the writer's
phase readers on hand-made records, `program_spans` on synthetic span
lists, a hand-made recording and the recorded v5e trace, and
`trace_program.py` at a tiny size.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, NamedTuple, Optional

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import program_spans  # noqa: E402
import traces  # noqa: E402

TRACE = os.path.join(CHIP, "testdata", "v5e_small.xplane.pb")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
WRITE_READERS = {"write_base_read_s": "base_read_s",
                 "write_encode_s": "encode_s",
                 "write_digest_s": "digest_s",
                 "write_file_s": "file_s",
                 "write_commit_s": "commit_s"}


class Rec(NamedTuple):
    name: str
    id: int
    parent: Optional[int]
    thread: int
    start_ns: int
    end_ns: int
    counts: Dict[str, int] = {}


@pytest.mark.parametrize("metric,key", sorted(WRITE_READERS.items()))
def test_write_phase_readers(metric, key):
    read = harness.metric_reader(metric).read
    old = [{"step": 2, "bytes": 10, "snapshot_s": 1.0, "write_s": 3.0}]
    assert read({"ckpt_stats": old}) is None          # the parent program
    assert read({}) is None and read({"ckpt_stats": []}) is None
    stats = [dict(old[0], **{key: 0.5}), dict(old[0], step=4, **{key: 2.0})]
    assert read({"ckpt_stats": stats}) == pytest.approx(1.25)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == ["qwen2-0.5b.save_tight",
                                  "qwen2-1.5b.save_sharded"]
    assert entry["moves"] == "save_stall_s"
    assert entry["layer"] == "checkpoint writer"


def test_idle_gaps_on_the_chip_trace_unchanged():
    summary = traces.reduce(traces.load(TRACE), kernels=harness.KERNELS)
    got = dict(summary["breakdown"]["idle_gaps"])
    assert got == pytest.approx({
        "bench.save_async": 0.025792898,
        "bench.step": 0.021043847,
        "between ops (gaps under 10 us)": 3.64e-07})


def _ms(a, b):
    return int(a * 1e6), int(b * 1e6)


# main thread (1): a save whose safe point waits for the previous
# write; writer thread (2): that write, digest then file write
SAVE = [
    Rec("safe_point", 1, None, 1, *_ms(0, 100)),
    Rec("snapshot", 2, 1, 1, *_ms(1, 99)),
    Rec("ckpt.save", 3, 2, 1, *_ms(2, 98)),
    Rec("ckpt.wait", 4, 3, 1, *_ms(10, 97)),
    Rec("ckpt.write", 5, None, 2, *_ms(5, 95)),
    Rec("ckpt.digest", 6, 5, 2, *_ms(20, 40)),
    Rec("ckpt.file_write", 7, 5, 2, *_ms(40, 80)),
]


def test_working_span_on_another_thread_labels_a_wait():
    line = program_spans.timeline(program_spans.leaf_segments(SAVE))
    gaps = [_ms(25, 35), _ms(50, 70), _ms(85, 89), _ms(96, 97.5),
            _ms(101, 110), _ms(30, 30.005)]
    by_label, untraced, total = program_spans.label_gaps(gaps, line)
    assert by_label == pytest.approx({
        "mana.ckpt.digest": 0.010,
        "mana.ckpt.file_write": 0.020,
        "mana.ckpt.write": 0.004,       # the writer's own time
        "mana.ckpt.wait": 0.0015,       # only the wait is open there
        program_spans.UNTRACED: 0.009,
        "between ops (gaps under 10 us)": 5e-6})
    assert untraced == pytest.approx(0.009)
    assert total == pytest.approx(sum(by_label.values()))


def test_a_gap_goes_whole_to_the_span_that_covers_most_of_it():
    # 5 ms of digest, 15 ms of file write: the gap is the file write's
    line = program_spans.timeline(program_spans.leaf_segments(SAVE))
    by_label, untraced, _ = program_spans.label_gaps([_ms(35, 55)], line)
    assert by_label == pytest.approx({"mana.ckpt.file_write": 0.020})
    assert untraced == 0


def test_deepest_working_span_wins():
    spans = [Rec("step", 1, None, 1, *_ms(0, 10)),
             Rec("ckpt.write", 2, None, 2, *_ms(0, 10)),
             Rec("ckpt.encode", 3, 2, 2, *_ms(0, 10)),
             Rec("ckpt.base_read", 4, 3, 2, *_ms(2, 8))]
    line = program_spans.timeline(program_spans.leaf_segments(spans))
    assert [x[2] for x in line] == ["ckpt.encode", "ckpt.base_read",
                                    "ckpt.encode"]


def test_idle_by_span_on_the_chip_trace():
    pd = traces.load(TRACE)
    offset = dict(next(p for p in pd.planes
                       if p.name == "Task Environment").stats)[
        "profile_start_time"]
    window = next((a, b) for a, b, n in traces.host_spans(pd)
                  if n == "bench.window")
    assert program_spans.idle_by_span(pd, [])["untraced_idle_share"] == \
        pytest.approx(100)
    # one span over the whole window, on the profile's wall clock
    whole = [Rec("ckpt.write", 1, None, 9, int(offset + window[0]) - 1000,
                 int(offset + window[1]) + 1000)]
    out = program_spans.idle_by_span(pd, whole)
    assert out["untraced_idle_share"] == pytest.approx(0, abs=1e-9)
    summary = traces.reduce(pd)
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(v for _, v in out["idle_gaps_by_program_span"]) == \
        pytest.approx(idle, rel=1e-6)


def test_restore_readings_per_resume():
    from repro.core import tracing
    rec = tracing.Recording()
    rec.spans = [
        Rec("runtime.build", 10, None, 1, *_ms(20, 22)),
        Rec("ckpt.file_read", 13, 12, 1, *_ms(22, 26), {"bytes_read": 8000}),
        Rec("ckpt.verify", 14, 12, 1, *_ms(26, 27), {"h2d_bytes": 8192}),
        Rec("ckpt.decode", 15, 12, 1, *_ms(27, 27.5),
            {"decode_copy_bytes": 4096}),
        Rec("ckpt.restore", 12, 11, 1, *_ms(22, 28),
            {"bytes_read": 8000, "h2d_bytes": 8192}),
        Rec("restore.bind", 17, 11, 1, *_ms(28, 30), {"h2d_bytes": 8000}),
        Rec("restore", 11, None, 1, *_ms(22, 30),
            {"bytes_read": 8000, "h2d_bytes": 16192}),
    ]
    got = program_spans.restore_readings(rec.summary())
    assert got == pytest.approx({
        "restore_file_read_s": 0.004, "restore_verify_s": 0.001,
        "restore_decode_s": 0.0005, "decode_copy_bytes": 4096,
        "restore_read_mb_s": 2.0, "restore_span_s": 0.006,
        "restore_bind_s": 0.002, "runtime_build_s": 0.002,
        "restore_h2d_bytes": 16192})
    # two resumes: the means halve the sums
    rec.spans = rec.spans + [r._replace(id=r.id + 100, parent=(
        r.parent + 100 if r.parent else None)) for r in rec.spans]
    assert program_spans.restore_readings(rec.summary()) == \
        pytest.approx(got)
    assert program_spans.restore_readings({}) == {}


def _tiny_measure(traffic_name):
    import trace_program
    from test_bench_chip import tiny
    traffic = harness.load_json("traffic", traffic_name + ".json")
    cell = next(c for c in BENCH["workloads"]
                if c["traffic"] == traffic_name)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    args = argparse.Namespace(workload=cell["name"], seed=2 ** 31 + 11,
                              seconds=2.0, profile=0)
    return trace_program.measure(
        args, require_tpu=False, cfg_override=tiny(cfg),
        traffic_override=dict(traffic, batch=4, seq_len=64),
        t_start=time.monotonic())


def test_trace_program_splits_the_writes_of_a_tiny_save_run():
    out = _tiny_measure("save_tight")
    p = out["program"]
    import trace_program
    parts = sum(p[m] for m in trace_program.WRITE_METRICS)
    assert 0 < parts <= out["harness"]["write_s"]
    assert parts + p["write_own_s"] == pytest.approx(
        out["harness"]["write_s"])
    assert p["write_base_read_s"] > 0      # the delta write in the drain
    assert p["span_records"] > 0 and "restore_file_read_s" not in p
    assert out["end_to_end"]["save_bytes"] > 0


def test_trace_program_splits_a_tiny_resume():
    out = _tiny_measure("resume")
    p, h = out["program"], out["harness"]
    parts = (p["restore_file_read_s"] + p["restore_verify_s"]
             + p["restore_decode_s"])
    assert 0 < parts <= p["restore_span_s"] <= h["restore_read_s"]
    assert p["restore_decode_s"] > 0
    assert p["decode_copy_bytes"] == 0     # every buffer read is writable
    assert p["runtime_build_s"] + p["restore_bind_s"] <= h[
        "restore_to_device_s"]
    assert p["restore_read_mb_s"] > 0 and p["restore_h2d_bytes"] > 0
    assert "write_base_read_s" not in p
