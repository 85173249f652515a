"""The program's spans and byte counters (`repro.core.tracing`): what a
save, a write and a restore record, the per-phase seconds every save
carries in `CheckpointManager.stats`, compiles as child events, and the
profiler annotations on the same clock."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import tracing
from repro.core.checkpoint import MANIFEST, CheckpointManager

STATS_KEYS = {"step", "bytes", "snapshot_s", "write_s"}
PHASES = ("base_read_s", "encode_s", "digest_s", "file_s", "commit_s")
BYTE_KEYS = {"d2h_bytes", "bytes_read", "h2d_bytes"}
WRITE_CHILDREN = {"ckpt.base_read", "ckpt.encode", "ckpt.digest",
                  "ckpt.file_write", "ckpt.commit"}


def _state(seed=0):
    rng = np.random.RandomState(seed)
    return {"params": {"w": rng.randn(300, 70).astype(np.float32),
                       "b": rng.randn(70).astype(np.float32)},
            "opt": {"m": rng.randn(300, 70).astype(np.float32)},
            "step": np.int32(seed)}


def _descendants(spans, root):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    out, todo = [], [root.id]
    while todo:
        for s in kids.get(todo.pop(), []):
            out.append(s)
            todo.append(s.id)
    return out


def _payload_bytes(directory):
    with open(os.path.join(directory, MANIFEST)) as f:
        man = json.load(f)
    return sum(os.path.getsize(os.path.join(directory, fm["file"]))
               for e in man["arrays"].values() for fm in e["files"])


def _two_saves(tmp_path, **kw):
    mgr = CheckpointManager(str(tmp_path), delta_keys=("params",), **kw)
    mgr.save(1, _state(1))
    mgr.save(2, _state(2))        # params as XOR deltas against step 1
    return mgr


def test_recording_off_records_nothing_and_stats_keep_their_keys(tmp_path):
    with tracing.recording() as rec:
        pass
    mgr = _two_saves(tmp_path)
    mgr.restore()
    assert rec.spans == [] and tracing._active == ()
    for st, step in zip(mgr.stats, (1, 2)):
        assert STATS_KEYS | set(PHASES) | BYTE_KEYS == set(st)
        assert st["step"] == step
        assert st["bytes"] == _payload_bytes(mgr.step_dir(step))
        assert st["snapshot_s"] >= 0 and st["d2h_bytes"] == 0  # host state
        assert st["h2d_bytes"] == 0                    # the numpy codecs
        # the phases lie inside the write they split
        assert 0 <= sum(st[k] for k in PHASES) <= st["write_s"] + 1e-4
    assert mgr.stats[0]["base_read_s"] == 0 < mgr.stats[1]["base_read_s"]
    assert mgr.stats[0]["bytes_read"] == 0 < mgr.stats[1]["bytes_read"]


def test_delta_write_has_five_phases_that_partition_it(tmp_path):
    mgr = CheckpointManager(str(tmp_path), delta_keys=("params",))
    mgr.save(1, _state(1))
    with tracing.recording() as rec:
        for step in (2, 3, 4):           # three delta writes
            mgr.save(step, _state(step))
    writes = [s for s in rec.spans if s.name == "ckpt.write"]
    child_ns = {}
    for s in rec.spans:
        child_ns[s.parent] = child_ns.get(s.parent, 0) + s.end_ns - s.start_ns
    uncovered = []
    for write, st in zip(writes, mgr.stats[1:]):
        below = _descendants(rec.spans, write)
        assert WRITE_CHILDREN <= {s.name for s in below}
        assert {s.name for s in below if s.parent == write.id} == \
            WRITE_CHILDREN - {"ckpt.base_read"}
        # base_read nests in encode, and holds the base's read and verify
        enc_ids = {s.id for s in below if s.name == "ckpt.encode"}
        base = [s for s in below if s.name == "ckpt.base_read"]
        assert base and all(s.parent in enc_ids for s in base)
        under_base = {x.name for b in base
                      for x in _descendants(rec.spans, b)}
        assert {"ckpt.file_read", "ckpt.verify", "ckpt.decode"} <= under_base
        # self times of the write's tree add up to its duration
        self_ns = sum(s.end_ns - s.start_ns - child_ns.get(s.id, 0)
                      for s in [write] + below)
        assert self_ns == write.end_ns - write.start_ns
        phases = sum(st[k] for k in PHASES)
        assert abs(st["write_s"] - write.seconds) < 1e-3
        uncovered.append(write.seconds - phases)
    # the five phases leave under 1 ms of a write uncovered (the best of
    # three, so that a descheduled process does not decide it)
    assert min(uncovered) < 1e-3 and min(uncovered) >= 0, uncovered
    summary = rec.summary()
    assert summary["ckpt.digest"]["total_s"] == pytest.approx(
        sum(st["digest_s"] for st in mgr.stats[1:]), abs=1e-5)
    assert summary["ckpt.encode"]["self_s"] == pytest.approx(
        sum(st["encode_s"] for st in mgr.stats[1:]), abs=1e-5)


def test_byte_counters_match_the_image(tmp_path):
    mgr = CheckpointManager(str(tmp_path), delta_keys=("params",))
    mgr.save(1, _state(1))
    with tracing.recording() as rec:
        mgr.save(2, _state(2))
        out, _ = mgr.restore(2)
    (write,) = [s for s in rec.spans if s.name == "ckpt.write"]
    (restore,) = [s for s in rec.spans if s.name == "ckpt.restore"]
    assert write.counts["bytes_written"] == mgr.stats[-1]["bytes"]
    base_read = sum(s.counts.get("bytes_read", 0) for s in rec.spans
                    if s.name == "ckpt.base_read")
    assert write.counts["bytes_read"] == base_read > 0
    assert mgr.stats[-1]["bytes_read"] == base_read
    # a delta image's restore reads its own files and its base's
    # params (what the delta was taken against)
    assert restore.counts["bytes_read"] == (_payload_bytes(mgr.step_dir(2))
                                            + base_read)
    np.testing.assert_array_equal(out["params"]["w"], _state(2)["params"]["w"])
    # every record is a registered span, with registered counters
    counters = {c for _, cs in tracing.SPANS.values() for c in cs}
    for s in rec.spans:
        assert s.name in tracing.SPANS and set(s.counts) <= counters, s


def test_device_bytes_counted_at_the_kernels(tmp_path):
    """With the kernels on, the digest and the XOR delta count the words
    they upload; the runtime's snapshot counts what it copies back."""
    import jax.numpy as jnp
    mgr = CheckpointManager(str(tmp_path), delta_keys=("params",),
                            use_pallas=True)
    mgr.save(1, _state(1))
    state = _state(2)
    with tracing.recording() as rec:
        mgr.save(2, {"params": {k: jnp.asarray(v)
                                for k, v in state["params"].items()},
                     "opt": state["opt"], "step": state["step"]})
    (write,) = [s for s in rec.spans if s.name == "ckpt.write"]
    (d2h,) = [s for s in rec.spans if s.name == "ckpt.d2h"]
    params = sum(v.nbytes for v in state["params"].values())
    assert d2h.counts["d2h_bytes"] == params == mgr.stats[-1]["d2h_bytes"]
    digest = sum(s.counts["h2d_bytes"] for s in rec.spans
                 if s.name == "ckpt.digest")
    assert digest >= mgr.stats[-1]["bytes"]
    assert write.counts["h2d_bytes"] >= digest + 2 * params
    assert mgr.stats[-1]["h2d_bytes"] == write.counts["h2d_bytes"]


@pytest.mark.parametrize("compress", [False, True])
def test_restore_reads_into_one_buffer_and_counts_its_copies(tmp_path,
                                                             compress):
    """A restore records no join, reads the image's payload bytes, and
    copies after the read only a read-only buffer: the raw leaves of a
    compressed image, counted as `decode_copy_bytes`."""
    mgr = CheckpointManager(str(tmp_path), compress=compress)
    mgr.save(1, _state(1))
    with tracing.recording() as rec:
        mgr.restore(1)
    assert "ckpt.join" not in {s.name for s in rec.spans}
    (restore,) = [s for s in rec.spans if s.name == "ckpt.restore"]
    assert restore.counts["bytes_read"] == _payload_bytes(mgr.step_dir(1))
    state = _state(1)
    leaves = [*state["params"].values(), state["opt"]["m"], state["step"]]
    copied = sum(x.nbytes for x in leaves) if compress else 0
    summary = rec.summary()
    assert summary["ckpt.decode"]["counts"].get("decode_copy_bytes",
                                                0) == copied
    assert restore.counts.get("decode_copy_bytes", 0) == copied


def test_compile_is_a_child_event_and_a_cached_call_records_none():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 5 - 2)
    x = jnp.arange(11.0)
    with tracing.recording() as rec:
        with tracing.span("step"):
            f(x).block_until_ready()
    (step,) = [s for s in rec.spans if s.name == "step"]
    compiles = [s for s in rec.spans if s.name == "compile"]
    assert compiles and all(c.parent == step.id for c in compiles)
    assert all(step.start_ns <= c.start_ns <= c.end_ns <= step.end_ns + 10**6
               for c in compiles)
    assert rec.summary()["step"]["self_s"] < rec.summary()["step"]["total_s"]
    with tracing.recording() as again:
        with tracing.span("step"):
            f(x).block_until_ready()
    assert [s.name for s in again.spans] == ["step"]


def test_nested_recordings_and_threads():
    import threading
    with tracing.recording() as outer:
        with tracing.span("ckpt.save"):
            with tracing.recording() as inner:
                t = threading.Thread(target=lambda: tracing.span(
                    "ckpt.write").__enter__().__exit__(None, None, None))
                t.start()
                t.join(10)
                assert not t.is_alive()
    assert [s.name for s in inner.spans] == ["ckpt.write"]
    # a span on another thread is a root there, and counts nothing here
    assert inner.spans[0].parent is None
    assert [s.name for s in outer.spans] == ["ckpt.write", "ckpt.save"]


def test_runtime_save_and_restore_spans(tmp_path):
    from repro.configs import ARCHS, reduced_config
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.core.runtime import MANARuntime
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rc = RunConfig(model=cfg, shape=ShapeConfig("doc", 32, 2, "train"))
    with tracing.recording() as rec:
        rt = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path), ckpt_every_steps=2)
        rt.initialize()
        rt.run(2)
        rt.close()
        rt2 = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path))
        assert rt2.restore() == 2
        rt2.close()
    by_id = {s.id: s for s in rec.spans}

    def kids(name):
        (s,) = [s for s in rec.spans if s.name == name]
        return s, {c.name for c in rec.spans if c.parent == s.id}

    sp, below = kids("safe_point")
    assert {"park", "drain", "snapshot", "commit"} <= below
    (save,) = [s for s in rec.spans if s.name == "ckpt.save"]
    assert by_id[save.parent].name == "snapshot"
    assert save.counts["d2h_bytes"] == rt.ckpt.stats[-1]["d2h_bytes"] > 0
    res, below = kids("restore")
    assert below == {"ckpt.restore", "restore.bind"}
    (bind,) = [s for s in rec.spans if s.name == "restore.bind"]
    assert bind.counts["h2d_bytes"] == save.counts["d2h_bytes"]
    builds = [s for s in rec.spans if s.name == "runtime.build"]
    steps = [s for s in rec.spans if s.name == "step"]
    assert len(builds) == 2 and len(steps) == 2
    assert any(s.name == "compile" and by_id.get(s.parent) in steps
               for s in rec.spans)


def test_spans_land_in_the_profiler_trace_on_the_same_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData
    mgr = CheckpointManager(str(tmp_path / "ckpt"), delta_keys=("params",))
    mgr.save(1, _state(1))
    with jax.profiler.trace(str(tmp_path / "trace")):
        with tracing.recording() as rec:
            mgr.save(2, _state(2))
            mgr.restore(2)
    path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    env = dict(next(p for p in pd.planes
                    if p.name == "Task Environment").stats)
    t0 = env["profile_start_time"]
    events = []        # (start_ns, end_ns, name, line) on the wall clock
    for plane in pd.planes:
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("mana."):
                    events.append((t0 + e.start_ns, t0 + e.end_ns, e.name,
                                   (plane.name, li)))
    by_id = {s.id: s for s in rec.spans}
    assert len(events) == len(rec.spans)

    def match(s):
        near = [e for e in events if e[2] == "mana." + s.name
                and abs(e[0] - s.start_ns) < 1e6]
        assert len(near) >= 1, s
        return min(near, key=lambda e: abs(e[0] - s.start_ns))

    for s in rec.spans:
        e = match(s)
        if s.parent is None:
            continue
        p = match(by_id[s.parent])
        # the parent's event encloses the child's, on the same line
        assert p[3] == e[3] and p[0] <= e[0] and e[1] <= p[1], (s, e, p)


def test_checkpoint_and_tracing_import_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "import repro.core.tracing, repro.core.checkpoint\n"
            "import repro.core.two_phase_commit\n"
            "from repro.core import tracing\n"
            "with tracing.span('ckpt.write') as w:\n"
            "    with tracing.span('ckpt.digest'):\n"
            "        tracing.count('h2d_bytes', 3)\n"
            "assert w.counts == {'h2d_bytes': 3} and w.total('ckpt.digest') >= 0\n"
            "print('ok')\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"),
         os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def _program_source():
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "repro")
    text = []
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    text.append(fh.read())
    return "\n".join(text)


@pytest.mark.parametrize("name", sorted(set(tracing.SPANS) - {"compile"}))
def test_every_registered_span_is_opened_by_the_program(name):
    """The registry names no span the program has stopped opening."""
    assert f'tracing.span("{name}")' in _program_source()
