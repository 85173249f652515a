"""CheckpointManager: roundtrip, integrity, encodings, GC, async, leaves
read from several chunk files into one buffer, images of the earlier
reader — and property-based fuzzing of the `_flatten`/`_rebuild` tree
codec."""
import json
import os
import random
import re
import tarfile

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal env: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

from repro.core import checkpoint
from repro.core.checkpoint import (MANIFEST, CheckpointError,
                                   CheckpointManager, ImageIntegrityError,
                                   _flatten, _rebuild)
from repro.kernels.quantize.ref import dequantize_np, quantize_np


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "params": {"w": rng.randn(64, 32).astype(np.float32),
                   "b": rng.randn(32).astype(np.float32)},
        "opt": {"m": {"w": rng.randn(64, 32).astype(np.float32),
                      "b": rng.randn(32).astype(np.float32)},
                "v": {"w": np.abs(rng.randn(64, 32)).astype(np.float32),
                      "b": np.abs(rng.randn(32)).astype(np.float32)},
                "count": np.int32(7)},
        "step": np.int32(7),
    }


def test_roundtrip_exact(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(7, tree, extra={"data": {"seed": 0, "step": 7}})
    out, extra = mgr.restore()
    assert extra["data"]["step"] == 7
    np.testing.assert_array_equal(out["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(out["opt"]["v"]["b"], tree["opt"]["v"]["b"])
    assert int(out["step"]) == 7


def test_corruption_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    d = mgr.step_dir(1)
    target = [f for f in os.listdir(d) if f.startswith("params.w")][0]
    path = os.path.join(d, target)
    raw = bytearray(open(path, "rb").read())
    raw[100] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(CheckpointError, match="checksum"):
        mgr.restore(1)


def test_quantized_moments_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), quantize_keys=("opt/m", "opt/v"))
    tree = _tree()
    stats = mgr.save(1, tree)
    out, _ = mgr.restore(1)
    # params exact, moments within int8 block quantization error
    np.testing.assert_array_equal(out["params"]["w"], tree["params"]["w"])
    m, m0 = out["opt"]["m"]["w"], tree["opt"]["m"]["w"]
    scale = np.abs(m0).max() / 127
    assert np.abs(m - m0).max() <= scale * 0.51 + 1e-7
    # and the checkpoint actually shrank
    raw = CheckpointManager(str(tmp_path) + "2")
    s2 = raw.save(1, tree)
    assert stats["bytes"] < s2["bytes"]


def test_delta_encoding_roundtrip_and_gc_protection(tmp_path):
    mgr = CheckpointManager(str(tmp_path), delta_keys=("params",), keep=2)
    t1 = _tree(1)
    mgr.save(1, t1)
    t2 = {**t1, "params": {"w": t1["params"]["w"] + 1,
                           "b": t1["params"]["b"]}}
    mgr.save(2, t2)
    out, _ = mgr.restore(2)
    np.testing.assert_array_equal(out["params"]["w"], t2["params"]["w"])
    np.testing.assert_array_equal(out["params"]["b"], t2["params"]["b"])
    # base of the newest delta is protected from GC
    mgr.save(3, t2)
    mgr.save(4, t2)
    assert 1 in mgr.steps() or all(
        "base_step" not in e
        for e in mgr._manifest(mgr.step_dir(mgr.latest_step()))["arrays"].values())


def test_gc_keeps_last_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    for s in range(1, 8):
        mgr.save(s, {"x": np.arange(s, dtype=np.float32)})
    assert mgr.steps() == [5, 6, 7]


def test_async_save_overlaps(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    fut = mgr.save_async(1, _tree())
    stats = fut.result()
    assert stats["bytes"] > 0
    assert mgr.latest_step() == 1


def test_restore_missing_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(CheckpointError):
        mgr.restore()


def test_rewrite_same_step_and_crash_recovery(tmp_path):
    """Re-checkpointing an existing step replaces it, and a crash
    between retiring the old image and committing the new one (the only
    non-atomic window) is recovered at the next manager init."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, {"x": np.zeros(4, np.float32)})
    mgr.save(5, {"x": np.ones(4, np.float32)})  # same step: replaced
    out, _ = mgr.restore(5)
    np.testing.assert_array_equal(out["x"], np.ones(4, np.float32))
    # simulate the mid-dance crash: committed image retired, new one lost
    d = mgr.step_dir(5)
    os.rename(d, os.path.join(str(tmp_path), "retired.ckpt_0000000005"))
    assert CheckpointManager(str(tmp_path)).steps() == [5]  # recovered
    out, _ = CheckpointManager(str(tmp_path)).restore(5)
    np.testing.assert_array_equal(out["x"], np.ones(4, np.float32))


# ---------------------------------------------------------------------------
# leaves of several chunk files: each part is read into one buffer
# ---------------------------------------------------------------------------

CHUNK = 1024  # bytes per chunk file here: the array leaves span several
# the images `_save_stack` wrote, with chunks of CHUNK bytes, before the
# read path read each part into one preallocated buffer
EARLIER_IMAGES = os.path.join(os.path.dirname(__file__), "data",
                              "chunked_images.tar.gz")
# stack -> (manager options, steps saved in order)
STACKS = {
    "raw": ({}, (1,)),
    "xor_delta_chain": ({"delta_keys": ("params",)}, (1, 2, 3)),
    "int8_moments": ({"quantize_keys": ("opt/m",)}, (1,)),
    "compressed": ({"compress": True}, (1,)),
}


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(checkpoint, "CHUNK_BYTES", CHUNK)


def _chunky_tree(seed):
    rng = np.random.RandomState(seed)

    def f32(*shape):
        return rng.randn(*shape).astype(np.float32)

    return {"params": {"w": f32(32, 48), "b": f32(300)},
            "opt": {"m": {"w": f32(32, 48), "b": f32(300)}},
            "step": np.int32(seed)}


def _save_stack(directory, stack):
    opts, steps = STACKS[stack]
    mgr = CheckpointManager(directory, **opts)
    for step in steps:
        mgr.save(step, _chunky_tree(step))
    return mgr


def _expected(stack, step):
    """The leaves a restore of `step` gives, by path: the saved ones,
    the moments through the int8 codec's numpy oracle."""
    want = _flatten(_chunky_tree(step))
    if stack == "int8_moments":
        for path, x in want.items():
            if path.startswith("opt/m"):
                want[path] = dequantize_np(*quantize_np(x), x.shape, x.dtype)
    return want


def _assert_bit_exact(out, want):
    got = _flatten(out)
    assert got.keys() == want.keys()
    for path, x in want.items():
        x = np.asarray(x)
        assert (got[path].dtype, got[path].shape) == (x.dtype, x.shape)
        assert got[path].tobytes() == x.tobytes(), path


@pytest.mark.parametrize("stack", list(STACKS))
def test_leaves_of_several_chunks_round_trip_bit_exactly(tmp_path,
                                                         small_chunks, stack):
    mgr = _save_stack(str(tmp_path), stack)
    step = STACKS[stack][1][-1]
    man = mgr._manifest(mgr.step_dir(step))["arrays"]
    for path in ("params/w", "opt/m/w"):
        assert len([f for f in man[path]["files"] if f["part"] == 0]) > 1
    if stack == "xor_delta_chain":
        assert man["params/w"]["encoding"] == "xor_delta"
    out, _ = mgr.restore(step)
    _assert_bit_exact(out, _expected(stack, step))
    # every leaf is writable and owns its memory
    leaves = list(_flatten(out).values())
    for i, a in enumerate(leaves):
        assert a.flags.writeable
        assert not any(np.shares_memory(a, b) for b in leaves[i + 1:])


@pytest.mark.parametrize("verify", [True, False])
@pytest.mark.parametrize("fault", ["cut_short", "bytes_appended"])
def test_a_chunk_file_of_the_wrong_size_raises(tmp_path, small_chunks,
                                               fault, verify):
    mgr = _save_stack(str(tmp_path), "raw")
    d = mgr.step_dir(1)
    name = mgr._manifest(d)["arrays"]["params/w"]["files"][1]["file"]
    with open(os.path.join(d, name), "r+b") as f:
        if fault == "cut_short":
            f.truncate(CHUNK - 1)
        else:
            f.seek(0, os.SEEK_END)
            f.write(b"\0")
    reader = CheckpointManager(str(tmp_path), verify=verify)
    with pytest.raises(ImageIntegrityError, match=re.escape(name)):
        reader.restore(1)


def test_images_of_the_earlier_reader_restore_and_rewrite_byte_for_byte(
        tmp_path, small_chunks):
    """The image format is unchanged: each image in EARLIER_IMAGES
    restores bit-exactly, and the same saves write it again to the byte
    (its manifest differs only in `written_at`)."""
    with tarfile.open(EARLIER_IMAGES) as tar:
        tar.extractall(tmp_path / "earlier", filter="data")
    for stack, (_, steps) in STACKS.items():
        earlier = CheckpointManager(str(tmp_path / "earlier" / stack))
        again = _save_stack(str(tmp_path / "again" / stack), stack)
        assert earlier.steps() == again.steps() == list(steps)
        for step in steps:
            a, b = earlier.step_dir(step), again.step_dir(step)
            assert sorted(os.listdir(a)) == sorted(os.listdir(b))
            for name in os.listdir(a):
                with open(os.path.join(a, name), "rb") as fa, \
                        open(os.path.join(b, name), "rb") as fb:
                    old, new = fa.read(), fb.read()
                if name == MANIFEST:
                    old, new = json.loads(old), json.loads(new)
                    old.pop("written_at"), new.pop("written_at")
                assert old == new, (stack, step, name)
            out, _ = earlier.restore(step)
            _assert_bit_exact(out, _expected(stack, step))


# ---------------------------------------------------------------------------
# property-based: _flatten/_rebuild over nested trees with PartitionSpec
# leaves — the seed-bug class PR 1 fixed by hand (a P() leaf vanishing /
# a P('data', ...) shredding into per-element paths made elastic restore
# bind arrays replicated), now fuzzed
# ---------------------------------------------------------------------------

def _spec_leaves():
    from jax.sharding import PartitionSpec as P
    return [P(), P("data"), P(None, "model"), P("data", "model"),
            P(("data", "model"))]


def _random_tree(rng, depth):
    """Random nested dict/list/tuple tree with PartitionSpec and scalar
    leaves (what real spec/state trees are made of)."""
    roll = rng.random()
    if depth == 0 or roll < 0.35:
        leaves = _spec_leaves() + [0, 1.5, "ax"]
        return leaves[rng.randrange(len(leaves))]
    n = rng.randint(1, 3)
    if roll < 0.65:
        return {f"k{rng.randrange(6)}{i}": _random_tree(rng, depth - 1)
                for i in range(n)}
    if roll < 0.85:
        return [_random_tree(rng, depth - 1) for _ in range(n)]
    return tuple(_random_tree(rng, depth - 1) for _ in range(n))


def _count_specs(tree):
    from jax.sharding import PartitionSpec
    if isinstance(tree, PartitionSpec):
        return 1
    if isinstance(tree, dict):
        return sum(_count_specs(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(_count_specs(v) for v in tree)
    return 0


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000_000))
def test_property_flatten_round_trip_with_partition_spec_leaves(seed):
    from jax.sharding import PartitionSpec
    rng = random.Random(seed)
    tree = {"root": _random_tree(rng, rng.randint(1, 4))}
    flat = _flatten(tree)
    # every PartitionSpec leaf survives as ONE leaf (never shredded
    # into per-element paths, never vanished when empty)
    n_specs = sum(1 for v in flat.values()
                  if isinstance(v, PartitionSpec))
    assert n_specs == _count_specs(tree)
    # no other tuples survive as leaves: plain tuples/lists shred into
    # indexed paths, ONLY PartitionSpec is a tuple-typed leaf
    assert all(isinstance(v, PartitionSpec) for v in flat.values()
               if isinstance(v, tuple))
    # round trip at the flat level: rebuild + reflatten is the identity
    # (paths AND leaf values; restore() matches state to specs by path)
    assert _flatten(_rebuild(flat)) == flat
