"""Chip smoke run: checkpointed training at published widths on a TPU.

    python chip_smoke.py              # one chip: qwen2-0.5b
    python chip_smoke.py --chips 4    # four chips: qwen2-1.5b, elastic mesh

One chip.  `MANARuntime` trains unreduced qwen2-0.5b (f32 params +
AdamW, bf16 compute, batch 8 x seq 2048) with the Pallas kernels
compiled (`use_pallas=True`) and XOR-delta params.  It saves through the
2PC safe point every 2 steps (a full image, then a delta), keeps
training as the uninterrupted reference, then restores the newest save
into a fresh runtime in this same process.  The restored state must be
bit-identical to a host copy taken at that save, and the resumed losses
bit-identical to the reference's.  The quantize kernel runs on one
full-width Adam moment leaf against `quantize_np`.

Four chips.  qwen2-1.5b (about 18.6 GB of f32 state, more than one chip
holds) trains on a (data 2, model 2) mesh with ZeRO-1 moments, saves,
and restores onto (data 1, model 4).  The gathered state must be
bit-identical to the saved one, and the losses within rtol 5e-3 of the
uninterrupted (2, 2) run (bf16 reductions differ across factorizations).

Every number printed is a smoke-run reading, not a benchmark.  The last
line is the JSON result.  Without a TPU the script exits non-zero and
prints no result.  Checkpoints go to a temp directory, removed at exit.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.configs.base import RunConfig, ShapeConfig  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.checkpoint import MANIFEST  # noqa: E402
from repro.core.runtime import MANARuntime  # noqa: E402

BATCH, SEQ = 8, 2048
LOSS_RTOL_ACROSS_MESHES = 5e-3


def run_config(cfg, batch: int = BATCH, seq: int = SEQ) -> RunConfig:
    return RunConfig(model=cfg, shape=ShapeConfig("chip_smoke", seq, batch,
                                                  "train"),
                     loss_chunk=min(512, seq), attn_chunk=min(512, seq))


def host_copy(state):
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)


def state_mismatches(state, host) -> list:
    """Paths whose restored bytes differ from the host copy."""
    assert jax.tree.structure(state) == jax.tree.structure(host)
    bad = []
    for (path, leaf), ref in zip(jax.tree_util.tree_leaves_with_path(state),
                                 jax.tree.leaves(host)):
        got = np.asarray(jax.device_get(leaf))
        if (got.dtype != ref.dtype or got.shape != ref.shape
                or not np.array_equal(got.reshape(-1).view(np.uint8),
                                      ref.reshape(-1).view(np.uint8))):
            bad.append(jax.tree_util.keystr(path))
    return bad


def device_bytes(state) -> dict:
    """Bytes of the state held on each device (addressable shards)."""
    out: dict = {}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + \
                shard.data.nbytes
    return out


def save_encodings(ckpt) -> dict:
    """step -> number of arrays stored as XOR deltas in that image."""
    out = {}
    for s in ckpt.steps():
        with open(os.path.join(ckpt.step_dir(s), MANIFEST)) as f:
            man = json.load(f)
        out[s] = sum("base_step" in e for e in man["arrays"].values())
    return out


def train_and_save(cfg, rc, ckpt_dir, *, mesh=None, every=2, saves=2,
                   tail_steps=3, use_pallas=False, delta_params=False,
                   seed=0) -> dict:
    """Train `every * saves` steps saving every `every`, take a host copy
    of the state at the last save, then keep training `tail_steps` with
    checkpointing off: the uninterrupted reference.

    Step times are wall clock between the ends of consecutive steps
    (`block_until_ready`), leaving out those that also held a save's
    safe point or the wait for its write.  A step is "clean" unless it
    compiled or began while a save's background write was still in
    flight; those are reported apart."""
    with tracing.recording() as rec:
        rt = MANARuntime(cfg, rc, ckpt_dir=ckpt_dir, mesh=mesh,
                         ckpt_every_steps=every, use_pallas=use_pallas,
                         delta_params=delta_params, seed=seed)
        rt.initialize()
        jax.block_until_ready(rt.state)
        spread = device_bytes(rt.state)
        marks = []

        def on_metrics(step, m):
            jax.block_until_ready(rt.state)
            marks.append((time.monotonic(), rt.checkpoints_taken,
                          rt.ckpt.writing(), len(rec.compiles())))
            if "moe_tokens_held" in m:   # a dropless expert layer's counters
                print(f"smoke: step {step} moe_tokens_held "
                      f"{m['moe_tokens_held']:.0f} moe_max_load "
                      f"{m['moe_max_load']:.3f}", flush=True)

        c0, t0 = len(rec.compiles()), time.monotonic()
        rt.run(1, on_metrics=on_metrics)
        first_step_s = time.monotonic() - t0
        first = rec.compiles()[c0:]
        c1 = len(rec.compiles())
        rt.run(every * saves - 1, on_metrics=on_metrics)
        compiles_in_saves = len(rec.compiles()) - c1
        host = host_copy(rt.state)
        segment = len(marks)
        rt.ckpt_every_steps = None
        rt.run(tail_steps, on_metrics=on_metrics)
    # a save's stall is its whole safe point: park, drain, snapshot
    # (the D2H and any wait for the previous write) and commit
    saving = {s.parent for s in rec.spans if s.name == "snapshot"}
    stalls = [s.seconds for s in rec.spans
              if s.name == "safe_point" and s.id in saving]
    steps: dict = {"clean": [], "writer": [], "compiling": []}
    for i, (a, b) in enumerate(zip(marks, marks[1:])):
        if a[1] != b[1] or i + 1 == segment:
            continue
        # the writer compiles its kernels on first use: a step begun
        # during a write counts as "writer" whatever compiled in it
        kind = ("writer" if a[2] else "compiling" if b[3] > a[3]
                else "clean")
        steps[kind].append(b[0] - a[0])
    out = {
        "saved_step": int(host["step"]),
        "host": host,
        "ref_losses": [h["loss"] for h in rt.history[-tail_steps:]],
        "first_step_s": first_step_s,
        "compile_s": sum(c.seconds for c in first),
        "compiles_first_step": len(first),
        "compiles_in_saves": compiles_in_saves,
        "step_times": steps,
        "stalls": stalls,
        "save_bytes": [s["bytes"] for s in rt.ckpt.stats],
        "delta_arrays": save_encodings(rt.ckpt),
        "spread": spread,
    }
    rt.close()
    rt.state = None
    del rt
    gc.collect()
    return out


def restore_and_resume(cfg, rc, ckpt_dir, saved, *, mesh=None,
                       use_pallas=False, seed=0) -> dict:
    """Restore the newest save into a fresh runtime, check it against the
    host copy, and train as many steps as the reference did after it."""
    rt = MANARuntime(cfg, rc, ckpt_dir=ckpt_dir, mesh=mesh,
                     use_pallas=use_pallas, seed=seed)
    t0 = time.monotonic()
    start = rt.restore()
    jax.block_until_ready(rt.state)
    restore_s = time.monotonic() - t0
    mismatched = state_mismatches(rt.state, saved["host"])
    spread = device_bytes(rt.state)
    hist = rt.run(len(saved["ref_losses"]))
    out = {"start": start, "restore_s": restore_s, "mismatched": mismatched,
           "losses": [h["loss"] for h in hist], "spread": spread}
    rt.close()
    rt.state = None
    del rt
    gc.collect()
    return out


def kernel_check(leaf: np.ndarray, on_tpu: bool) -> dict:
    """Every kernel of the checkpoint path on one full-width leaf, through
    the host entry points with use_pallas, bit for bit against its numpy
    oracle; on a TPU each must compile to a Mosaic custom call."""
    import jax.numpy as jnp

    from repro.kernels import host_words
    from repro.kernels.checksum import ops as cops
    from repro.kernels.checksum.ref import BLOCK, checksum_np
    from repro.kernels.delta import ops as dops
    from repro.kernels.delta.ref import DBLOCK, delta_np
    from repro.kernels.quantize import ops as qops
    from repro.kernels.quantize import ref as qref

    flipped = leaf.copy()
    flipped.reshape(-1).view(np.uint32)[::4099] ^= 1
    q1, s1, p1 = qops.quantize_host(leaf, use_pallas=True)
    q2, s2, p2 = qref.quantize_np(leaf)
    print(f"smoke: quantize kernel vs quantize_np: {int((q1 != q2).sum())} "
          f"of {q2.size} codes and {int((s1 != s2).sum())} of {s2.size} "
          f"scales differ", flush=True)
    same = {
        "checksum": cops.checksum_host(leaf, True) == checksum_np(leaf),
        "delta": np.array_equal(dops.delta_host(flipped, leaf, True),
                                delta_np(flipped, leaf)),
        "quantize": (np.array_equal(q1, q2) and np.array_equal(s1, s2)
                     and p1 == p2),
    }
    words = jnp.asarray(host_words(leaf, BLOCK))
    dwords = jnp.asarray(host_words(leaf, DBLOCK))
    hlo = {
        "checksum": cops.checksum_words.lower(words).compile().as_text(),
        "delta": dops.delta_words.lower(dwords, dwords).compile().as_text(),
        "quantize": qops.quantize.lower(
            jnp.asarray(leaf)).compile().as_text(),
    }
    custom = {k: "tpu_custom_call" in v for k, v in hlo.items()}
    for name in same:
        assert same[name], f"{name} kernel differs from its numpy oracle"
        assert custom[name] == on_tpu, (name, custom[name])
    return {"same": same, "custom_call": custom, "elements": leaf.size}


def check_spread(a, b, n_devices: int = 4) -> None:
    """The meshed state sits on every device, none holding half of it."""
    total = sum(x.nbytes for x in jax.tree.leaves(a["host"]))
    for name, spread in (("(2,2)", a["spread"]), ("(1,4)", b["spread"])):
        line = ", ".join(f"dev{d}={n}" for d, n in sorted(spread.items()))
        print(f"smoke: state bytes per device on {name}: {line} "
              f"(state {total})")
        assert len(spread) == n_devices and min(spread.values()) > 0, spread
        assert max(spread.values()) < total / 2, spread


def one_chip() -> None:
    cfg = ARCHS["qwen2-0.5b"]
    rc = run_config(cfg)
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        print(f"smoke: checkpoint dir free bytes "
              f"{shutil.disk_usage(tmp).free}", flush=True)
        a = train_and_save(cfg, rc, tmp, use_pallas=True, delta_params=True)
        report_train(cfg, rc, a)
        b = restore_and_resume(cfg, rc, tmp, a, use_pallas=True)
        report_restore(a, b, exact_losses=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    k = kernel_check(a["host"]["opt"]["v"]["embed"]["embedding"], on_tpu=True)
    print(f"smoke: kernels on opt/v/embed/embedding ({k['elements']} f32): "
          f"bit-identical {k['same']}, tpu_custom_call {k['custom_call']}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"smoke: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def four_chip() -> None:
    from repro.launch.mesh import make_mesh
    cfg = ARCHS["qwen2-1.5b"]
    rc = run_config(cfg)
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        print(f"smoke: checkpoint dir free bytes "
              f"{shutil.disk_usage(tmp).free}", flush=True)
        a = train_and_save(cfg, rc, tmp, mesh=make_mesh((2, 2),
                                                        ("data", "model")),
                           saves=1)
        report_train(cfg, rc, a)
        b = restore_and_resume(cfg, rc, tmp, a,
                               mesh=make_mesh((1, 4), ("data", "model")))
        report_restore(a, b, exact_losses=False)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    check_spread(a, b)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"smoke: dev{d.id} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')}")


def report_train(cfg, rc, a) -> None:
    shp = rc.shape
    print(f"smoke: {cfg.arch_id} d_model {cfg.d_model} x {cfg.n_layers} "
          f"layers, vocab {cfg.vocab_size}, params {cfg.param_count()}, "
          f"batch {shp.global_batch} x seq {shp.seq_len}")
    print(f"smoke: first step {a['first_step_s']:.3f} s, compile "
          f"{a['compile_s']:.3f} s in {a['compiles_first_step']} compiles")
    st = a["step_times"]
    print(f"smoke: median clean step {statistics.median(st['clean']):.4f} s "
          f"over {len(st['clean'])} steps {st['clean']}; steps begun during "
          f"a background write {st['writer']}; steps that compiled "
          f"{st['compiling']}")
    print(f"smoke: stall per save (s) {a['stalls']}, bytes per save "
          f"{a['save_bytes']}, delta arrays per save {a['delta_arrays']}")
    print(f"smoke: compiles during the saves {a['compiles_in_saves']}",
          flush=True)
    assert len(a["save_bytes"]) >= 1 and all(a["save_bytes"])


def report_restore(a, b, *, exact_losses: bool) -> None:
    print(f"smoke: restore {b['restore_s']:.3f} s from step {b['start']}")
    ref, got = np.asarray(a["ref_losses"]), np.asarray(b["losses"])
    print(f"smoke: losses reference {a['ref_losses']} resumed {b['losses']} "
          f"max abs diff {float(np.max(np.abs(ref - got)))}", flush=True)
    assert b["start"] == a["saved_step"], (b["start"], a["saved_step"])
    assert not b["mismatched"], f"restored state differs: {b['mismatched']}"
    if exact_losses:
        assert len(a["stalls"]) >= 2 and any(a["delta_arrays"].values()), \
            a["delta_arrays"]
        assert a["ref_losses"] == b["losses"], "resumed losses differ"
    else:
        np.testing.assert_allclose(got, ref, rtol=LOSS_RTOL_ACROSS_MESHES)
    assert np.all(np.isfinite(got))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache(REPO)
    dev = devices[0]
    print(f"smoke: device {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"compile cache {cache}", flush=True)
    with tracing.recording() as rec:
        (four_chip if args.chips == 4 else one_chip)()
    events = [s.counts for s in rec.spans if s.name == "compile"]
    print(f"smoke: persistent cache hits "
          f"{sum(c['cache_hit'] for c in events)}, misses "
          f"{sum(c['cache_miss'] for c in events)}, compiles "
          f"{len(rec.compiles())}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
