"""CPU tests of the chip benchmark's harness.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests

They check what a CPU can: the files load by name (and a family module
or a mesh that does not fit is refused), the counts, the trace
reduction on a trace recorded on a TPU v5e, that the command refuses a
CPU, and each traffic kind's control flow at a tiny size with
interpret-mode kernels, with and without a fault planted underneath,
the four-chip cell on four CPU devices.  No test describes a TPU
topology.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

import counts  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402
import traces  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BENCH_CFG = harness.load_json("configs", "qwen2-0.5b.json")
TRACE = os.path.join(CHIP, "testdata", "v5e_small.xplane.pb")


def tiny(cfg, limits=None):
    """A tiny configuration of the same family (padded heads and the
    mesh kept)."""
    return dict(cfg, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=256,
                layout=dict(cfg["layout"], qkv_bias=True, head_pad_to=8),
                limits=limits or TINY_LIMITS)


# limits for the tiny size, set as the cells' are: above what the
# program reads there (loss 5e-5, change 2.3e-3 on seeds 1, 2) and
# below the fp8 control's loss gap (5e-4 .. 8e-4)
TINY_LIMITS = {"loss_gap": 2e-4, "change_gap": 1e-2}
# the four-device run's, set the same way from the `save_sparse` mix at
# the tiny size: the program's `change_gap` reads 3.6e-3 .. 1.31e-2 on
# seeds 2**31 + 7, 5 and 11, alike on one device and on a (2,2) mesh,
# where the half batch reads 2.2e-2 .. 4.1e-2 (seeds 1-3 on the mesh);
# the fp8 control (6.7e-4 .. 8.8e-4) and the half batch fail `loss_gap`
TINY_SHARDED_LIMITS = {"loss_gap": 2e-4, "change_gap": 2e-2}


def test_files_load_by_name():
    names = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]}
    for cfg in BENCH["configs"]:
        c = json.load(open(os.path.join(ROOT, cfg["file"])))
        assert c["name"] == cfg["name"]
        assert harness.reference(c).param_shapes(c)
    for cell in BENCH["workloads"]:
        got, cfg, traffic, _ = bench_run.cell_files(cell["name"], ROOT)
        assert got == cell and traffic["name"] == cell["traffic"]
        assert cfg["name"] == cell["config"]
    for m in BENCH["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
        assert m["moves"] in names
    assert "TPU v5 lite" in json.load(open(os.path.join(CHIP, "peaks.json")))[
        "devices"]
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


@pytest.mark.parametrize("name,flops_per_step,state", [
    ("qwen2-0.5b", 52_887_690_412_032, 5_994_490_376),
    ("qwen2-1.5b-l6", 52_396_453_527_552, 6_283_229_192),
    ("qwen2-1.5b", 160_397_701_152_768, 19_053_225_992),
])
def test_counts(name, flops_per_step, state):
    cfg = harness.load_json("configs", name + ".json")
    per_step = counts.train_flops_per_token(cfg, 2048) * 8 * 2048
    assert per_step == flops_per_step
    assert counts.state_bytes(cfg) == state
    import jax
    from repro.training.step import abstract_train_state
    model, rc = harness.program_config(cfg, {"name": "t", "batch": 8,
                                             "seq_len": 2048})
    st = abstract_train_state(model, rc)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(st)) == state


def test_a_family_without_a_function_is_refused(tmp_path, monkeypatch):
    """A configuration whose family module lacks `program_model` fails
    as it is loaded, and the error names the function."""
    src = open(os.path.join(CHIP, "references", "qwen2.py")).read()
    head, rest = src.split("def program_model(", 1)
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "qwen2_partial.py").write_text(
        head + rest[rest.index("\ndef "):])
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    cfg = dict(BENCH_CFG, reference="qwen2_partial")
    with pytest.raises(AttributeError, match="lacks program_model$"):
        harness.reference(cfg)


def test_a_cell_whose_chips_differ_from_its_mesh_is_refused(tmp_path):
    bench = dict(BENCH, workloads=[
        dict(c, chips=1) if c["config"] == "qwen2-1.5b" else
        dict(c, chips=4) for c in BENCH["workloads"]])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for cell in bench["workloads"]:
        with pytest.raises(SystemExit, match="asks for"):
            bench_run.cell_files(cell["name"], str(tmp_path))
    for cell in BENCH["workloads"]:
        assert bench_run.cell_files(cell["name"], ROOT)[0] == cell


def test_union_and_bytes():
    assert traces.union([(0, 2), (1, 3), (5, 6), (7, 9)], 0, 8) == \
        [(0, 3), (5, 6), (7, 8)]
    hlo = ("%checksum_block_sums.1 = (s32[256,1]{1,0:T(8,128)S(1)}, "
           "s32[256,1]{1,0:T(8,128)S(1)}) custom-call(u32[256,2048]"
           "{1,0:T(8,128)} %words.1), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={u32[256,2048]{1,0}}")
    assert traces.operand_bytes(hlo) == 2 * 256 * 4 + 256 * 2048 * 4
    assert traces.operand_bytes("%x = token[] after-all()") is None


def test_trace_reduction_on_a_chip_trace():
    """A trace recorded on a TPU v5e: three steps of a small jitted
    program, then one digest and one XOR delta, inside bench spans."""
    summary = traces.reduce(traces.load(TRACE), kernels=harness.KERNELS)
    known = json.load(open(TRACE + ".json"))
    for key in ("busy_s", "window_s"):
        assert summary[key] == pytest.approx(known[key], rel=1e-9)
    assert 0 < summary["busy_s"] < summary["window_s"]
    for name, k in known["kernels"].items():
        assert summary["kernels"][name] == pytest.approx(k)
    assert len(summary["breakdown"]["device_ops"]) <= 10
    assert summary["breakdown"]["idle_gaps"]


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# ---------------------------------------------------------------------------
# each traffic kind, tiny, on the CPU
# ---------------------------------------------------------------------------

def rehearse(traffic_name, fault=None, seconds=2.0, seed=2 ** 31 + 7,
             cell=None, limits=None):
    """One run of a traffic mix, tiny, through a cell of the benchmark
    (by default the first whose mix is of the same kind), its
    configuration made tiny."""
    traffic = harness.load_json("traffic", traffic_name + ".json")
    cell = cell or next(c["name"] for c in BENCH["workloads"]
                        if harness.load_json("traffic", c["traffic"] + ".json")
                        ["kind"] == traffic["kind"])
    _, cfg, _, _ = bench_run.cell_files(cell, ROOT)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    return bench_run.run_cell(
        args, require_tpu=False, cfg_override=tiny(cfg, limits),
        traffic_override=dict(traffic, batch=4, seq_len=64),
        fault=fault, t_start=time.monotonic())


# every traffic mix in traffic/, those of no cell yet included
KINDS = sorted(f[:-5] for f in os.listdir(os.path.join(CHIP, "traffic"))
               if f.endswith(".json"))


@pytest.mark.parametrize("traffic", KINDS)
def test_each_traffic_kind_runs_and_is_correct(traffic):
    res = rehearse(traffic, seconds=3.0)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["setup_s"]["value"] > 0
    if traffic == "resume":
        assert m["resume_s"]["value"] > 0
        assert res["checks"]["restore_mismatch"]["value"] == 0
    else:
        assert m["tokens_per_s"]["value"] > 0
    if traffic.startswith("save"):
        assert m["save_bytes"]["value"] > 0
        assert res["checks"]["image_mismatch"]["value"] == 0
    assert list(res)[-1] == "checks"


def _unchanged(rt):
    inner = rt.lower.train_step
    rt.lower.train_step = lambda state, b: (state, inner(state, b)[1])


def _half_batch(rt):
    inner = rt.lower.train_step
    rt.lower.train_step = lambda state, b: inner(
        state, {k: v[: v.shape[0] // 2] for k, v in b.items()})


def _altered_image(rt):
    inner = rt.ckpt.save_async

    def save_async(step, state, *a, **kw):
        state = dict(state, params=dict(state["params"],
                                        ln_f=state["params"]["ln_f"] + 1.0))
        return inner(step, state, *a, **kw)

    rt.ckpt.save_async = save_async


def _altered_restore(rt):
    inner = rt.ckpt.restore

    def restore(*a, **kw):
        tree, extra = inner(*a, **kw)
        tree["params"]["ln_f"] = tree["params"]["ln_f"] + 1.0
        return tree, extra

    rt.ckpt.restore = restore


FAULTS = [(t, f) for t in KINDS
          for f in (_unchanged, _half_batch,
                    _altered_restore if t == "resume" else _altered_image)
          if not (f is _altered_image and t == "no_ckpt")]


@pytest.mark.parametrize("traffic,fault", FAULTS,
                         ids=[f"{t}-{f.__name__[1:]}" for t, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(traffic, fault):
    from repro.core.codec import CheckpointError
    try:
        res = rehearse(traffic, fault=fault)
    except CheckpointError:
        # a state that never advances saves one step twice, and the
        # program's delta of that step against itself cannot be read:
        # the run ends without a result, which fails it as well
        assert fault is _unchanged and traffic.startswith("save")
        return
    assert res["correct"] is False, res["checks"]


# the four-chip cell, tiny, on a (2,2) mesh of four CPU devices, once
# clean and once with each fault it can have planted underneath, all in
# one process of its own (the mesh needs XLA_FLAGS before jax starts)
SHARDED_CELL = next(c["name"] for c in BENCH["workloads"] if c["chips"] == 4)
SHARDED_FAULTS = ("unchanged", "half_batch", "altered_image")
SHARDED = """
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import test_bench_chip as t
from repro.core.codec import CheckpointError
for name in sys.argv[3:]:
    try:
        res = t.rehearse("save_sparse", seconds=4.0, cell=sys.argv[2],
                         limits=t.TINY_SHARDED_LIMITS,
                         fault=None if name == "none" else getattr(t, "_" + name))
        out = {"correct": res["correct"], "checks": res["checks"],
               "readings": res["readings"]}
    except CheckpointError as e:
        out = {"correct": False, "error": repr(e)}
    print(json.dumps(dict(out, run=name, devices=len(jax.devices()))),
          flush=True)
"""


@pytest.fixture(scope="module")
def sharded_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARDED, HERE, SHARDED_CELL,
                        "none", *SHARDED_FAULTS], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    assert all(r["devices"] == 4 for r in runs)
    return {r["run"]: r for r in runs}


def test_a_sharded_save_on_four_cpu_devices_is_correct(sharded_runs):
    """The state is placed by the program's shardings, saved, read back
    onto the mesh and checked against the reference run on the same
    mesh."""
    res = sharded_runs["none"]
    assert res["correct"], res["checks"]
    assert res["checks"]["image_mismatch"]["value"] == 0
    assert len(res["readings"]["memory_peak_bytes_per_chip"]) == 4


@pytest.mark.parametrize("fault", SHARDED_FAULTS)
def test_a_broken_sharded_path_is_not_correct(sharded_runs, fault):
    """On the (data 2, model 2) mesh, a step that leaves out the exchange
    over `data` goes on from one shard's rows with their mean: it reads
    as `half_batch`."""
    assert sharded_runs[fault]["correct"] is False, sharded_runs[fault]


def test_control_fails_the_limits():
    """The fp8 control, put in the program's place, is not correct."""
    import compare
    import control
    _, cfg, traffic, _ = bench_run.cell_files("qwen2-0.5b.save_tight", ROOT)
    out = control.readings(tiny(cfg), dict(traffic, batch=4, seq_len=64), 1,
                           ["fp8"])
    fp8 = {k: {"value": out[1][k]} for k in TINY_LIMITS}
    ok, _ = compare.judge(fp8, TINY_LIMITS)
    assert not ok
