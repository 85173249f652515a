"""The chip benchmark's harness: one run of one cell.

A cell names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`); per-layer metrics are readers
(`metrics/<name>.py`).  The harness finds each by the name that
`BENCHMARK.json` gives, so a new cell, mix or metric is new files.

A run: set-up (weights from the seed, made on the device in one jitted
call; every shape the window uses warmed; the traffic's first steps
through the window's own call), then the measured window, then the
correctness check against the plain reference once the program's state
is freed.  Traffic kinds:

* `train`: the window drives `MANARuntime.run` and requests a save at
  the step boundaries the mix names.  The window ends at the first step
  boundary after `--seconds`; a save begun in it is timed to its end.
* `resume`: set-up saves one image; the window restarts from it back to
  back (caches cleared, image pages dropped, fresh runtime, restore,
  one step) until `--seconds` have passed.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

KERNELS = ("checksum_block_sums", "delta_xor")


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_json(*parts) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _reference(name: str):
    return load_module(os.path.join(HERE, "references", name + ".py"),
                       "ref_" + name)


def reference(cfg: Dict):
    """The plain reference of a configuration's model family."""
    return _reference(cfg["reference"])


def train_reference():
    """The plain training step (loss, gradient, AdamW) over a model's
    reference, and the norms both sides are compared by."""
    return _reference("train")


def metric_reader(name: str):
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "metric_" + name.replace(".", "_"))


def peaks(device_kind: str) -> Dict:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]


def seed32(seed: int) -> int:
    """A 31-bit seed for JAX and the program from any whole number."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF


def batch(cfg: Dict, traffic: Dict, seed: int, step: int) -> Dict:
    """The token batch of a global step: uniform ids from a Philox
    stream keyed by the seed, counter = step (next-token labels)."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=step))
    seq = rng.integers(0, cfg["vocab_size"],
                       size=(traffic["batch"], traffic["seq_len"] + 1),
                       dtype=np.int64)
    return {"tokens": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32)}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_config(cfg: Dict, traffic: Dict):
    """The program's ModelConfig and RunConfig for a configuration file."""
    from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
    run, opt = cfg["run"], cfg["run"]["optimizer"]
    model = ModelConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        qkv_bias=cfg["layout"]["qkv_bias"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        pad_to=cfg["layout"]["head_pad_to"], source=cfg["source"])
    shape = ShapeConfig(traffic["name"], traffic["seq_len"], traffic["batch"],
                        "train")
    rc = RunConfig(model=model, shape=shape, remat_policy=run["remat_policy"],
                   loss_chunk=run["loss_chunk"], attn_chunk=run["attn_chunk"],
                   lr=opt["lr"], weight_decay=opt["weight_decay"],
                   beta1=opt["beta1"], beta2=opt["beta2"],
                   grad_clip=opt["grad_clip"], dtype=run["compute_dtype"],
                   param_dtype=run["param_dtype"])
    return model, rc


def new_runtime(model, rc, cfg: Dict, ckpt_dir: str, seed: int):
    from repro.core.runtime import MANARuntime
    ck = cfg["run"]["checkpoint"]
    rt = MANARuntime(model, rc, ckpt_dir=ckpt_dir, seed=seed, keep=ck["keep"],
                     use_pallas=ck["use_pallas"],
                     delta_params=ck["delta_params"],
                     quantize_moments=ck["quantize_moments"])
    rt.ckpt.verify = ck["verify"]
    rt.ckpt.full_every = ck["full_every"]
    return rt


def free_runtime(rt) -> None:
    rt.close()
    rt.state = None
    gc.collect()


class CompileCounter:
    """Backend compiles and persistent-cache hits and misses, through
    jax.monitoring (listeners live as long as the process)."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict:
        """JAX times a load from the persistent cache as a backend
        compile too: compiles are the events that were not loads."""
        return {"compiles": self.compiles - self.cache_hits,
                "cache_loads": self.cache_hits,
                "cache_misses": self.cache_misses}


# ---------------------------------------------------------------------------
# yardstick functions run on the device
# ---------------------------------------------------------------------------

def _fingerprint_fn(flat):
    """Per leaf: wrapping uint32 sums of the words and of the words
    weighted by position; any changed word changes one of them."""
    out = {}
    for k, x in flat.items():
        w = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
        i = jnp.arange(1, w.size + 1, dtype=jnp.uint32)
        out[k] = jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                            jnp.sum(w * i, dtype=jnp.uint32)])
    return out


_fingerprint = jax.jit(_fingerprint_fn)


def fingerprint(tree) -> Dict[str, List[int]]:
    """path -> two uint32 words, for every leaf of a state tree."""
    flat = {jax.tree_util.keystr(p): jnp.asarray(x) for p, x
            in jax.tree_util.tree_flatten_with_path(tree)[0]}
    fp = _fingerprint(flat)
    return {k: [int(a) for a in np.asarray(v)]
            for k, v in jax.device_get(fp).items()}


def leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    return {k: v * scale
            for k, v in train_reference().leaf_norms(tree).items()}


def change_norms(params, ref, cfg: Dict, seed: int) -> Dict[str, float]:
    """Norm of each leaf's change from the seed's initial weights."""
    t = train_reference()
    return t.leaf_norms(t.subtract(params, make_params(ref, cfg, seed)))


def make_params(ref, cfg: Dict, seed: int):
    return _jit_init(json.dumps(cfg, sort_keys=True))(np.uint32(seed))


@functools.cache
def _jit_init(cfg_json: str):
    cfg = json.loads(cfg_json)
    ref = reference(cfg)
    return jax.jit(lambda s: ref.init_params(cfg, s))


def make_state(ref, cfg: Dict, seed: int, abstract) -> Dict:
    """The program's state tree: the seed's weights, zero moments."""
    fn = _jit_state(json.dumps(cfg, sort_keys=True))
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), abstract)
    got = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                       jax.eval_shape(fn, np.uint32(seed)))
    if want != got:
        raise RuntimeError("the reference's parameter layout differs from "
                           f"the program's state:\n{want}\n{got}")
    return fn(np.uint32(seed))


@functools.cache
def _jit_state(cfg_json: str):
    cfg = json.loads(cfg_json)
    ref = reference(cfg)

    def build(s):
        p = ref.init_params(cfg, s)
        return {"params": p,
                "opt": {"m": jax.tree.map(jnp.zeros_like, p),
                        "v": jax.tree.map(jnp.zeros_like, p),
                        "count": jnp.zeros((), jnp.int32)},
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(build)


def warm_save_shapes(state, ck: Dict) -> None:
    """Compile the digest of every chunk size and the XOR delta of every
    params leaf shape that a save of this state uses, through the
    program's own kernel entry points."""
    from repro.core.checkpoint import CHUNK_BYTES
    from repro.kernels.checksum.ops import checksum_host
    from repro.kernels.delta.ops import delta_host
    if not ck["use_pallas"]:
        return
    sizes, leaf_shapes = set(), set()
    for path, x in jax.tree_util.tree_flatten_with_path(state)[0]:
        n = x.size * x.dtype.itemsize
        sizes.update({min(CHUNK_BYTES, n), n % CHUNK_BYTES or CHUNK_BYTES})
        if ck["delta_params"] and jax.tree_util.keystr(path).startswith(
                "['params']"):
            leaf_shapes.add((x.shape, x.dtype))
    for n in sorted(sizes):
        checksum_host(np.zeros(max(n, 1), np.uint8), use_pallas=True)
    for shape, dtype in sorted(leaf_shapes, key=str):
        z = np.zeros(shape, dtype)
        delta_host(z, z, use_pallas=True)


# ---------------------------------------------------------------------------
# timing around the program's calls
# ---------------------------------------------------------------------------

class Span:
    """A bench.* TraceAnnotation that may open and close in different
    calls."""

    def __init__(self, name: str):
        self._ann = jax.profiler.TraceAnnotation("bench." + name)
        self._ann.__enter__()

    def close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


def timed(obj, attr: str, span: str, sink: List[Dict]) -> None:
    """Replace obj.attr by a wrapper that records each call's host-clock
    duration (and opens a bench span around it)."""
    inner = getattr(obj, attr)

    def wrapper(*a, **kw):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench." + span):
            out = inner(*a, **kw)
        sink.append({"t0": t0, "s": time.monotonic() - t0, "out": out})
        return out

    setattr(obj, attr, wrapper)


def drop_page_cache(directory: str) -> None:
    """fsync every file of an image directory and drop its pages, so the
    next read comes from the disk as after a restart."""
    for root, _, files in os.walk(directory):
        for name in files:
            fd = os.open(os.path.join(root, name), os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    def __init__(self, cell: Dict, cfg: Dict, traffic: Dict, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 fault: Optional[Callable] = None):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed = seed32(seed)
        self.seconds, self.trace, self.t_start = seconds, trace, t_start
        self.fault = fault
        self.ref = reference(cfg)
        self.model, self.rc = program_config(cfg, traffic)
        self.workdir = tempfile.mkdtemp(prefix="bench_chip_")
        self.ckpt_dir = os.path.join(self.workdir, "ckpt")
        self.counter = CompileCounter()
        self.rec: Dict = {"steps": [], "saves": [], "resumes": [],
                          "save_fp": {}, "losses": []}
        self.trace_dir = os.path.join(self.workdir, "trace")

    # ---- set-up ---------------------------------------------------------
    def _setup_runtime(self):
        from repro.training.step import abstract_train_state
        rt = new_runtime(self.model, self.rc, self.cfg, self.ckpt_dir,
                         self.seed)
        prog = rt.dataset.get_batch(0)
        mine = batch(self.cfg, self.traffic, self.seed, 0)
        if not all(np.array_equal(prog[k], mine[k]) for k in mine):
            raise RuntimeError("the program's batch for step 0 differs from "
                               "the benchmark's traffic")
        rt.state = make_state(self.ref, self.cfg, self.seed,
                              abstract_train_state(self.model, self.rc))
        self._plant(rt)
        fingerprint(rt.state)
        leaf_norms(rt.state["params"])
        return rt

    def _plant(self, rt) -> None:
        """Tests only: break the timed path underneath a runtime."""
        if self.fault is not None:
            self.fault(rt)

    def _first_steps(self, rt, n: int, save_after: bool = False) -> None:
        """The traffic's first steps, through the window's own call."""
        b1 = self.cfg["run"]["optimizer"]["beta1"]

        def on_metrics(step, m):
            self.rec["losses"].append(m["loss"])
            if len(self.rec["losses"]) == 1:
                # the first gradient as the optimizer got it: m1/(1-b1)
                self.rec["grad_norms"] = leaf_norms(
                    rt.state["opt"]["m"], 1.0 / (1.0 - b1))
            if save_after and len(self.rec["losses"]) == n:
                jax.block_until_ready(rt.state)
                self.rec["save_fp"][step + 1] = fingerprint(rt.state)
                rt.request_checkpoint()

        rt.run(n, on_metrics=on_metrics)
        if not save_after:
            self.rec["change_norms"] = change_norms(
                rt.state["params"], self.ref, self.cfg, self.seed)

    def _warm_restored(self, rt) -> None:
        """Compile, in set-up, what the window runs on a restored state:
        a restore binds host arrays with `jnp.asarray`, which the step
        and the yardstick functions take as other arguments than the
        jitted state they have seen."""
        host = jax.device_get(rt.state)
        rt.state = None
        gc.collect()
        rt.state = jax.tree.map(jnp.asarray, host)
        del host
        fingerprint(rt.state)
        rt.run(1)
        change_norms(rt.state["params"], self.ref, self.cfg, self.seed)

    # ---- the window: train ---------------------------------------------
    def _train_window(self, rt) -> None:
        tr = self.traffic
        first, every = tr["save_first_step"], tr["save_every_steps"]
        steps, saves, writes = self.rec["steps"], [], []
        safe_points: List[Dict] = []
        timed(rt.agent, "safe_point", "safe_point", safe_points)
        timed(rt.ckpt, "save_async", "save_async", saves)
        timed(rt.ckpt, "_write", "write", writes)
        inner_step = rt.lower.train_step
        open_span: List[Span] = []

        def train_step(state, b):
            open_span.append(Span("step"))
            return inner_step(state, b)

        rt.lower.train_step = train_step
        n0 = len(rt.history)
        end = {"nominal": None, "stop": None}

        def on_metrics(step, m):
            jax.block_until_ready(rt.state)
            t = time.monotonic()
            open_span.pop().close()
            k = len(rt.history) - n0          # window steps done
            steps.append({"t": t, "saves_before": rt.checkpoints_taken})
            if t >= end["nominal"]:
                return
            if first and (k == first or (every and k > first
                                         and (k - first) % every == 0)):
                self.rec["save_fp"][step + 1] = fingerprint(rt.state)
                rt.request_checkpoint()

        def stop() -> bool:
            if time.monotonic() < end["nominal"]:
                return False
            if end["stop"] is None:
                end["stop"] = time.monotonic()
                win.close()
                end["drain"] = Span("drain")
            return True

        c0 = self.counter.snapshot()
        win = Span("window")
        t0 = time.monotonic()
        end["nominal"] = t0 + self.seconds
        steps.append({"t": t0, "saves_before": rt.checkpoints_taken})
        rt.run(10 ** 9, on_metrics=on_metrics, stop_flag=stop)
        t_drained = time.monotonic()
        end["drain"].close()
        self.rec["window"] = (t0, end["stop"])
        self.rec["compiles_in_window"] = _diff(self.counter.snapshot(), c0)
        self.rec["drain_s"] = t_drained - end["stop"]
        self.rec["saves"] = [
            {"safe_point_s": sp["s"], "save_async_s": sv["s"]}
            for sp, sv in zip([s for s in safe_points if s["out"]], saves)]
        self.rec["ckpt_stats"] = list(rt.ckpt.stats)

    # ---- the window: resume ---------------------------------------------
    def _resume_window(self) -> None:
        c0 = self.counter.snapshot()
        win = Span("window")
        t0 = time.monotonic()
        while True:
            jax.clear_caches()
            drop_page_cache(self.ckpt_dir)
            r = {}
            sp = Span("rebind")
            ta = time.monotonic()
            rt = new_runtime(self.model, self.rc, self.cfg, self.ckpt_dir,
                             self.seed)
            self._plant(rt)
            reads: List[Dict] = []
            timed(rt.ckpt, "restore", "restore", reads)
            start = rt.restore()
            jax.block_until_ready(rt.state)
            tb = time.monotonic()
            sp.close()
            r["read_s"] = reads[0]["s"]
            r["to_device_s"] = (tb - ta) - r["read_s"]
            r["start"] = start
            r["fp"] = fingerprint(rt.state)
            sp = Span("first_step")
            tc = time.monotonic()
            hist = rt.run(1)
            jax.block_until_ready(rt.state)
            td = time.monotonic()
            sp.close()
            r["first_step_s"] = td - tc
            r["loss"] = hist[-1]["loss"]
            r["change_norms"] = change_norms(
                rt.state["params"], self.ref, self.cfg, self.seed)
            free_runtime(rt)
            self.rec["resumes"].append(r)
            if td - t0 >= self.seconds:
                break
        t1 = time.monotonic()
        win.close()
        self.rec["window"] = (t0, t1)
        self.rec["compiles_in_window"] = _diff(self.counter.snapshot(), c0)

    # ---- the whole run --------------------------------------------------
    def run(self) -> Dict:
        tr = self.traffic
        rt = self._setup_runtime()
        if tr["kind"] == "train":
            if tr["save_first_step"]:
                warm_save_shapes(rt.state, self.cfg["run"]["checkpoint"])
            self._first_steps(rt, tr["setup_steps"])
        elif tr["kind"] == "resume":
            warm_save_shapes(rt.state, self.cfg["run"]["checkpoint"])
            self._first_steps(rt, tr["setup_steps"], save_after=True)
            self._warm_restored(rt)
            free_runtime(rt)
            rt = None
            drop_page_cache(self.ckpt_dir)
        else:
            raise ValueError(f"unknown traffic kind {tr['kind']!r}")
        self.rec["setup_s"] = time.monotonic() - self.t_start
        self.rec["setup_compiles"] = self.counter.snapshot()
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            if tr["kind"] == "train":
                self._train_window(rt)
            else:
                self._resume_window()
        finally:
            if self.trace:
                jax.profiler.stop_trace()
        stats = jax.devices()[0].memory_stats() or {}
        self.rec["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
        if tr["kind"] == "train":
            rt.state = None          # the image check needs the device
            gc.collect()
            self._check_image(rt)
            free_runtime(rt)
        return self.rec

    def _check_image(self, rt) -> None:
        """Read the newest image back through the program's restore and
        fingerprint it against the state at its step."""
        if not self.rec["save_fp"]:
            return
        step = max(self.rec["save_fp"])
        want = self.rec["save_fp"][step]
        self.rec["image_step"] = step
        try:
            host, _ = rt.ckpt.restore(step)
            got = fingerprint({"params": host["params"], "opt": host["opt"],
                               "step": host["step"]})
        except Exception as e:  # an image that cannot be read back is wrong
            print(f"bench: image {step} cannot be read back: {e!r}",
                  file=sys.stderr)
            got = {}
        self.rec["image_mismatch"] = sum(got.get(k) != v
                                         for k, v in want.items())

    def cleanup(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


def _diff(a: Dict, b: Dict) -> Dict:
    return {k: a[k] - b[k] for k in a}


def mean(xs) -> Optional[float]:
    xs = [x for x in xs if x is not None]
    return statistics.fmean(xs) if xs else None
