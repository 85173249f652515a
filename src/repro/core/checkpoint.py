"""Sharded, asynchronous, integrity-checked checkpointing with elastic
restore — the upper-half persistence layer (paper §II-A, §II-B).

Split-process discipline: a checkpoint contains ONLY upper-half state —
raw array bytes + logical axis names + scalars (step, RNG, data cursor,
virtual-object tables).  No device ids, no mesh shapes, no executables.
Restore therefore accepts ANY target mesh/rules and binds arrays with
fresh NamedShardings (elastic restart), exactly as MANA restarts the
lower half from scratch and maps the upper half back in.

Write path (the Fig-3 axis):
  snapshot (device_get, blocking but fast) -> background writer thread
  (async: training resumes immediately after phase 2 commits the
  snapshot) -> per-array chunk files (parallel "burst-buffer" style) +
  checksums -> manifest.json written last via atomic rename -> GC of old
  checkpoints (keep-N; the paper's retirement/GC lesson applied to
  images).

Per-array encodings are a pluggable `ImageCodec` STACK
(`repro.core.codec`): the first codec that claims a path encodes it
(blockwise int8 quantization for optimizer moments, XOR delta against
the previous checkpoint for slowly-changing state), `RawCodec` is the
terminal fallback, and every payload chunk is stamped with a Fletcher
digest that restore verifies (`use_pallas=True` routes digests and
deltas through the pallas kernels, otherwise the numpy oracles run).
Delta chains are bounded: a full image every `full_every` checkpoints
on the write side, a `max_chain` reconstruction bound on the read side,
and GC protects the transitive base chain of every kept checkpoint.
"""
from __future__ import annotations

import json
import os
import shutil
import time
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core import tracing
from repro.core.codec import (DEFAULT_COMPRESS_LEVEL, ChainPolicy,
                              CheckpointError, DeltaChainError, DeltaCodec,
                              ImageCodec, ImageError, ImageIntegrityError,
                              QuantizeCodec, RawCodec, shard_digest)

__all__ = ["CheckpointManager", "CheckpointError", "ImageError",
           "ImageIntegrityError", "DeltaChainError", "MANIFEST"]

MANIFEST = "manifest.json"
CHUNK_BYTES = 64 << 20  # 64 MiB chunks (burst-buffer-friendly writes)


def _flatten(tree, prefix="") -> Dict[str, Any]:
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    elif (isinstance(tree, (list, tuple))
          and type(tree).__name__ != "PartitionSpec"):
        # PartitionSpec IS a tuple subclass but is a spec-tree LEAF: an
        # empty P() would otherwise vanish and a P('data', ...) would
        # shred into per-element paths, so elastic restore would bind
        # every array replicated (checked by name to keep jax lazy here)
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix[:-1]] = tree
    return out


class _EncodeCtx:
    """Write-side codec context: the delta base image (if the chain
    policy allows another delta) and the kernel/oracle switch."""

    def __init__(self, mgr: "CheckpointManager", base_step: Optional[int]):
        self._mgr = mgr
        self.base_step = base_step
        self.use_pallas = mgr.use_pallas

    def base_array(self, path: str) -> Optional[np.ndarray]:
        if self.base_step is None:
            return None
        with tracing.span("ckpt.base_read"):
            return self._mgr._read_array(
                self._mgr.step_dir(self.base_step), path)


class _DecodeCtx:
    """Read-side codec context: resolves a path's delta base from
    another step's image, with the chain-depth bound enforced."""

    def __init__(self, mgr: "CheckpointManager", path: str, depth: int):
        self._mgr = mgr
        self._path = path
        self._depth = depth
        self.use_pallas = mgr.use_pallas

    def read_base(self, step: int) -> Optional[np.ndarray]:
        return self._mgr._read_array(self._mgr.step_dir(step), self._path,
                                     _depth=self._depth + 1)


class CheckpointManager:
    """File-image checkpoint store with a pluggable codec stack.

    >>> import numpy as np, tempfile
    >>> d = tempfile.mkdtemp()
    >>> mgr = CheckpointManager(d, keep=2, delta_keys=("w",))
    >>> _ = mgr.save(1, {"w": np.zeros(512, np.float32)})
    >>> _ = mgr.save(2, {"w": np.ones(512, np.float32)})   # XOR delta vs 1
    >>> mgr.steps()
    [1, 2]
    >>> out, extra = mgr.restore()          # newest step, chain rebuilt
    >>> float(out["w"].sum())
    512.0

    Encodings are selected per array path by the `codecs` stack (first
    claim wins; raw is the terminal fallback).  `quantize_keys` /
    `delta_keys` are sugar for the standard stack; pass `codecs=` for a
    custom one.  `verify=True` (default) checks every chunk digest at
    read time and raises a typed `ImageIntegrityError` on mismatch.
    """

    def __init__(self, directory: str, keep: int = 3,
                 quantize_keys: Tuple[str, ...] = (),
                 delta_keys: Tuple[str, ...] = (), verify: bool = True,
                 full_every: int = 4, max_chain: int = ChainPolicy.max_chain,
                 codecs: Optional[Sequence[ImageCodec]] = None,
                 use_pallas: bool = False, compress: bool = False,
                 compress_level: int = DEFAULT_COMPRESS_LEVEL):
        self.dir = directory
        self.keep = keep
        self.verify = verify
        self.use_pallas = use_pallas
        self.compress = compress
        # deflate level for compress=True payload chunks; the default
        # tracks repro.core.codec.DEFAULT_COMPRESS_LEVEL, which the
        # image_codec_throughput benchmark picked
        self.compress_level = compress_level
        # delta checkpoints form chains; bound them with periodic fulls
        # on the write side and a reconstruction-depth cap on the read
        # side (the two sides may be different processes/configs)
        self.full_every = max(1, full_every)
        self.max_chain = max_chain
        self._since_full = 0
        if codecs is None:
            codecs = []
            if quantize_keys:
                codecs.append(QuantizeCodec(tuple(quantize_keys)))
            if delta_keys:
                codecs.append(DeltaCodec(tuple(delta_keys)))
        self.codecs: List[ImageCodec] = list(codecs) + [RawCodec()]
        # decode must handle EVERY known encoding regardless of the
        # configured write stack (a fresh manager reads old images)
        self._decoders: Dict[str, ImageCodec] = {}
        for codec in [*self.codecs, QuantizeCodec(), DeltaCodec()]:
            self._decoders.setdefault(codec.name, codec)
        os.makedirs(directory, exist_ok=True)
        # crash recovery for the re-checkpoint retire dance (_write): a
        # kill between retiring the old image and committing the new
        # one leaves the only valid image under retired.* — put it back;
        # a retired dir whose step also has a committed image is trash
        for name in os.listdir(directory):
            if not name.startswith("retired.ckpt_"):
                continue
            retired = os.path.join(directory, name)
            d = os.path.join(directory, name[len("retired."):])
            if os.path.exists(os.path.join(d, MANIFEST)):
                shutil.rmtree(retired, ignore_errors=True)
            else:
                shutil.rmtree(d, ignore_errors=True)  # partial commit
                os.replace(retired, d)
        self._writer = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="ckpt-writer")
        self._pending: Optional[Future] = None
        self.stats: List[Dict] = []

    # ---- public API -----------------------------------------------------------
    def step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"ckpt_{step:010d}")

    def save_async(self, step: int, state_tree, logical_tree=None,
                   extra: Optional[Dict] = None) -> Future:
        """Snapshot now (device_get), write in the background.

        Returns a Future resolving to write stats.  A second save while
        one is in flight waits for it first (double buffering).
        """
        with tracing.span("ckpt.save"):
            self.wait()
            with tracing.span("ckpt.d2h") as d2h:
                host_tree = _to_host(state_tree)
            logical_flat = (
                {k: list(v) if isinstance(v, tuple) else None
                 for k, v in _flatten(logical_tree).items()}
                if logical_tree is not None else {})
            fut = self._writer.submit(
                self._write, step, host_tree, logical_flat, extra or {},
                {"snapshot_s": round(d2h.seconds, 4),
                 "d2h_bytes": d2h.counts.get("d2h_bytes", 0)})
            self._pending = fut
        return fut

    def save(self, step: int, state_tree, logical_tree=None,
             extra: Optional[Dict] = None) -> Dict:
        return self.save_async(step, state_tree, logical_tree, extra).result()

    def wait(self) -> None:
        if self._pending is not None:
            with tracing.span("ckpt.wait"):
                self._pending.result()
            self._pending = None

    def writing(self) -> bool:
        """Whether a background write is still in flight."""
        return self._pending is not None and not self._pending.done()

    def steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.dir):
            p = os.path.join(self.dir, name, MANIFEST)
            if name.startswith("ckpt_") and os.path.exists(p):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    # ---- write path -----------------------------------------------------------
    def _write(self, step: int, host_tree, logical_flat, extra,
               snap: Dict) -> Dict:
        with tracing.span("ckpt.write") as w:
            total = self._write_image(step, host_tree, logical_flat, extra)
        # per-phase seconds of this write: base_read_s holds the base
        # image's read and verify, encode_s the encode less that read;
        # what is left of write_s is the write's own bookkeeping.  The
        # write's bytes read back (the delta base) and sent to the
        # device (digests, XOR and quantize kernels) beside them.
        stats = {"step": step, "bytes": total, **snap,
                 "write_s": round(w.seconds, 4),
                 "base_read_s": round(w.total("ckpt.base_read"), 6),
                 "encode_s": round(w.self_total("ckpt.encode"), 6),
                 "digest_s": round(w.total("ckpt.digest"), 6),
                 "file_s": round(w.total("ckpt.file_write"), 6),
                 "commit_s": round(w.total("ckpt.commit"), 6),
                 "bytes_read": w.counts.get("bytes_read", 0),
                 "h2d_bytes": w.counts.get("h2d_bytes", 0)}
        self.stats.append(stats)
        return stats

    def _write_image(self, step: int, host_tree, logical_flat,
                     extra) -> int:
        """Encode, digest and write one image, then commit it; returns
        its payload bytes."""
        d = self.step_dir(step)
        tmp = d + ".tmp"
        with tracing.span("ckpt.file_write"):
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
        flat = _flatten(host_tree)
        arrays: Dict[str, Dict] = {}
        total = 0
        prev_step = self.latest_step()
        delta_ok = (prev_step is not None
                    and self._since_full < self.full_every - 1)
        ctx = _EncodeCtx(self, prev_step if delta_ok else None)
        for path, arr in flat.items():
            with tracing.span("ckpt.encode"):
                arr = np.asarray(arr)
                for codec in self.codecs:
                    encoded = codec.encode(path, arr, ctx)
                    if encoded is not None:
                        break
                encoding, payloads, meta = encoded
                if self.compress:
                    payloads = [zlib.compress(p, self.compress_level)
                                for p in payloads]
            entry: Dict[str, Any] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "logical": logical_flat.get(path),
                "encoding": encoding,
                **meta,
            }
            if self.compress:
                entry["compressed"] = True
            files = []
            for pi, payload in enumerate(payloads):
                for ci, o in enumerate(range(0, max(len(payload), 1),
                                             CHUNK_BYTES)):
                    fname = f"{path.replace('/', '.')}-{pi}.{ci}"
                    with tracing.span("ckpt.file_write"):
                        chunk = payload[o:o + CHUNK_BYTES]
                        with open(os.path.join(tmp, fname), "wb") as f:
                            f.write(chunk)
                        tracing.count("bytes_written", len(chunk))
                    with tracing.span("ckpt.digest"):
                        digest = shard_digest(chunk, self.use_pallas)
                    files.append({"file": fname, "part": pi,
                                  "nbytes": len(chunk),
                                  "checksum": digest})
                    total += len(chunk)
            entry["files"] = files
            arrays[path] = entry
        with tracing.span("ckpt.commit"):
            self._commit(step, d, tmp, arrays, extra, total)
        return total

    def _commit(self, step: int, d: str, tmp: str, arrays: Dict, extra,
                total: int) -> None:
        """Manifest last, atomic rename, then GC of older images."""
        manifest = {
            "format_version": 2,
            "step": step,
            "written_at": time.time(),
            "arrays": arrays,
            "extra": extra,
            "total_bytes": total,
        }
        with open(os.path.join(tmp, MANIFEST), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(d):
            # re-checkpointing a step (e.g. a restarted run reaching
            # the same boundary): os.replace cannot overwrite a
            # non-empty directory, and deleting the old image BEFORE
            # the rename would leave a crash window with no committed
            # checkpoint at this step — retire it aside first.  The
            # "retired." prefix keeps it invisible to steps()/restore.
            retired = os.path.join(self.dir,
                                   "retired." + os.path.basename(d))
            shutil.rmtree(retired, ignore_errors=True)
            os.replace(d, retired)
            os.replace(tmp, d)  # atomic commit
            shutil.rmtree(retired, ignore_errors=True)
        else:
            os.replace(tmp, d)  # atomic commit
        wrote_delta = any("base_step" in e for e in arrays.values())
        self._since_full = self._since_full + 1 if wrote_delta else 0
        self._gc()

    def _gc(self) -> None:
        steps = self.steps()
        # protect the TRANSITIVE delta-base chain of every kept checkpoint
        needed: set = set()
        frontier = list(steps[-self.keep:]) if self.keep else []
        while frontier:
            s = frontier.pop()
            try:
                man = self._manifest(self.step_dir(s))
            except FileNotFoundError:
                continue
            for e in man["arrays"].values():
                b = e.get("base_step")
                if b is not None and b not in needed:
                    needed.add(b)
                    frontier.append(b)
        for s in steps[:-self.keep]:
            if s in needed:
                continue
            shutil.rmtree(self.step_dir(s), ignore_errors=True)

    # ---- read path -------------------------------------------------------------
    def _manifest(self, d: str) -> Dict:
        with open(os.path.join(d, MANIFEST)) as f:
            return json.load(f)

    def _read_payload(self, d: str, entry: Dict,
                      part: int) -> Union[np.ndarray, bytes]:
        """One payload part, its chunk files read in manifest order into
        one buffer allocated at the part's size.  Each file must hold
        the bytes its manifest entry says; with `verify` each chunk's
        digest is checked before anything decodes it.  Returns the
        writable buffer, or the decompressed `bytes` of a compressed
        part."""
        files = [f for f in entry["files"] if f["part"] == part]
        buf = np.empty(sum(f["nbytes"] for f in files), np.uint8)
        view = memoryview(buf)
        off = 0
        for fmeta in files:
            n = fmeta["nbytes"]
            chunk = view[off:off + n]
            off += n
            with tracing.span("ckpt.file_read"):
                with open(os.path.join(d, fmeta["file"]), "rb") as f:
                    size = os.fstat(f.fileno()).st_size
                    got = f.readinto(chunk) if size == n else 0
                tracing.count("bytes_read", got)
            if size != n or got != n:
                raise ImageIntegrityError(
                    f"size mismatch in {fmeta['file']}: {size} bytes on "
                    f"disk, {got} read, {n} in the manifest")
            if self.verify:
                with tracing.span("ckpt.verify"):
                    digest = shard_digest(chunk, self.use_pallas)
                if digest != fmeta["checksum"]:
                    raise ImageIntegrityError(
                        f"checksum mismatch in {fmeta['file']}: "
                        f"{digest} != {fmeta['checksum']}")
        if entry.get("compressed"):
            with tracing.span("ckpt.decode"):
                return zlib.decompress(buf)
        return buf

    def _read_array(self, d: str, path: str, *,
                    _depth: int = 0) -> Optional[np.ndarray]:
        if _depth > self.max_chain:
            raise DeltaChainError(
                f"{path}: delta chain longer than the max_chain bound "
                f"({self.max_chain})")
        try:
            with tracing.span("ckpt.file_read"):
                man = self._manifest(d)
        except FileNotFoundError:
            return None
        entry = man["arrays"].get(path)
        if entry is None:
            return None
        codec = self._decoders.get(entry["encoding"])
        if codec is None:
            raise CheckpointError(f"unknown encoding {entry['encoding']}")
        n_parts = 1 + max((f["part"] for f in entry["files"]), default=0)
        parts = [self._read_payload(d, entry, pi) for pi in range(n_parts)]
        with tracing.span("ckpt.decode"):
            return codec.decode(parts, entry, _DecodeCtx(self, path, _depth))

    def restore(self, step: Optional[int] = None, *, mesh=None, specs=None,
                skeleton=None) -> Tuple[Any, Dict]:
        """Load a checkpoint.  Elastic: pass a (possibly different) mesh +
        PartitionSpec tree to bind arrays to the NEW topology; with
        mesh=None returns host numpy arrays.

        Returns (state_tree, extra).
        """
        with tracing.span("ckpt.restore"):
            step = self.latest_step() if step is None else step
            if step is None:
                raise CheckpointError("no checkpoints found")
            d = self.step_dir(step)
            with tracing.span("ckpt.file_read"):
                man = self._manifest(d)
            flat = {p: self._read_array(d, p) for p in man["arrays"]}
            spec_flat = _flatten(specs) if specs is not None else {}

            def bind(path, arr):
                if mesh is None:
                    return arr
                import jax
                from jax.sharding import NamedSharding, PartitionSpec
                spec = spec_flat.get(path, PartitionSpec())
                tracing.count("h2d_bytes", arr.nbytes)
                return jax.device_put(arr, NamedSharding(mesh, spec))

            bound = {p: bind(p, a) for p, a in flat.items()}
        return _rebuild(bound), man["extra"]


def _to_host(tree):
    import jax

    def get(x):
        if hasattr(x, "addressable_shards") or hasattr(x, "device_buffer"):
            host = np.asarray(jax.device_get(x))
            tracing.count("d2h_bytes", host.nbytes)
            return host
        return np.asarray(x)

    return jax.tree.map(get, tree)


def _rebuild(flat: Dict[str, Any]):
    """Rebuild a nested dict tree from 'a/b/c' paths."""
    root: Dict[str, Any] = {}
    for path, val in flat.items():
        parts = path.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return root
