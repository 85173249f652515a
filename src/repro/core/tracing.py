"""Spans and byte counters inside the program, on the device trace's
clock.

Every layer of the save and restore paths opens a named span around
its work (`SPANS` lists them) and counts the bytes it moves at the
point of work (`count`).  A span always times its phase with
`time.monotonic` and adds its time to the spans it sits in, so
`CheckpointManager.stats` carries the per-phase seconds of every save
with no recording on.

Recording is off by default.  `with recording() as rec:` turns it on
for the process and hands back the records:

>>> from repro.core import tracing
>>> with tracing.recording() as rec:
...     with tracing.span("ckpt.write"):
...         with tracing.span("ckpt.file_write"):
...             tracing.count("bytes_written", 4096)
>>> [(s.name, s.parent is None, s.counts) for s in rec.spans]
[('ckpt.file_write', False, {'bytes_written': 4096}), ('ckpt.write', True, {'bytes_written': 4096})]
>>> rec.summary()["ckpt.write"]["counts"]
{'bytes_written': 4096}

With recording on, each span also opens a profiler annotation named
`"mana." + name`, so a trace taken with `jax.profiler` shows the span
on its host plane; the recorded start and end are taken on the same
clock as the profiler's host events (`time.time_ns`).  A span's parent
is the innermost span open on its thread; a counter adds to every span
open on the thread where the work happens.  Backend compiles are
recorded as `compile` records, children of the innermost open span,
with `cache_hit` set when the executable was loaded from the
persistent compilation cache rather than compiled.

This module imports jax only when a recording starts, so rank
processes and `repro.core.checkpoint` import without it.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

__all__ = ["SPANS", "Span", "SpanRecord", "Recording",
           "span", "count", "recording"]

# Every span the program opens: where it is opened, and the counters
# incremented inside it at the point of work.  A span's recorded counts
# also hold those of the spans nested in it.  PERF.md's span table is
# kept equal to this by docs/check_docs_drift.py.
SPANS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    "runtime.build": ("MANARuntime.__init__", ()),
    "step": ("MANARuntime.run", ()),
    "safe_point": ("RankAgent.safe_point", ()),
    "park": ("RankAgent.safe_point", ()),
    "drain": ("RankAgent.safe_point", ()),
    "snapshot": ("RankAgent.safe_point", ()),
    "commit": ("RankAgent.safe_point", ()),
    "ckpt.save": ("CheckpointManager.save_async", ()),
    "ckpt.wait": ("CheckpointManager.wait", ()),
    "ckpt.d2h": ("CheckpointManager.save_async", ("d2h_bytes",)),
    "ckpt.write": ("CheckpointManager._write", ()),
    "ckpt.base_read": ("_EncodeCtx.base_array", ()),
    "ckpt.encode": ("CheckpointManager._write", ("h2d_bytes",)),
    "ckpt.digest": ("CheckpointManager._write", ("h2d_bytes",)),
    "ckpt.file_write": ("CheckpointManager._write", ("bytes_written",)),
    "ckpt.commit": ("CheckpointManager._write", ()),
    "ckpt.restore": ("CheckpointManager.restore", ("h2d_bytes",)),
    "ckpt.file_read": ("CheckpointManager._read_payload", ("bytes_read",)),
    "ckpt.verify": ("CheckpointManager._read_payload", ("h2d_bytes",)),
    "ckpt.decode": ("CheckpointManager._read_array", ("decode_copy_bytes",)),
    "restore": ("MANARuntime.restore", ()),
    "restore.bind": ("MANARuntime.restore", ("h2d_bytes",)),
    "compile": ("jax.monitoring listener", ("cache_hit", "cache_miss")),
}

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hit",
                 "/jax/compilation_cache/cache_misses": "cache_miss"}


class SpanRecord(NamedTuple):
    """One closed span (or compile) of a recording; times in ns on the
    profiler's host clock."""
    name: str
    id: int
    parent: Optional[int]
    thread: int
    start_ns: int
    end_ns: int
    counts: Dict[str, int]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Recording:
    """The records of one `recording()`: `spans` in the order they
    closed, a child before its parent."""

    def __init__(self):
        self.spans: List[SpanRecord] = []

    def summary(self) -> Dict[str, Dict]:
        """Per span name: count, total and self seconds (a span's time
        less what its children on the same thread cover, compiles
        included) and the summed counters."""
        child_ns: Dict[int, int] = {}
        for s in self.spans:
            if s.parent is not None:
                child_ns[s.parent] = (child_ns.get(s.parent, 0)
                                      + s.end_ns - s.start_ns)
        out: Dict[str, Dict] = {}
        for s in self.spans:
            e = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0, "counts": {}})
            dur = s.end_ns - s.start_ns
            e["count"] += 1
            e["total_s"] += dur / 1e9
            e["self_s"] += (dur - child_ns.get(s.id, 0)) / 1e9
            for k, v in s.counts.items():
                e["counts"][k] = e["counts"].get(k, 0) + v
        return out

    def compiles(self) -> List[SpanRecord]:
        """Backend compiles, loads from the persistent cache left out."""
        return [s for s in self.spans
                if s.name == "compile" and not s.counts.get("cache_hit")]


_ids = itertools.count(1)
_local = threading.local()
_lock = threading.Lock()
_active: Tuple[Recording, ...] = ()
_listening = False


def _stack() -> List["Span"]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class Span:
    """A timed phase; use through `span(name)`.

    After it closes, `seconds` is its duration and `counts` the bytes
    counted inside it; `total(name)` and `self_total(name)` sum the
    time of the spans of that name nested in it (any depth)."""

    __slots__ = ("name", "parent", "counts", "seconds", "_child_s",
                 "_phases", "_t0", "_id", "_start_ns", "_ann", "_recs")

    def __init__(self, name: str):
        self.name = name
        self.parent: Optional[Span] = None
        self.counts: Dict[str, int] = {}
        self.seconds = 0.0
        self._child_s = 0.0
        # name -> [inclusive seconds, self seconds] over nested spans
        self._phases: Dict[str, List[float]] = {}
        self._recs: Tuple[Recording, ...] = ()
        self._id: Optional[int] = None
        self._ann = None

    def total(self, name: str) -> float:
        return self._phases.get(name, (0.0, 0.0))[0]

    def self_total(self, name: str) -> float:
        return self._phases.get(name, (0.0, 0.0))[1]

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        recs = _active
        if recs:
            self._recs = recs
            self._id = next(_ids)
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation("mana." + self.name)
            self._ann.__enter__()
            self._start_ns = time.time_ns()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.monotonic() - self._t0
        if self._recs:
            end_ns = time.time_ns()
            self._ann.__exit__(None, None, None)
            self._ann = None
            parent = self.parent
            rec = SpanRecord(self.name, self._id,
                             parent._id if parent is not None else None,
                             threading.get_native_id(), self._start_ns,
                             end_ns, dict(self.counts))
            for r in self._recs:
                r.spans.append(rec)
        _stack().pop()
        p = self.parent
        if p is not None:
            p._child_s += self.seconds
            phases = p._phases
            for k, (t, s) in self._phases.items():
                acc = phases.setdefault(k, [0.0, 0.0])
                acc[0] += t
                acc[1] += s
            acc = phases.setdefault(self.name, [0.0, 0.0])
            acc[0] += self.seconds
            acc[1] += self.seconds - self._child_s


def span(name: str) -> Span:
    """A context manager that times `name` (see the module doc)."""
    return Span(name)


def count(key: str, n: int) -> None:
    """Add n to counter `key` of every span open on this thread."""
    for s in _stack():
        s.counts[key] = s.counts.get(key, 0) + n


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Record every span closed, and every backend compile, in this
    process until the block ends.  Recordings may nest; each gets the
    records of its own time."""
    global _active
    _install_compile_listener()
    rec = Recording()
    with _lock:
        _active = _active + (rec,)
    try:
        yield rec
    finally:
        with _lock:
            _active = tuple(r for r in _active if r is not rec)


def _install_compile_listener() -> None:
    """One process-wide jax.monitoring listener, installed with the
    first recording; it records nothing while no recording is on."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _listening = True


def _on_event(event: str, **_) -> None:
    # a load from the persistent cache, or a write to it, happens
    # inside the backend-compile event it belongs to, on the same thread
    key = _CACHE_EVENTS.get(event)
    if key is not None and _active:
        pending = _local.__dict__.setdefault("cache", {})
        pending[key] = pending.get(key, 0) + 1


def _on_time_span(event: str, start: float, end: float, **_) -> None:
    if event != _BACKEND_COMPILE:
        return
    counts = _local.__dict__.pop("cache", {})
    recs = _active
    if not recs:
        return
    stack = _stack()
    parent = stack[-1]._id if stack else None
    rec = SpanRecord("compile", next(_ids), parent,
                     threading.get_native_id(), int(start * 1e9),
                     int(end * 1e9),
                     {"cache_hit": counts.get("cache_hit", 0),
                      "cache_miss": counts.get("cache_miss", 0)})
    for r in recs:
        r.spans.append(rec)
