"""Reduction of the program's own span records to per-layer numbers.

A record is what `repro.core.tracing.recording()` keeps for each closed
span: `name`, `id`, `parent`, `thread`, `start_ns`, `end_ns` (the
profiler's wall clock) and `counts`.  Two reductions:

* `restore_readings(summary)`: the restore's phases per resume, from
  the recording's `summary()` (the writer's phases per write are in
  `CheckpointManager.stats`, which the `write_*` readers read).
* `idle_by_span(pd, spans)`: the device's idle gaps in the window, each
  labelled with the deepest `mana.*` span that covers most of it on any
  thread, a waiting span (`ckpt.wait`, `park`) only where no working
  span is open; and the share of the idle time that no span covers.
  The same shape as `traces.reduce`'s `idle_gaps`, on the same gaps.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import traces

WAIT = frozenset({"ckpt.wait", "park"})
PREFIX = "mana."
UNTRACED = "outside any mana span"


def restore_readings(summary: Dict[str, Dict]) -> Dict[str, float]:
    """Mean per resume of each restore phase, for the `summary()` of a
    recording of resume windows (every file read under a restore):
    file reads, digest verify, decode (its self time: codec decode and
    chain fold), the bytes the decode copied because a buffer was
    read-only, the file reads' rate, the runtime build, the bind, and
    the bytes sent to the device."""
    n = summary.get("restore", {}).get("count", 0)
    if not n:
        return {}

    def get(name, key="total_s"):
        return summary.get(name, {}).get(key, 0.0)

    read_s = get("ckpt.file_read")
    nbytes = summary.get("ckpt.file_read", {}).get("counts", {}).get(
        "bytes_read", 0)
    out = {"restore_file_read_s": read_s / n,
           "restore_verify_s": get("ckpt.verify") / n,
           "restore_decode_s": get("ckpt.decode", "self_s") / n,
           "decode_copy_bytes": summary.get("ckpt.decode", {}).get(
               "counts", {}).get("decode_copy_bytes", 0) / n,
           "restore_span_s": get("ckpt.restore") / n,
           "runtime_build_s": get("runtime.build") / n,
           "restore_bind_s": get("restore.bind") / n,
           "restore_h2d_bytes": summary["restore"]["counts"].get(
               "h2d_bytes", 0) / n}
    if read_s > 0:
        out["restore_read_mb_s"] = nbytes / read_s / 1e6
    return out


class Seg(NamedTuple):
    start: float
    end: float
    name: str
    depth: int


def leaf_segments(spans, offset: float = 0.0) -> List[Seg]:
    """Per thread, the innermost open span at each moment, shifted by
    `offset` ns (spans of one thread nest: they are `with` blocks)."""
    depth: Dict[int, int] = {}
    by_id = {s.id: s for s in spans}

    def depth_of(s) -> int:
        if s.id not in depth:
            p = by_id.get(s.parent)
            depth[s.id] = 0 if p is None else depth_of(p) + 1
        return depth[s.id]

    by_thread: Dict[int, list] = defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(Seg(s.start_ns - offset, s.end_ns - offset,
                                       s.name, depth_of(s)))
    out: List[Seg] = []

    def emit(a, b, s):
        if b > a:
            out.append(Seg(a, b, s.name, s.depth))

    for segs in by_thread.values():
        stack: List[Seg] = []
        t = None
        for s in sorted(segs, key=lambda s: (s.start, -s.end)):
            while stack and stack[-1].end <= s.start:
                top = stack.pop()
                emit(t, top.end, top)
                t = top.end
            if stack:
                emit(t, s.start, stack[-1])
            stack.append(s)
            t = s.start
        while stack:
            top = stack.pop()
            emit(t, top.end, top)
            t = top.end
    return sorted(out)


def timeline(segs: List[Seg]) -> List[Tuple[float, float, Optional[str]]]:
    """[(start, end, label)] over the union of all threads: at each
    moment the deepest working span open on any thread, else the
    deepest waiting one; None where no span is open."""
    points = sorted({x for s in segs for x in (s.start, s.end)})
    out: List[Tuple[float, float, Optional[str]]] = []
    active: List[Seg] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(segs) and segs[i].start <= a:
            active.append(segs[i])
            i += 1
        active = [s for s in active if s.end > a]
        pool = [s for s in active if s.name not in WAIT] or active
        label = max(pool, key=lambda s: (s.depth, s.name)).name \
            if pool else None
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def label_gaps(gaps: Iterable[Tuple[float, float]],
               line: List[Tuple[float, float, Optional[str]]]
               ) -> Tuple[Dict[str, float], float, float]:
    """Seconds of idle time per label (each gap whole to the label that
    covers most of it), the idle seconds no span covers, and the total
    idle seconds."""
    by_label: Dict[str, float] = defaultdict(float)
    untraced = total = 0.0
    j = 0
    for a, b in sorted(gaps):
        total += (b - a) / 1e9
        cover: Dict[Optional[str], float] = defaultdict(float)
        while j < len(line) and line[j][1] <= a:
            j += 1
        k = j
        while k < len(line) and line[k][0] < b:
            lo, hi = max(a, line[k][0]), min(b, line[k][1])
            if hi > lo:
                cover[line[k][2]] += hi - lo
            k += 1
        untraced += ((b - a) - sum(v for n, v in cover.items()
                                   if n is not None)) / 1e9
        if b - a < traces.SHORT_GAP_NS:
            label = "between ops (gaps under 10 us)"
        else:
            named = {n: v for n, v in cover.items() if n is not None}
            label = (PREFIX + max(named, key=lambda n: (named[n], n))
                     if named else UNTRACED)
        by_label[label] += (b - a) / 1e9
    return dict(by_label), untraced, total


def idle_by_span(pd, spans, window: Optional[Tuple[float, float]] = None,
                 top: int = 10) -> Dict:
    """`idle_gaps_by_program_span` and `untraced_idle_share` (%) of the
    device's idle time in the window (default: the `bench.window` span),
    for a profile `pd` and the records of a recording made during it."""
    env = next(p for p in pd.planes if p.name == "Task Environment")
    offset = dict(env.stats)["profile_start_time"]
    if window is None:
        window = next((a, b) for a, b, n in traces.host_spans(pd)
                      if n == traces.SPAN_PREFIX + "window")
    lo, hi = window
    ops = traces.union(((e[0], e[1]) for e in traces.device_events(pd)[0]),
                       lo, hi)
    edges = [lo] + [x for ab in ops for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    by_label, untraced, total = label_gaps(
        gaps, timeline(leaf_segments(spans, offset)))
    return {"idle_gaps_by_program_span": sorted(
                ([k, v] for k, v in by_label.items()),
                key=lambda kv: -kv[1])[:top],
            "untraced_idle_share": 100.0 * untraced / total if total
            else None}
