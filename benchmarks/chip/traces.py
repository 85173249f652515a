"""Reduction of a profiler trace (`.xplane.pb`) to device numbers.

Device planes are those named `/device:TPU:<n>`; on each, the line of
XLA ops holds one event per operation run.  Busy time is the union of
those intervals, a kernel's time the sum of its events' durations.  The
benchmark's own host spans (`bench.*` `TraceAnnotation`s) label the idle
gaps between device operations.  Both clocks are the profile's own.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_SHAPE = re.compile(r"\b([suf])(8|16|32|64)\[([0-9,]*)\]")


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_events(pd) -> List[List[Tuple[float, float, str]]]:
    """Per device plane, in the order of the chips' ids: [(start_ns,
    end_ns, HLO text)] of XLA ops."""
    out = []
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    for plane in sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1])):
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
        out.append(sorted(evs, key=lambda e: e[0]))
    return out


def host_spans(pd) -> List[Tuple[float, float, str]]:
    """The benchmark's own spans, from every host thread."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(out)


def union(intervals: Iterable[Tuple[float, float]],
          lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def operand_bytes(hlo: str) -> Optional[int]:
    """Bytes of the result and operand shapes of an op, from its HLO
    text (`%name = RESULT op(OPERANDS), attributes`, the event name on a
    TPU): what a kernel that reads and writes each once moves."""
    head = hlo.split(", custom_call_target=")[0].split("), ")[0]
    shapes = _SHAPE.findall(head)
    if not shapes:
        return None
    total = 0
    for _, bits, dims in shapes:
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * int(bits) // 8
    return total


def reduce(pd, window: Optional[Tuple[float, float]] = None,
           kernels: Iterable[str] = (), top: int = 10) -> Dict:
    """Busy and idle time inside `window` (ns; default: the span named
    `bench.window`), per chip and averaged over the chips; the time and
    bytes of each named kernel over the whole trace, summed over the
    chips; and the breakdown the result line carries (chip 0's)."""
    planes = device_events(pd)
    spans = host_spans(pd)
    if window is None:
        named = [s for s in spans if s[2] == SPAN_PREFIX + "window"]
        if not named:
            raise ValueError("trace holds no bench.window span")
        window = (named[0][0], named[0][1])
    lo, hi = window
    if not planes or not any(planes):
        raise ValueError("trace holds no device operation")
    busy = []
    for evs in planes:
        busy.append(sum(b - a for a, b in
                        union(((e[0], e[1]) for e in evs), lo, hi)))
    kern: Dict[str, Dict] = {}
    for name in kernels:
        t, nbytes, n, known = 0.0, 0, 0, True
        for evs in planes:
            for a, b, ev_name in evs:
                if name not in ev_name:
                    continue
                t += b - a
                n += 1
                by = operand_bytes(ev_name)
                if by is None:
                    known = False
                else:
                    nbytes += by
        if n:
            kern[name] = {"time_s": t / 1e9, "events": n,
                          "bytes": nbytes if known else None}
    op_time: Dict[str, float] = defaultdict(float)
    for a, b, name in planes[0]:
        if lo <= a < hi:
            op_time[short_name(name)] += (b - a) / 1e9
    gaps = []
    ops = union(((e[0], e[1]) for e in planes[0]), lo, hi)
    edges = [lo] + [x for ab in ops for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gap_time: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        gap_time[_label(spans, a, b)] += (b - a) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy],
        "window_s": window_s,
        "kernels": kern,
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in op_time.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(([k, v] for k, v in gap_time.items()),
                                key=lambda kv: -kv[1])[:top],
        },
    }


SHORT_GAP_NS = 10_000
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def short_name(hlo: str) -> str:
    """`%fusion.541 (fusion)` for an op's full HLO text."""
    name, _, rest = hlo.partition(" = ")
    op = _OPCODE.search(rest)
    return f"{name} ({op.group(1)})" if op else name


def _label(spans, a: float, b: float) -> str:
    """The innermost benchmark span that covers most of a gap."""
    if b - a < SHORT_GAP_NS:
        return "between ops (gaps under 10 us)"
    best, best_cover, best_len = "outside any bench span", 0.0, float("inf")
    for s0, s1, name in spans:
        if name == SPAN_PREFIX + "window":
            continue
        cover = min(b, s1) - max(a, s0)
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and s1 - s0 < best_len):
            best, best_cover, best_len = name, cover, s1 - s0
    return best
