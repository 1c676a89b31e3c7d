"""The program's own host spans in a trace, for the ``api`` and ``device`` metrics.

The program names each span of its ingest path by its dotted path
(``repro.obs.span``): ``ingest.partial_fit`` for one call, under it ``h2d``
(the caller's block copied to the device), ``chunk``, ``chunk.sketch``,
``chunk.fold.<kind>`` and, wherever the host waits for the device,
``readback``. The spans of one thread nest, so the calls' time splits into
segments, each owned by the innermost span open over it: the segments of a
path add up to its self time, and those of every path to the calls' time.
A trace of a program without these spans has no segment, and the readers
then report nothing.
"""
from __future__ import annotations

from bench import trace as T

PREFIX = "ingest."


def segments(tr: T.Trace) -> list:
    """[(path, start, end)] in time order: the window's ``ingest.*`` time,
    each piece owned by the innermost span over it."""
    spans = []
    for name, s, e in tr.host:
        s, e = max(s, tr.t0), min(e, tr.t1)
        if name.startswith(PREFIX) and e > s:
            spans.append((name, s, e))
    out, stack, cur = [], [], 0
    for name, s, e in sorted(spans, key=lambda h: (h[1], -h[2], len(h[0]))):
        while stack and stack[-1][2] <= s:
            path, _, end = stack.pop()
            if end > cur:
                out.append((path, cur, end))
            cur = max(cur, end)
        if stack:
            if s > cur:
                out.append((stack[-1][0], cur, s))
            e = min(e, stack[-1][2])
        cur = s
        stack.append((name, s, e))
    while stack:
        path, _, end = stack.pop()
        if end > cur:
            out.append((path, cur, end))
        cur = max(cur, end)
    return out


def of(ctx) -> list | None:
    """The window's segments, or None where the trace has no device plane,
    no chunk was folded, or the program wrote no ``ingest.*`` span."""
    if not ctx.devices or ctx.chunks <= 0:
        return None
    return segments(ctx.trace) or None


def self_ns(segs) -> dict:
    """{path: ns} of the segments each span owns (its self time)."""
    out: dict = {}
    for path, s, e in segs:
        out[path] = out.get(path, 0) + e - s
    return out


def kind(path: str) -> str:
    """``h2d``, ``readback``, or ``dispatch`` for the rest of the host's work."""
    last = path.rsplit(".", 1)[-1]
    return last if last in ("h2d", "readback") else "dispatch"


def part(path: str) -> str:
    """The part of a call a path belongs to: ``sketch``, ``fold.<kind>``
    (with what nests under it), ``scan``, or ``other`` for the self time of
    the call and its chunks (slicing, bookkeeping)."""
    rest = path.split(".")[2:]          # drop "ingest.<call>"
    if rest[:1] == ["chunk"]:
        rest = rest[1:]
    if not rest:
        return "other"
    return ".".join(rest[:2]) if rest[0] == "fold" else rest[0]


def idle(tr: T.Trace, dev: int) -> list:
    """[(start, end)] of the window in which ``dev`` runs no operation."""
    out, cur = [], tr.t0
    for s, e in T.union((o.start, o.end) for o in T.window_ops(tr, dev)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if tr.t1 > cur:
        out.append((cur, tr.t1))
    return out


def overlap_ns(segs, intervals) -> dict:
    """{path: ns} of each path's segments that lie inside ``intervals``
    (sorted and disjoint, as ``idle`` gives them)."""
    out: dict = {}
    j = 0
    for path, s, e in segs:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < e:
            ov = min(e, intervals[k][1]) - max(s, intervals[k][0])
            if ov > 0:
                out[path] = out.get(path, 0) + ov
            k += 1
    return out


def ms_per_chunk(ns, ctx) -> float:
    return ns / 1e6 / ctx.chunks
