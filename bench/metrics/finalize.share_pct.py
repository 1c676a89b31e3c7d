"""finalize.share_pct: the share of the window the host spends in the program's
finalize, in %.

Layer ``finalize``: the union of the window's ``finalize.*`` host spans
(``repro.obs.span``; each consumer's ``finalize.<kind>``, with its seeding,
iterations and the labels' readback inside) over the traced window. The
finalize's last read waits for the device, so the span holds the device's
finalize work. ``per_span`` gives each ``finalize.<kind>.<part>`` path's
share, nested parts counted in their own path only. A program without the
spans reports nothing.
"""
from bench import trace as T

PREFIX = "finalize."


def spans(tr):
    return [(n, max(s, tr.t0), min(e, tr.t1)) for n, s, e in tr.host
            if n.startswith(PREFIX) and min(e, tr.t1) > max(s, tr.t0)]


def read(ctx):
    tr = ctx.trace
    got = spans(tr)
    if not got or tr.window_ns <= 0:
        return None
    per: dict = {}
    for n, s, e in got:
        if n.count(".") >= 2:
            per[n] = per.get(n, 0) + e - s
    total = sum(e - s for s, e in T.union((s, e) for _, s, e in got))
    return {"value": 100.0 * total / tr.window_ns,
            "per_span": {n: 100.0 * v / tr.window_ns for n, v in sorted(per.items())}}
