"""finalize.idle_pct: the share of the finalize in which the device is idle, in %.

Layer ``finalize``: the time inside the window's ``finalize.kmeans`` host
spans (the seeding, the iterations and the labels' readback) during which
a device of the cell runs no operation, over that span time, the mean over
the devices. It shows whether the Lloyd iterations, dispatched with no
read between them, keep the device fed. A program without the spans
reports nothing.
"""
from bench import spans as S
from bench import trace as T

SPAN = "finalize.kmeans"


def read(ctx):
    tr = ctx.trace
    inside = T.union((max(s, tr.t0), min(e, tr.t1)) for n, s, e in tr.host
                     if (n == SPAN or n.startswith(SPAN + ".")) and min(e, tr.t1) > max(s, tr.t0))
    total = sum(e - s for s, e in inside)
    if total <= 0 or not ctx.devices:
        return None
    segs = [("finalize", s, e) for s, e in inside]
    idle = [S.overlap_ns(segs, S.idle(tr, d)).get("finalize", 0) for d in ctx.devices]
    return {"value": 100.0 * sum(idle) / len(idle) / total}
