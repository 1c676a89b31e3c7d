"""device.idle_in_api_pct: the share of the traced window in which a device
runs no operation while the host is inside the program's ``ingest.*`` spans,
in %, the mean over the cell's devices.

Layer ``device``: the busy union of ``device.idle_pct``, against the
program's host spans on the same clock. ``per_span`` splits it by the
innermost span open over the idle time. It is at most ``device.idle_pct``;
the difference is idle time the caller caused, outside the program.
"""
from bench import spans as S


def read(ctx):
    segs = S.of(ctx)
    tr = ctx.trace
    if segs is None or tr.window_ns <= 0:
        return None
    per: dict = {}
    for d in ctx.devices:
        for p, v in S.overlap_ns(segs, S.idle(tr, d)).items():
            per[p] = per.get(p, 0) + v
    pct = {p: 100.0 * v / tr.window_ns / len(ctx.devices) for p, v in sorted(per.items())}
    return {"value": sum(pct.values()), "per_span": pct}
