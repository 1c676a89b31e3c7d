"""api.readback_ms_per_chunk: host milliseconds per chunk spent waiting on
device→host reads inside the program's ingest calls.

Layer ``api``: the program's ``*.readback`` spans under ``ingest.*`` (the
per-step reassignment counts of minibatch K-means, row counts, a
multi-process step's assembly), read from the traced run's host plane,
clipped to the window, divided by the chunks folded there. ``per_site``
splits it by the span that holds the read (``fold.kmeans``, ``scan``); the
trace keeps no span attributes, so a read's ``site`` is not split further.
"""
from bench import spans as S


def read(ctx):
    segs = S.of(ctx)
    if segs is None:
        return None
    per: dict = {}
    for p, v in S.self_ns(segs).items():
        if S.kind(p) == "readback":
            key = S.part(p[:-len(".readback")])
            per[key] = per.get(key, 0) + v
    return {"value": S.ms_per_chunk(sum(per.values()), ctx),
            "per_site": {k: S.ms_per_chunk(v, ctx) for k, v in sorted(per.items())}}
