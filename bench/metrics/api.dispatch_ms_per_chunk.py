"""api.dispatch_ms_per_chunk: host milliseconds per chunk of the program's
ingest calls that are neither the copy to the device nor a readback.

Layer ``api``: the time in ``ingest.*`` spans less their ``h2d`` and
``readback`` descendants, that is the Python and launch work of the calls,
read from the traced run's host plane, clipped to the window, divided by
the chunks folded there. ``per_span`` splits it into ``sketch``, each
consumer's ``fold.<kind>`` (less its readbacks) and ``other``, the self time
of the call and chunk spans. With ``api.h2d_ms_per_chunk`` and
``api.readback_ms_per_chunk`` it adds up to the calls' own time.
"""
from bench import spans as S


def read(ctx):
    segs = S.of(ctx)
    if segs is None:
        return None
    per: dict = {}
    for p, v in S.self_ns(segs).items():
        if S.kind(p) == "dispatch":
            key = S.part(p)
            per[key] = per.get(key, 0) + v
    return {"value": S.ms_per_chunk(sum(per.values()), ctx),
            "per_span": {k: S.ms_per_chunk(v, ctx) for k, v in sorted(per.items())}}
