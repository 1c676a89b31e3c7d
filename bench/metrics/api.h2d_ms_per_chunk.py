"""api.h2d_ms_per_chunk: host milliseconds per chunk spent copying the
caller's blocks to the device.

Layer ``api``: the program's ``ingest.*.h2d`` spans (``jnp.asarray`` and the
cast of the caller's block in ``SketchCursor.partial_fit``), read from the
traced run's host plane, clipped to the window, divided by the chunks
folded there. Nothing where the program wrote no ``ingest.*`` span.
"""
from bench import spans as S


def read(ctx):
    segs = S.of(ctx)
    if segs is None:
        return None
    ns = sum(v for p, v in S.self_ns(segs).items() if S.kind(p) == "h2d")
    return {"value": S.ms_per_chunk(ns, ctx)}
