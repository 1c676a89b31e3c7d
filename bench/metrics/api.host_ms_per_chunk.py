"""api.host_ms_per_chunk: host milliseconds of the api layer per chunk.

Layer ``api``: ``SharedSketchRun.partial_fit`` → ``SketchCursor.fold_rows``
→ the consumers' ``_consume`` / ``_flush_step``. Read from the benchmark's
own host clock around every ``partial_fit`` call of the traced window,
divided by the chunks those calls folded. A call returns once its work is
dispatched, or later where the program waits on the device inside it.
"""


def read(ctx):
    calls = ctx.window.call_seconds
    if not calls or ctx.chunks <= 0:
        return None
    return {"value": 1e3 * sum(calls) / ctx.chunks}
