"""fold.device_ms_per_chunk: device milliseconds of the folds per chunk.

Layer ``lowrank.range_finder`` (``spmm`` / ``spmm_t``), ``stream.accumulators``
(dense covariance update, minibatch K-means), ``stream.sharded`` (the
shard_map reduction): every device operation of the window that is neither
the sketch's (``_sketch_impl``) nor a collective. That includes the
cursor's slicing of a call into chunks, which is small. Summed over the
cell's devices, divided by the chunks folded in the window.
"""
import re

from bench import trace as T

SKETCH = re.compile(r"_sketch_impl")


def device_ns(ctx) -> int:
    tot = 0
    for d in ctx.devices:
        ops = [o for o in T.window_ops(ctx.trace, d)
               if not SKETCH.search(o.module) and not T.is_collective(o.name)]
        tot += T.busy_ns(ops)
    return tot


def read(ctx):
    ns = device_ns(ctx)
    if ns <= 0 or ctx.chunks <= 0:
        return None
    return {"value": ns / 1e6 / ctx.chunks}
