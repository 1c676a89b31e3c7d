"""device.idle_pct: the share of the traced window in which a device ran no
operation, 100 x (1 - union of its op intervals / window), the mean over the
cell's devices."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    if tr.window_ns <= 0 or not ctx.devices:
        return None
    idle = [100.0 * (1.0 - T.busy_ns(T.window_ops(tr, d)) / tr.window_ns)
            for d in ctx.devices]
    return {"value": sum(idle) / len(idle), "per_device": idle}
