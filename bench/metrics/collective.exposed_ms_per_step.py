"""collective.exposed_ms_per_step: collective device time per step during
which no other operation runs on that device (``stream.sharded``'s psum of
the per-step delta). The mean over the cell's devices, divided by the steps
of the traced window; nothing where the window holds no collective."""
from bench import trace as T


def read(ctx):
    got = [T.exposed_collective_ns(ctx.trace, d) for d in ctx.devices]
    got = [g for g in got if g is not None]
    if not got or ctx.steps <= 0:
        return None
    return {"value": sum(got) / len(got) / 1e6 / ctx.steps}
