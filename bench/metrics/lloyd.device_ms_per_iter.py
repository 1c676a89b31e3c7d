"""lloyd.device_ms_per_iter: device milliseconds of one Lloyd iteration.

Layer ``finalize`` (``core.kmeans.lloyd_step``: the assignment and the
Eq. 39 update of every hypothesis, on the ``kernels.sparse_assign``
kernels): the busy time of the window's operations of the ``lloyd_step``
program, summed over the cell's devices, over the iterations the job ran
(``Window.traced["lloyd_steps"]``, the longest hypothesis's). A program
without that program, or a window without iterations, reports nothing.
"""
import re

from bench import trace as T

PROGRAM = re.compile(r"lloyd_step")


def device_ns(ctx) -> int:
    tot = 0
    for d in ctx.devices:
        tot += T.busy_ns([o for o in T.window_ops(ctx.trace, d) if PROGRAM.search(o.module)])
    return tot


def read(ctx):
    steps = (ctx.window.traced or {}).get("lloyd_steps", 0)
    ns = device_ns(ctx)
    if ns <= 0 or steps <= 0:
        return None
    return {"value": ns / 1e6 / steps}
