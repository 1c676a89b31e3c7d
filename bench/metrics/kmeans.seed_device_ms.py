"""kmeans.seed_device_ms: device milliseconds of K-means++ seeding, per hypothesis.

Layer ``finalize`` (``core.kmeans.kmeans_seed``: K − 1 steps, each one pass
over the retained sketch against every hypothesis's candidates): the busy
time of the window's operations of the ``kmeans_seed`` program, summed
over the cell's devices, over the hypotheses it seeded
(``Window.traced["hypotheses"]``). ``per_step`` divides by the seeding
steps as well. A program without that program reports nothing.
"""
import re

from bench import trace as T

PROGRAM = re.compile(r"kmeans_seed")


def read(ctx):
    traced = ctx.window.traced or {}
    hyp, steps = traced.get("hypotheses", 0), traced.get("seed_steps", 0)
    ns = 0
    for d in ctx.devices:
        ns += T.busy_ns([o for o in T.window_ops(ctx.trace, d) if PROGRAM.search(o.module)])
    if ns <= 0 or hyp <= 0:
        return None
    out = {"value": ns / 1e6 / hyp}
    if steps > 0:
        out["per_step"] = ns / 1e6 / steps
    return out
