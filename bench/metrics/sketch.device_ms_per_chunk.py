"""sketch.device_ms_per_chunk: device milliseconds of the sketch per chunk.

Layer ``core.sketch`` / ``core.sampling`` / ``kernels.fwht``,
``kernels.sketch_fused``: every operation of the program's jitted
``_sketch_impl`` (the Pallas FWHT or fused sketch kernel, the mask's
uniforms, ``top_k`` and sort, the gather), matched by that module name.
Summed over the cell's devices, divided by the chunks folded in the window.
"""
import re

from bench import trace as T

PATTERN = re.compile(r"_sketch_impl")


def device_ns(ctx) -> int:
    return sum(T.time_matching(ctx.trace, d, PATTERN) for d in ctx.devices)


def read(ctx):
    ns = device_ns(ctx)
    if ns <= 0 or ctx.chunks <= 0:
        return None
    return {"value": ns / 1e6 / ctx.chunks}
