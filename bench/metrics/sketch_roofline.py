"""sketch_roofline: the sketch's share of its roofline, in %.

Essential work of sketching one chunk of n rows of width p (padded to
p_pad, m kept), counted from shapes and never from the implementation's
schedule:

- bytes: the dense chunk read once (n·p·4) and the kept values and their
  int32 indices written (n·m·8);
- operations: the Walsh-Hadamard transform's n·p_pad·log2(p_pad)
  additions. Drawing the mask is not counted: it is work a sampler may
  do in many ways.

The share is the least time the chip could take, the larger of operations
over its peak rate and bytes over its memory bandwidth
(``bench/peaks.json``), over the sketch's device time per chunk
(``sketch.device_ms_per_chunk``); ``bound`` says which of the two it is.
"""
import math

from bench.harness import metric_reader


def work(shape: dict) -> tuple[float, float]:
    """(operations, bytes) of one chunk."""
    n, p, pp, m = shape["n"], shape["p"], shape["p_pad"], shape["m"]
    return float(n * pp * int(math.log2(pp))), float(n * p * 4 + n * m * 8)


def read(ctx):
    if ctx.peaks is None:
        return None
    ns = metric_reader("sketch.device_ms_per_chunk").device_ns(ctx)
    if ns <= 0 or ctx.chunks <= 0:
        return None
    ops, nbytes = work(shape_of(ctx.job))
    t_ops, t_mem = ops / ctx.peaks["flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"]
    per_chunk = ns / 1e9 / ctx.chunks
    return {"value": 100.0 * max(t_ops, t_mem) / per_chunk,
            "bound": "compute" if t_ops >= t_mem else "memory"}


def shape_of(job) -> dict:
    pl = job.cfg["plan"]
    return {"n": job.batch, "p": job.p, "p_pad": job.p_pad, "m": job.m,
            "l": pl.get("rank"), "consumers": job.cfg["consumers"]}
