"""fold_roofline: the folds' share of their roofline, in %.

Essential work of folding one chunk's sketch (n rows, m kept of p_pad,
values f32 and indices int32) into every consumer of the cell, counted
from shapes, never from the implementation's schedule:

- the sketch is read once for all consumers: n·m·8 bytes;
- each consumer's own fold, by the ``fold_work`` of its kind's file
  (``bench/consumers/<kind>.py``), where the reasoning is written.

The share is max(operations / peak rate, bytes / bandwidth) over the folds'
device time per chunk (``fold.device_ms_per_chunk``); ``bound`` says which.
"""
from bench.harness import consumer, metric_reader


def work(shape: dict) -> tuple[float, float]:
    """(operations, bytes) of one chunk's fold into every consumer."""
    ops, nbytes = 0.0, float(shape["n"] * shape["m"] * 8)
    for c in shape["consumers"]:
        o, b = consumer(c["kind"]).fold_work(c, shape)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def read(ctx):
    if ctx.peaks is None:
        return None
    ns = metric_reader("fold.device_ms_per_chunk").device_ns(ctx)
    if ns <= 0 or ctx.chunks <= 0:
        return None
    ops, nbytes = work(metric_reader("sketch_roofline").shape_of(ctx.job))
    t_ops, t_mem = ops / ctx.peaks["flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"]
    per_chunk = ns / 1e9 / ctx.chunks
    return {"value": 100.0 * max(t_ops, t_mem) / per_chunk,
            "bound": "compute" if t_ops >= t_mem else "memory"}
