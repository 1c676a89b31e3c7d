"""lloyd_roofline: one Lloyd iteration's share of its roofline, in %.

Essential work of one iteration of every hypothesis over the n retained
rows (m kept each), by the ``fold_work`` of the cell's ``kmeans_lloyd``
consumer (``bench/consumers/kmeans_lloyd.py``): the sparse form's
3·n·m·K operations a hypothesis and the sketch read once. The kernels
contract a densified tile instead, n·p_pad·K products and more at full f32
precision; that extra work is not credited.

The share is max(operations / peak rate, bytes / bandwidth) over the
iteration's device time (``lloyd.device_ms_per_iter``); ``bound`` says
which.
"""
from bench.harness import consumer, metric_reader


def read(ctx):
    if ctx.peaks is None:
        return None
    got = metric_reader("lloyd.device_ms_per_iter").read(ctx)
    if got is None:
        return None
    job = ctx.job
    shape = {"n": ctx.window.rows, "m": job.m, "p_pad": job.p_pad}
    ops = nbytes = 0.0
    for c in job.cfg["consumers"]:
        if c["kind"] == "kmeans_lloyd":
            o, b = consumer(c["kind"]).fold_work(c, shape)
            ops, nbytes = ops + o, nbytes + b
    if ops <= 0:
        return None
    t_ops, t_mem = ops / ctx.peaks["flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"]
    return {"value": 100.0 * max(t_ops, t_mem) / (got["value"] / 1e3),
            "bound": "compute" if t_ops >= t_mem else "memory"}
