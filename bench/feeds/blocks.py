"""Feed ``blocks``: a closed loop of whole blocks through the ingest front door.

The job is driven as users call it: ``fit_many(plan, consumers,
first_block, finalize=False)``, then ``SharedSketchRun.partial_fit(block)``
for every later block, each call made as soon as the last returned, then
``finalize()`` and the fitted outputs read back to the host. The pool is
drawn once in set-up and cycled through; the cursor's chunk index keeps
advancing, so a repeated block gets fresh masks.

Parameters, from the mix's file:

- ``min_call_bytes``: a call is the fewest whole steps (``batch_size`` rows
  on each of ``n_shards``) that reach it;
- ``pool_rows``, ``pool_bytes``: the pool holds whole calls within both;
- ``min_calls``: calls the window makes at the least;
- ``rows_on``: ``host`` hands each call a numpy block, as a file reader
  would, so the host→device copy is part of the job; ``device`` hands it a
  block placed on the device in set-up;
- ``scan``: ``fit_many(..., scan=True)``, one jitted scan per call in place
  of the per-chunk host loop. The scan keeps no sketch, so such a run
  compares the folded state and outputs alone.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import harness as H


def schedule(cfg: dict, mx: dict, p: int) -> tuple[int, int]:
    """(rows per call, calls in the pool)."""
    step = int(cfg["plan"]["batch_size"]) * int(cfg["plan"].get("n_shards", 1))
    row_bytes = 4 * p
    steps = max(1, math.ceil(int(mx["min_call_bytes"]) / (step * row_bytes)))
    call_rows = steps * step
    max_rows = min(int(mx["pool_rows"]), int(mx["pool_bytes"]) // row_bytes)
    n_calls = max(1, max_rows // call_rows)
    return call_rows, n_calls


class Blocks:
    """The pool's blocks as the mix hands them over."""

    def __init__(self, job: H.Job):
        self.job = job
        self.on_device = job.mix.get("rows_on", "host") == "device"
        if self.on_device:
            import jax

            self.blocks = [jax.device_put(job.block(c)) for c in range(job.n_calls)]
            jax.block_until_ready(self.blocks)

    def __call__(self, call: int):
        if self.on_device:
            return self.blocks[call % self.job.n_calls]
        return self.job.block(call)

    def starts(self, call: int) -> list:
        """Pool rows of the chunks of call ``call``, in the cursor's order."""
        base = (call % self.job.n_calls) * self.job.call_rows
        return [base + i * self.job.batch for i in range(self.job.chunks_per_call)]


def _fit_many(api, plan, consumers, block, job: H.Job):
    return api.fit_many(plan, consumers, block, finalize=False,
                        scan=bool(job.mix.get("scan", False)))


def warm_up(api, job: H.Job, impl: str):
    """A separate run of ``warm_calls`` blocks (one, where the configuration
    gives none) and one finalize: every program the window uses is compiled,
    or loaded from the cache, here. A sharded state changes its placement
    after its first step, so a sharded cell warms two. Returns the blocks,
    for the window."""
    blocks = Blocks(job)
    plan, consumers = H.build_consumers(api, job, impl)
    run = _fit_many(api, plan, consumers, blocks(0), job)
    for call in range(1, int(job.cfg.get("warm_calls", 1))):
        run.partial_fit(blocks(call))
    run.finalize()
    H.extract(consumers, job)
    return blocks


def run_window(api, job: H.Job, impl: str, seconds: float, blocks: Blocks, *,
               trace_dir: str | None = None, counter=None) -> H.Window:
    """Start a fresh run with its first call (``fit_many``, part of set-up),
    then fold for ``seconds`` from its first ``partial_fit`` (at least the
    mix's ``min_calls`` calls in all), finalize, and read the outputs back:
    that is the window. With ``trace_dir`` the profiler records the
    window's calls, from a quiet device to a quiet one; ``counter`` counts
    what the window lowers."""
    import jax

    min_calls = int(job.mix.get("min_calls", 2))
    plan, consumers = H.build_consumers(api, job, impl)
    rng = np.random.default_rng(job.seed ^ 0x5EED)
    kept: list = []          # reservoir of (chunk, SparseRows)
    seen = 0
    call_s = []
    starts: list = []
    traced = None

    def keep(run):
        nonlocal seen
        s = run.cursor.last_sketch
        if s is None:
            return
        item = (run.cursor.chunk - 1, s)
        if len(kept) < H.CHUNK_SAMPLE:
            kept.append(item)
        else:
            j = int(rng.integers(0, seen + 1))
            if j < H.CHUNK_SAMPLE:
                kept[j] = item
        seen += 1

    run = _fit_many(api, plan, consumers, blocks(0), job)
    starts += blocks.starts(0)
    calls = 1
    keep(run)
    H.quiet(consumers, job)
    rows0, c0 = run.count, run.cursor.chunk
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=H.profile_options())
        win = jax.profiler.TraceAnnotation("bench.window")
        win.__enter__()
    if counter is not None:
        counter.on = True
    t0 = time.perf_counter()
    while calls < min_calls or time.perf_counter() - t0 < seconds:
        blk = blocks(calls)
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.partial_fit"):
            run.partial_fit(blk)
        call_s.append(time.perf_counter() - ts)
        starts += blocks.starts(calls)
        calls += 1
        keep(run)
    if trace_dir is not None:
        with jax.profiler.TraceAnnotation("bench.drain"):
            H.quiet(consumers, job)
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        chunks = run.cursor.chunk - c0
        traced = {"chunks": chunks, "steps": chunks // job.n_shards, "calls": calls - 1}
    with jax.profiler.TraceAnnotation("bench.finalize"):
        run.finalize()
        outputs = H.extract(consumers, job)
    t1 = time.perf_counter()
    if counter is not None:
        counter.on = False
    sketches = {j: (np.asarray(s.values), np.asarray(s.indices)) for j, s in kept}
    return H.Window(t0=t0, rows=run.count - rows0, count=run.count, seconds=t1 - t0,
                    outputs=outputs, sketches=sketches, fed_rows=calls * job.call_rows,
                    starts=starts, call_seconds=call_s, traced=traced,
                    unobserved=() if sketches else ("sketch.values",))
