"""Feed ``dataset``: one whole Alg. 1 job a window, through the ingest front door.

A job is the configuration's ``job_rows`` rows, handed over as host numpy
blocks of the mix's call size (the ``blocks`` feed's ``schedule``) and
folded in a closed loop: ``fit_many(plan, consumers, first_block,
finalize=False)``, ``partial_fit`` for every later block, then
``finalize()`` and the fitted outputs read back to the host. The blocks
cycle the pool; the cursor's chunk index keeps advancing, so a repeated
block gets fresh masks, which plays the part of a large corpus's deformed
copies.

The window is the whole job, from the first block to the outputs on the
host: ``--seconds`` does not set its length. With ``trace_dir`` the
profiler records all of it, the finalize included. Set-up warms, in this
order, a job of one block (its outputs extracted, so a program that lacks
what the cell reads fails here, in seconds) and a job of the full size,
so that the window compiles nothing. ``Window.traced`` carries the chunks
and, for the finalize's readers, the Lloyd iterations the job ran
(``lloyd_steps``), the seeding steps and the hypotheses.
"""
from __future__ import annotations

import time

from bench import harness as H

ITERATIONS = "kmeans.lloyd.iterations"


def schedule(cfg: dict, mx: dict, p: int) -> tuple[int, int]:
    """(rows per call, calls in the pool), as the ``blocks`` feed plans them."""
    return H.feed("blocks").schedule(cfg, mx, p)


def job_calls(job: H.Job) -> int:
    return max(1, int(job.cfg["job_rows"]) // job.call_rows)


def _job(api, job: H.Job, impl: str, calls: int, after=None):
    """Fold ``calls`` blocks and finalize; ``after(run, call)`` follows each
    call's fold."""
    plan, consumers = H.build_consumers(api, job, impl)
    run = api.fit_many(plan, consumers, job.block(0), finalize=False)
    for call in range(calls):
        if call:
            run.partial_fit(job.block(call))
        if after is not None:
            after(run, call)
    run.finalize()
    return run, H.extract(consumers, job)


def warm_up(api, job: H.Job, impl: str):
    """A one-block job, then a full-size one, each finalized and read back."""
    _job(api, job, impl, 1)
    _job(api, job, impl, job_calls(job))
    return None


def _iterations() -> float:
    from repro import obs

    for m in obs.default_registry().metrics():
        if m.name == ITERATIONS:
            return float(m.value)
    return 0.0


def run_window(api, job: H.Job, impl: str, seconds: float, blocks, *,
               trace_dir: str | None = None, counter=None) -> H.Window:
    """One whole job, timed (and traced) from its first block to its outputs
    on the host; ``seconds`` is not used."""
    import jax
    import numpy as np

    del seconds, blocks
    calls = job_calls(job)
    kept: dict = {}
    rng = np.random.default_rng(job.seed ^ 0x5EED)
    sample = set(rng.choice(calls, size=min(H.CHUNK_SAMPLE, calls), replace=False).tolist())

    def keep(run, call):
        if call in sample and run.cursor.last_sketch is not None:
            kept[run.cursor.chunk - 1] = run.cursor.last_sketch

    it0 = _iterations()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=H.profile_options())
        win = jax.profiler.TraceAnnotation("bench.window")
        win.__enter__()
    if counter is not None:
        counter.on = True
    t0 = time.perf_counter()
    run, outputs = _job(api, job, impl, calls, after=keep)
    t1 = time.perf_counter()
    if counter is not None:
        counter.on = False
    traced = None
    if trace_dir is not None:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        chunks = run.cursor.chunk
        traced = {"chunks": chunks, "steps": chunks // job.n_shards, "calls": calls,
                  "lloyd_steps": _iterations() - it0,
                  "seed_steps": sum(int(c["k"]) - 1 for c in job.cfg["consumers"]),
                  "hypotheses": sum(int(c.get("n_init", 3)) for c in job.cfg["consumers"])}
    starts = [(call % job.n_calls) * job.call_rows + i * job.batch
              for call in range(calls) for i in range(job.chunks_per_call)]
    sketches = {j: (np.asarray(s.values), np.asarray(s.indices)) for j, s in kept.items()}
    return H.Window(t0=t0, rows=run.count, count=run.count, seconds=t1 - t0,
                    outputs=outputs, sketches=sketches, fed_rows=calls * job.call_rows,
                    starts=starts, call_seconds=[], traced=traced,
                    unobserved=() if sketches else ("sketch.values",))
