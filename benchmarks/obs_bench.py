"""Telemetry overhead + reconciliation benchmark → ``BENCH_obs.json``.

Three claims of the observability layer (repro.obs), each measured and gated,
and the cost of one span (recorded, not gated):

1. **Zero-cost disabled** — an engine run with ``telemetry=None`` (the
   default) vs the pre-obs loop shape: the telemetry branch is one
   ``if tel is None`` per step, so the run must sit within noise of itself
   across repeats (gated loosely at ≤5% spread — pure run-to-run noise).
2. **≤3% enabled** — the SAME run with a full :class:`EngineTelemetry`
   (registry + spans + per-step records into a JSONL StepLogger) must cost
   ≤3% wall time over the telemetry-off median. The JSONL goes to
   ``obs_smoke.jsonl`` and is uploaded as a CI artifact next to the JSON.
3. **Exact reconciliation at 256 tenants** — a 256-tenant SketchService run
   where every registry metric the serving layer exposes (request counters,
   coalesce histogram, queue-depth/pending gauges, submit→resolve latency
   count) reconciles EXACTLY with the known request totals — metrics that
   drift from the truth are worse than no metrics.

The span cost is timed with no profiler running, as the ingest path runs
outside a traced window: a bare ``jax.profiler.TraceAnnotation``, and an
``obs.span`` with no registry nested under a call span, with no attribute
and with the four of a chunk span.

CI runs this as the ``obs-bench`` job and uploads both artifacts so the
overhead trajectory accumulates across commits.
"""
from __future__ import annotations

import json
import os
import sys
import time
import timeit

import jax
import numpy as np

from benchmarks.common import emit
from repro import obs
from repro.core import sketch
from repro.stream import EngineTelemetry, StreamEngine

RECORDS: list[dict] = []

P_DIM = 512
BATCH = 256
STEPS = 60
REPEATS = 5


def record(name: str, us: float, **extra):
    rec = {"name": name, "us_per_call": round(us, 1), **extra}
    RECORDS.append(rec)
    derived = " ".join(f"{k}={v}" for k, v in extra.items()
                       if isinstance(v, (int, float, str)))
    emit(name, us, derived)


# ------------------------------------------------------- engine overhead ----


def _make_engine():
    spec = sketch.make_spec(P_DIM, jax.random.PRNGKey(1), gamma=0.1)
    data = np.asarray(jax.random.normal(jax.random.PRNGKey(0),
                                        (8, BATCH, P_DIM)))
    return StreamEngine(spec, lambda seed, step, shard: data[step % 8],
                        track_cov=True)


def _run_once(engine, telemetry=None) -> float:
    t0 = time.perf_counter()
    res = engine.run(STEPS, telemetry=telemetry)
    jax.block_until_ready(res.mean)
    return time.perf_counter() - t0


def engine_overhead(jsonl_path: str) -> None:
    engine = _make_engine()
    _run_once(engine)   # compile once; every arm below is steady-state

    off = sorted(_run_once(engine) for _ in range(REPEATS))
    t_off = off[len(off) // 2]

    def _tel(logger):
        return EngineTelemetry(registry=obs.MetricsRegistry(),
                               step_logger=logger)

    with open(jsonl_path, "w") as f:
        on = sorted(_run_once(engine, _tel(obs.StepLogger(stream=f)))
                    for _ in range(REPEATS))
    t_on = on[len(on) // 2]

    noise = (off[-1] - off[0]) / t_off
    overhead = t_on / t_off - 1.0
    rows = STEPS * BATCH
    record("obs/engine/telemetry_off", t_off / STEPS * 1e6,
           rows_per_sec=round(rows / t_off), repeats=REPEATS,
           noise_spread=round(noise, 4))
    record("obs/engine/telemetry_on", t_on / STEPS * 1e6,
           rows_per_sec=round(rows / t_on),
           overhead_frac=round(overhead, 4))

    smoke = obs.read_jsonl(jsonl_path)
    assert len(smoke) == STEPS * REPEATS, (
        f"telemetry JSONL has {len(smoke)} records, expected "
        f"{STEPS} steps x {REPEATS} repeats")
    assert smoke[-1]["rows_total"] == rows, (
        "telemetry JSONL does not cover the run")
    assert overhead <= 0.03, (
        f"enabled telemetry costs {overhead * 100:.1f}% (> 3% gate) — "
        f"off={t_off:.4f}s on={t_on:.4f}s")


# ------------------------------------------------------------ span cost -----


def span_cost(number: int = 100_000, repeat: int = 15) -> None:
    from jax.profiler import TraceAnnotation

    def best(fn) -> float:
        return min(timeit.repeat(fn, number=number, repeat=repeat)) / number * 1e6

    def bare():
        with TraceAnnotation("ingest.partial_fit.chunk"):
            pass

    def plain():
        with obs.span("sketch"):
            pass

    def chunk():
        with obs.span("chunk", chunk=5, step=5, shard=0, rows=4096):
            pass

    record("obs/span/bare_annotation", best(bare))
    with obs.span("ingest.partial_fit", call=0, rows=8192):
        record("obs/span/nested_no_attrs", best(plain))
        record("obs/span/nested_chunk_attrs", best(chunk))


# ------------------------------------------- 256-tenant exact reconcile -----


def serve_reconcile(n_tenants: int = 256) -> None:
    from repro.api import Plan
    from repro.sketchserve import SketchService

    rng = np.random.default_rng(0)
    plan = Plan(backend="stream", gamma=0.25, batch_size=128,
                cov_path="lowrank", rank=4)
    groups = 32
    rows_per, n_queries, n_rejected = 16, 32, 4
    rows = rng.normal(size=(rows_per, 64)).astype(np.float32)
    # sized so the workload's own ingest always admits, while one deliberately
    # oversized request per rejection deterministically trips the per-group cap
    cap = (2 * n_tenants // groups) * rows_per + rows_per
    too_big = np.zeros((cap + 1, 64), np.float32)

    t0 = time.perf_counter()
    with SketchService(max_queue=8 * n_tenants, max_batch=64,
                       max_pending_rows=cap) as svc:
        for i in range(n_tenants):
            svc.create_tenant(f"t{i}", "pca" if i % 2 else "mean", plan=plan,
                              key=1, group=f"g{i % groups}",
                              **({"n_components": 2} if i % 2 else {}))
        futs = [svc.ingest(f"g{i % groups}", rows)
                for i in range(2 * n_tenants)]
        assert all(f.result(120).ok for f in futs)
        # deterministic backpressure: a single request larger than the cap is
        # rejected at submit — and MUST still be latency-accounted below
        for i in range(n_rejected):
            r = svc.ingest(f"g{i}", too_big).result(120)
            assert r.status == "rejected", r
        for i in range(n_queries):
            svc.query(f"t{2 * i + 1}", "components").unwrap()
        stats = svc.stats
        reg = svc.registry
        dt = time.perf_counter() - t0

        n_ingest = 2 * n_tenants
        assert stats["ingest_requests"] == n_ingest
        assert stats["ingest_rows"] == n_ingest * rows_per
        assert stats["queries"] == n_queries
        assert stats["rejected"] == n_rejected
        assert stats["requests"] == n_ingest + n_queries + n_tenants
        # every ingest request is accounted to exactly one coalesced fold
        h_coal = reg.histogram("serve.coalesced_requests")
        assert h_coal.sum == n_ingest and h_coal.count == stats["ingest_folds"]
        # everything admitted was folded; the backlog gauges settled to zero
        assert reg.gauge("serve.pending_rows").value == 0
        assert reg.gauge("serve.queue_depth").value == 0
        # every request's submit→resolve latency was observed — INCLUDING the
        # rejected ones (the submit fast path must route through _resolve_fut,
        # not bare set_result; rejections invisible to the latency histogram
        # would understate tail latency exactly when the service is saturated)
        h_lat = reg.histogram("serve.request_seconds")
        assert h_lat.count == n_ingest + n_queries + n_tenants + n_rejected
        # the exposition renders every serving series (scrape-ready)
        text = obs.render_exposition(reg)
        for needle in ("serve_queue_depth", "serve_pending_rows",
                       "serve_request_seconds_count",
                       "serve_coalesced_requests_count"):
            assert needle in text, f"exposition is missing {needle}"
        lat_p50, lat_p99 = h_lat.quantile(0.5, 0.99)

    coalesce = n_ingest / max(stats["ingest_folds"], 1)
    record(f"obs/serve/reconcile/{n_tenants}", dt / n_ingest * 1e6,
           tenants=n_tenants, ingest_requests=n_ingest,
           requests_per_fold=round(coalesce, 2),
           latency_p50_ms=round(lat_p50 * 1e3, 2),
           latency_p99_ms=round(lat_p99 * 1e3, 2),
           reconciled=True)


def run(json_path: str = "BENCH_obs.json"):
    RECORDS.clear()
    jsonl = os.environ.get("OBS_SMOKE_JSONL", "obs_smoke.jsonl")
    engine_overhead(jsonl)
    span_cost()
    serve_reconcile()
    out = os.environ.get("BENCH_OBS_JSON", json_path)
    with open(out, "w") as f:
        json.dump({"records": RECORDS}, f, indent=2)
    print(f"obs_bench: wrote {out} ({len(RECORDS)} records)", file=sys.stderr)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
