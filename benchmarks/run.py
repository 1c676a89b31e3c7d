"""Benchmark runner — one module per paper table/figure (+ framework benches).

Prints ``name,us_per_call,derived`` CSV. See DESIGN.md §6 for the experiment
index; EXPERIMENTS.md records the reference outputs and their interpretation.

The api_bench suite additionally writes ``BENCH_api.json`` (rows/sec, backend,
γ per measurement, including the fused fit_many ingest speedup) — CI uploads
it as an artifact so the perf trajectory accumulates across commits.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (
        api_bench,
        bigdata_kmeans,
        cluster_bench,
        fig1_explained_variance,
        fig2_mean_bound,
        fig3_cov_bound,
        fig4_precond_effect,
        fig5_hk_concentration,
        fig7_kmeans_accuracy,
        fig8_kmeans_timing,
        grad_compress_bench,
        kernel_bench,
        lowrank_bench,
        obs_bench,
        refine_bench,
        serve_bench,
        stream_bench,
    )

    suites = [
        ("fig1_explained_variance", fig1_explained_variance.run),
        ("fig2_mean_bound", fig2_mean_bound.run),
        ("fig3_cov_bound", fig3_cov_bound.run),
        ("fig4_precond_effect", fig4_precond_effect.run),
        ("fig5_hk_concentration", fig5_hk_concentration.run),
        ("fig7_kmeans_accuracy", fig7_kmeans_accuracy.run),
        ("fig8_kmeans_timing", fig8_kmeans_timing.run),
        ("bigdata_kmeans", bigdata_kmeans.run),
        ("kernel_bench", kernel_bench.run),
        ("grad_compress_bench", grad_compress_bench.run),
        ("stream_bench", stream_bench.run),
        ("api_bench", api_bench.run),
        ("lowrank_bench", lowrank_bench.run),
        ("refine_bench", refine_bench.run),
        ("serve_bench", serve_bench.run),
        ("cluster_bench", cluster_bench.run),
        ("obs_bench", obs_bench.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if only and only not in name:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"suite/{name},{(time.time()-t0)*1e6:.0f},ok", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"suite/{name},{(time.time()-t0)*1e6:.0f},FAILED", flush=True)
    if failures:
        raise SystemExit(failures)


if __name__ == "__main__":
    main()
