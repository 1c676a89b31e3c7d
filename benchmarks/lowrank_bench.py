"""Low-rank spectral path: ingest throughput + peak accumulator bytes vs the
dense / compact (p, p) covariance paths.

Fits ``SparsifiedPCA`` on a spiked stream with ``cov_path`` = dense, compact,
lowrank(range), lowrank(fd) and records rows/sec per path plus the byte size of
each path's covariance accumulator — the headline: the (p, p) accumulator
(p²·4 bytes) shrinks to the O(l·p) lowrank state, asserted here so a
regression that silently re-materializes (p, p) fails CI. A subspace sanity
check (principal angle vs the dense path) guards against winning the memory
game by returning garbage.

Writes ``BENCH_lowrank.json`` (name, us_per_call, rows/sec, accumulator_bytes,
max angle) — uploaded as a CI artifact by the lowrank-bench job.
"""
from __future__ import annotations

import json
import os
import sys

import jax

from benchmarks.common import emit, max_angle_sin as _max_angle_sin, spiked, timeit
from repro.api import Plan, SparsifiedPCA

RECORDS: list[dict] = []


def _spiked(n, p, k):
    return spiked(jax.random.PRNGKey(0), n, p, k)


def _state_bytes(est: SparsifiedPCA) -> int:
    st = est._reducer.state
    if st is None:  # batch dense/compact: the retained sketch IS the state
        return (est._reducer.retained.nbytes if est._reducer.retained is not None else 0)
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(st))


def record(name, us, rows, acc_bytes, angle=None):
    rec = {"name": name, "us_per_call": round(us, 1),
           "rows_per_sec": round(rows / (us / 1e6)),
           "accumulator_bytes": int(acc_bytes)}
    if angle is not None:
        rec["max_angle_sin_vs_dense"] = round(angle, 6)
    RECORDS.append(rec)
    extra = f"rows_per_sec={rec['rows_per_sec']:,} acc_bytes={acc_bytes:,}"
    if angle is not None:
        extra += f" angle={angle:.1e}"
    emit(name, us, extra)


def run(json_path: str = "BENCH_lowrank.json"):
    RECORDS.clear()
    n, p, k, ell = 8192, 1024, 8, 64
    x = _spiked(n, p, k)
    base = Plan(backend="stream", gamma=0.05, batch_size=2048)

    paths = {
        "dense": base,
        "compact": base.replace(cov_path="compact"),
        "lowrank_range": base.replace(cov_path="lowrank", rank=ell),
        "lowrank_fd": base.replace(cov_path="lowrank", rank=ell, lowrank_method="fd"),
    }
    fitted, acc_bytes = {}, {}
    for name, plan in paths.items():
        def fit(plan=plan):
            est = SparsifiedPCA(k, plan, key=1).fit(x)
            return est

        est = fit()  # measured separately so the bytes probe isn't timed
        fitted[name], acc_bytes[name] = est, _state_bytes(est)
        us = timeit(lambda: fit().components_, warmup=1, iters=3)
        angle = (None if name == "dense"
                 else _max_angle_sin(est.components_, fitted["dense"].components_))
        record(f"lowrank/pca/{name}", us, n, acc_bytes[name], angle)

    # ---- the acceptance assertions -----------------------------------------
    pp_bytes = p * p * 4
    for name in ("lowrank_range", "lowrank_fd"):
        st = fitted[name]._reducer.state
        leaves = jax.tree.leaves(st)
        # O(l·p), and no leaf anywhere near a (p, p) materialization
        assert max(leaf.size for leaf in leaves) <= ell * p, (
            f"{name}: accumulator leaf larger than l·p")
        assert acc_bytes[name] <= 3 * ell * p * 4, (
            f"{name}: accumulator {acc_bytes[name]} bytes exceeds O(l·p)")
        assert acc_bytes[name] < pp_bytes / 4, (
            f"{name}: no memory win over the (p, p) accumulator")
    assert acc_bytes["dense"] >= pp_bytes  # what the lowrank path replaces

    # the memory win must not come from a garbage subspace. At the throughput
    # config's γ=0.05 the DENSE estimate is itself noise-dominated (the angle
    # is recorded above, not asserted); fidelity is asserted in the estimator-
    # noise-benign regime (γ=0.5 — the slow-lane acceptance test pins 1e-3 at
    # its full n; this is the cheap CI-bench guard).
    pf, kf, ellf, nf = 128, 4, 64, 8192
    xf = _spiked(nf, pf, kf)
    planf = Plan(backend="stream", gamma=0.5, batch_size=2048)
    df = SparsifiedPCA(kf, planf, key=1).fit(xf)
    planl = planf.replace(cov_path="lowrank", rank=ellf)
    lf = SparsifiedPCA(kf, planl, key=1).fit(xf)
    us = timeit(lambda: SparsifiedPCA(kf, planl, key=1).fit(xf).components_,
                warmup=0, iters=1)
    angle = _max_angle_sin(lf.components_, df.components_)
    record("lowrank/fidelity/gamma0.5", us, nf, _state_bytes(lf), angle)
    assert angle < 0.1, f"lowrank subspace drifted from the dense path: {angle}"

    out = os.environ.get("BENCH_LOWRANK_JSON", json_path)
    with open(out, "w") as f:
        json.dump({"records": RECORDS, "p": p, "rank": ell,
                   "pp_accumulator_bytes": pp_bytes}, f, indent=2)
    print(f"lowrank_bench: wrote {out} ({len(RECORDS)} records)", file=sys.stderr)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
