#!/usr/bin/env python3
"""Bring-up smoke of the sketch-ingest path on a TPU.

    python3 chip_smoke.py              # three phases on one chip
    python3 chip_smoke.py --chips 4    # phase 1 sharded over four chips vs one
    python3 chip_smoke.py --rehearse [--chips 4]   # tiny shapes on the CPU

Drives the README's front door (``repro.api`` estimators, ``fit_many`` and
``SketchService``) once, in this one process, at the sizes its users run, and
checks every answer against a plain float32 ``jax.numpy`` reference computed
on the same device: the same ``Plan`` with ``impl="ref"`` under
``jax.default_matmul_precision("highest")``, and the ``repro.kernels.ref``
oracles at each phase's shapes. Data is drawn on the device from ``--seed``.

1. Dense high-dimensional streaming PCA: p = 65536, gamma = 0.05, spiked
   rows, 2048-row batches, ``cov_path="lowrank"`` with l = 128. The sketch
   runs the chunked FWHT and a gather; the fold runs ``spmm``/``spmm_t``.
2. p = 1024 ingest: ``fit_many`` over the mean, the dense-path covariance and
   minibatch K-means (K = 10) of ten-cluster rows, through the single-tile
   fused sketch kernel; the K-means kernels' ``kmeans_assign`` and
   ``kmeans_dists`` are checked on the phase's sketch.
3. The sketch service at p = 4096: groups of co-registered PCA and K-means
   tenants take ingest requests and queries through ``SketchService``; every
   response must be ``ok`` and equal a direct ``fit_many`` of the same rows.

``--chips 4`` runs phase 1 alone under ``Plan(backend="sharded",
n_shards=4)`` over a four-device mesh, and compares it with the same plan
under ``backend="stream"`` on one device: the accumulated range states and
the top-k eigenpairs finalized at rank k must agree to 1e-5. The fits' own
eigenmodels (rank l/2) are printed beside them: their basis cut lies inside
the noise bulk, which magnifies the states' float-sum-order gap.

Every phase prints its shapes, its compile time (a first, one-step fit) apart
from its run time (the full fit, warm), its parity errors, the
``kernels.dispatch{op=,path=}`` counters of the run under test, and the
device's ``peak_bytes_in_use`` so far. A dispatch to ``ref`` or
``interpret`` in the run under test fails the phase: it did not run the
kernels.

Tolerances, and why they are fair:

- a kernel's output against its oracle: max|a - b| / max|b| <= 1e-3. A
  relative perturbation e of the kept values moves a mean estimate by at most
  e and a second moment by about 2e (relative), and every estimator here
  carries a sampling error of several 1e-2 at these sizes (each phase prints
  its own), so 1e-3 stays an order of magnitude below it. A misplaced
  element in an index walk shows up as an error of order one.
- an estimator's output against the reference plan: relative norm <= 1e-2
  (for a subspace, the sine of its largest principal angle). The estimator
  under test runs its XLA matmuls at the device's default precision, as users
  run it; the reference at "highest". Each check prints the estimator's
  sampling error beside it: the same quantity measured against the truth the
  rows were drawn from, or against the exact statistic of the same rows.
- service against direct ``fit_many`` and sharded against one device: the
  same kernels on the same rows, so only the order of float sums differs:
  relative norm <= 1e-5, the agreement the README states for its backends.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``, printed only when every phase passed on a TPU. Without a
TPU the script exits non-zero and prints no result; it never carries on on
the CPU. ``--rehearse`` is the exception: it runs the same phases at tiny
shapes on the CPU with the Pallas kernels in interpret mode (with
``--chips 4``, on four forced host devices), and its last line says
``"rehearsal"``, never ``"ok"``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# full sizes (one chip) and the rehearsal's tiny ones
SIZES = {
    "chip": dict(
        p1=dict(p=1 << 16, gamma=0.05, batch=2048, steps=8, rank=128, comps=8),
        p2=dict(p=1024, gamma=0.05, batch=2048, steps=8, k=10),
        p3=dict(p=4096, gamma=0.05, batch=512, groups=3, requests=12, rank=32,
                comps=4, k=8, predict_rows=64)),
    "rehearse": dict(
        p1=dict(p=1 << 16, gamma=0.002, batch=16, steps=2, rank=16, comps=4),
        p2=dict(p=1024, gamma=0.05, batch=64, steps=2, k=10),
        p3=dict(p=4096, gamma=0.05, batch=32, groups=2, requests=3, rank=16,
                comps=2, k=4, predict_rows=8)),
}

KERNEL_TOL = 1e-3      # max-abs relative, kernel output vs its oracle
ESTIMATE_TOL = 1e-2    # relative norm, estimator vs the reference plan
SAME_PATH_TOL = 1e-5   # relative norm, the same kernels summed in another order


def _args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU, kernels in interpret mode")
    return ap.parse_args(argv)


class Phase:
    """One phase's printed record and its failures."""

    def __init__(self, name: str):
        self.failures = []
        print(f"== {name}", flush=True)

    def line(self, text: str) -> None:
        print(f"  {text}", flush=True)

    def check(self, what: str, err: float, tol: float,
              sampling: float | None = None) -> None:
        ok = bool(err == err and err <= tol)   # NaN fails
        extra = "" if sampling is None else f"  sampling_err={sampling:.3e}"
        self.line(f"parity {what}: err={err:.3e} tol={tol:.0e}{extra}"
                  f"  {'ok' if ok else 'MISS'}")
        if not ok:
            self.failures.append(f"{what}: {err:.3e} > {tol:.0e}")

    def require(self, what: str, cond: bool) -> None:
        self.line(f"check {what}: {'ok' if cond else 'FAILED'}")
        if not cond:
            self.failures.append(what)


def main(argv=None) -> int:
    args = _args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import jax
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run from the repo root",
              file=sys.stderr)
        return 2

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"devices: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if not args.rehearse and device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this smoke does not run on "
              f"{device['platform']!r} (use --rehearse for the CPU rehearsal)",
              file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 3
    cache = Path(compile_cache.enable())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({warm} entries at start)", flush=True)

    ctx = _Context(args, jax)
    phases = ([("phase 1 (4 chips): sharded vs one device", ctx.phase_sharded)]
              if args.chips == 4 else
              [("phase 1: dense high-dimensional streaming PCA", ctx.phase_pca),
               ("phase 2: p=1024 ingest, mean + dense cov + minibatch K-means",
                ctx.phase_ingest),
               ("phase 3: sketch service", ctx.phase_service)])
    failed = []
    for name, fn in phases:
        ph = Phase(name)
        try:
            fn(ph)
        except Exception:   # a crashed phase fails the smoke, the rest still run
            traceback.print_exc()
            ph.failures.append("raised")
        ctx.report_memory(ph)
        if ph.failures:
            failed.append(f"{name}: {'; '.join(ph.failures)}")
    if failed:
        for f in failed:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


class _Context:
    """What the phases share: sizes, the kernel mode under test, the seed."""

    def __init__(self, args, jax):
        self.jax = jax
        self.sizes = SIZES["rehearse" if args.rehearse else "chip"]
        self.seed = args.seed
        # the path the run under test must dispatch to, and the Plan.impl
        # that gets it there: users' "auto" on the chip
        self.path = "interpret" if args.rehearse else "kernel"
        self.impl = "interpret" if args.rehearse else "auto"

    # ------------------------------------------------------------ helpers --

    def key(self, tag: int):
        return self.jax.random.fold_in(self.jax.random.PRNGKey(self.seed), tag)

    def dispatch_counts(self) -> dict:
        from repro import obs

        return {(m.labels["op"], m.labels["path"]): m.value
                for m in obs.default_registry().metrics()
                if m.name == "kernels.dispatch"}

    def check_dispatch(self, ph: Phase, before: dict, ops: tuple) -> None:
        """The run under test dispatched every op in ``ops`` to the kernels and
        nothing to ``ref`` or ``interpret`` (the rehearsal: to ``interpret``)."""
        after = self.dispatch_counts()
        delta = {k: v - before.get(k, 0) for k, v in after.items()
                 if v - before.get(k, 0)}
        ph.line("dispatch: " + (" ".join(f"{op}/{path}={n}" for (op, path), n
                                         in sorted(delta.items())) or "none"))
        stray = [f"{op}/{path}" for (op, path) in delta
                 if not path.startswith(self.path)]
        ph.require(f"no dispatch off the {self.path} path ({', '.join(stray) or 'none'})",
                   not stray)
        missing = [op for op in ops
                   if not any(o == op for (o, _) in delta)]
        ph.require(f"kernel dispatch seen for {', '.join(ops)}", not missing)

    def report_memory(self, ph: Phase) -> None:
        peaks = []
        for d in self.jax.devices():
            stats = d.memory_stats() or {}
            peaks.append(stats.get("peak_bytes_in_use"))
        ph.line("peak_bytes_in_use so far: " +
                ", ".join("not reported" if b is None else f"{b} ({b / 2**20:.1f} MiB)"
                          for b in peaks))

    def rel(self, a, b) -> float:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))

    def max_rel(self, a, b) -> float:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-300))

    def subspace(self, a, b) -> float:
        """Sine of the largest principal angle between the row spaces of a, b."""
        qa, _ = np.linalg.qr(np.asarray(a, np.float64).T)
        qb, _ = np.linalg.qr(np.asarray(b, np.float64).T)
        s = np.linalg.svd(qa.T @ qb, compute_uv=False)
        return float(np.sqrt(max(0.0, 1.0 - float(s.min()) ** 2)))

    def matched_rel(self, a, b) -> float:
        """Relative distance of each row of b to its nearest row of a
        (centers come back in an order the fit chose)."""
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        d2 = ((b[:, None, :] - a[None, :, :]) ** 2).sum(-1).min(1)
        return float(np.sqrt(d2.sum()) / max(np.linalg.norm(b), 1e-300))

    def timed(self, fn):
        t0 = time.perf_counter()
        out = fn()
        self.jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    # ---------------------------------------------------------------- data --

    def spiked_source(self, p: int, batch: int, k: int):
        """(seed, step, shard) → (batch, p) rows of covariance U·diag(lam)·Uᵀ
        + I, drawn on the device; returns (source, U (p, k), lam).

        The sparsification noise of the estimate grows with each row's energy
        (about 2e3 at p = 65536 and unit noise, gamma = 0.05, 16384 rows), so
        the k spikes sit at 3e4 to 4e4: well clear of it, and of each other
        by less than the gap to it."""
        jax, jnp = self.jax, self.jax.numpy
        u, _ = jnp.linalg.qr(jax.random.normal(self.key(1), (p, k)))
        lam = jnp.linspace(40000.0, 30000.0, k)
        root = self.key(2)

        @jax.jit
        def rows(step, shard):
            kk = jax.random.fold_in(jax.random.fold_in(root, step), shard)
            z = jax.random.normal(jax.random.fold_in(kk, 0), (batch, k)) * jnp.sqrt(lam)
            noise = jax.random.normal(jax.random.fold_in(kk, 1), (batch, p))
            return jnp.dot(z, u.T, precision="highest") + noise

        return (lambda seed, step, shard: rows(step, shard)), u, lam

    def clustered_rows(self, tag: int, n: int, p: int, k: int, scale: float):
        """(rows (n, p), centers (k, p)): k Gaussian clusters of unit noise."""
        jax = self.jax
        key = self.key(tag)
        centers = scale * jax.random.normal(jax.random.fold_in(key, 0), (k, p))
        labels = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, k)
        noise = jax.random.normal(jax.random.fold_in(key, 2), (n, p))
        return centers[labels] + noise, centers

    def kernel_parity(self, ph: Phase, what: str, kernel_out, ref_out) -> None:
        ph.check(f"{what} kernel vs oracle (max-abs rel)",
                 self.max_rel(kernel_out, ref_out), KERNEL_TOL)

    # -------------------------------------------------------------- phases --

    def phase_pca(self, ph: Phase) -> None:
        from repro.api import Plan, SparsifiedPCA
        from repro.core import ros
        from repro.kernels import ops, ref
        from repro import lowrank

        c = self.sizes["p1"]
        p, batch, steps = c["p"], c["batch"], c["steps"]
        source, u, lam = self.spiked_source(p, batch, c["comps"])
        plan = Plan(backend="stream", gamma=c["gamma"], batch_size=batch,
                    cov_path="lowrank", rank=c["rank"], impl=self.impl)
        before = self.dispatch_counts()
        _, t_compile = self.timed(lambda: SparsifiedPCA(c["comps"], plan, key=3)
                                  .fit_stream(source, steps=1).explained_variance_)
        pca, t_run = self.timed(lambda: SparsifiedPCA(c["comps"], plan, key=3)
                                .fit_stream(source, steps=steps))
        spec = pca.spec_
        state = pca._reducer.state
        ph.line(f"shapes: p={p} gamma={c['gamma']} m={spec.m} batch=({batch}, {p}) "
                f"f32 = {batch * p * 4 / 2**20:.1f} MiB on the device, steps={steps}, "
                f"rank={c['rank']}, state={state.nbytes() / 1e6:.1f} MB")
        ph.line(f"compile_s={t_compile:.3f} (first 1-step fit incl. finalize)  "
                f"run_s={t_run:.3f} ({steps}-step fit, warm; "
                f"{steps * batch / t_run:.0f} rows/s, bring-up only, not a measurement)")

        # the kernels at the phase's shapes against their oracles
        x0 = source(None, 0, 0)
        s = pca.sketch(x0, mask_key=0)
        signs = ros.signs_for(spec.signs_key(), spec.p_pad)
        self.kernel_parity(ph, "sketch (chunked FWHT + gather)", s.values,
                           ref.ref_sketch_fused(x0, signs, s.indices))
        om = lowrank.omega(spec.key, spec.p_pad, c["rank"])
        with self.jax.default_matmul_precision("highest"):
            t_ref = ref.ref_spmm(s.values, s.indices, om)
            y_ref = ref.ref_spmm_t(s.values, s.indices, t_ref, spec.p_pad)
        self.kernel_parity(ph, "spmm", ops.spmm(s.values, s.indices, om, mode=self.path),
                           t_ref)
        self.kernel_parity(ph, "spmm_t",
                           ops.spmm_t(s.values, s.indices, t_ref, spec.p_pad,
                                      mode=self.path), y_ref)
        self.check_dispatch(ph, before, ("hd_precondition", "sketch_fused", "spmm", "spmm_t"))

        with self.jax.default_matmul_precision("highest"):
            want = SparsifiedPCA(c["comps"], plan.replace(impl="ref"), key=3) \
                .fit_stream(source, steps=steps)
        ev, ev_ref = np.asarray(pca.explained_variance_), np.asarray(want.explained_variance_)
        truth = np.asarray(lam) + 1.0
        ph.line(f"eigenvalues: kernel {np.round(ev, 1).tolist()}")
        ph.line(f"             ref    {np.round(ev_ref, 1).tolist()}")
        ph.line(f"             truth  {np.round(truth, 1).tolist()}")
        ph.check("eigenvalues vs ref plan", self.rel(ev, ev_ref), ESTIMATE_TOL,
                 sampling=self.rel(ev_ref, truth))
        ph.check("top-k subspace vs ref plan",
                 self.subspace(pca.components_, want.components_), ESTIMATE_TOL,
                 sampling=self.subspace(want.components_, np.asarray(u).T))

    def phase_ingest(self, ph: Phase) -> None:
        from repro.api import (Plan, SparsifiedCov, SparsifiedKMeans,
                               SparsifiedMean, fit_many)
        from repro.core import kmeans, ros
        from repro.kernels import ops, ref

        jnp = self.jax.numpy
        c = self.sizes["p2"]
        p, batch, steps, k = c["p"], c["batch"], c["steps"], c["k"]
        x, centers = self.clustered_rows(4, steps * batch, p, k, scale=4.0)
        plan = Plan(backend="stream", gamma=c["gamma"], batch_size=batch,
                    impl=self.impl)

        def consumers(pl):
            return [SparsifiedMean(pl, key=5), SparsifiedCov(pl, key=5),
                    SparsifiedKMeans(k, pl, key=5, algorithm="minibatch")]

        def fit(pl, data):
            run = fit_many(pl, consumers(pl), data)
            return run, [run[0].mean_, run[1].cov_, run[2].centers_]

        before = self.dispatch_counts()
        _, t_compile = self.timed(lambda: fit(plan, x[:batch])[1])
        (run, outs), t_run = self.timed(lambda: fit(plan, x))
        spec = run.spec
        ph.line(f"shapes: p={p} gamma={c['gamma']} m={spec.m} rows={x.shape[0]} in "
                f"{steps} batches of {batch}, K={k}; cov state ({p}, {p}) f32")
        ph.line(f"compile_s={t_compile:.3f} (first 1-batch fit_many)  "
                f"run_s={t_run:.3f} ({steps}-batch fit_many, warm; bring-up only)")

        km = run[2]
        s = km.sketch(x[:batch], mask_key=0)
        signs = ros.signs_for(spec.signs_key(), spec.p_pad)
        self.kernel_parity(ph, "sketch_fused (single tile)", s.values,
                           ref.ref_sketch_fused(x[:batch], signs, s.indices))
        vt, it = kmeans.rows_transposed(s.values, s.indices)
        n_s = s.values.shape[0]
        d_k = ops.kmeans_dists(vt, it, km.centers_pre_, mode=self.path)[:, :n_s]
        a_k = ops.kmeans_assign(vt, it, km.centers_pre_[None], mode=self.path)[0][0, :n_s]
        with self.jax.default_matmul_precision("highest"):
            d_r = ref.ref_kmeans_dists(s.values.T, s.indices.T, km.centers_pre_)
        self.kernel_parity(ph, f"kmeans_dists (K={k})", d_k, d_r)
        ph.require("kmeans_assign argmin equals the oracle's",
                   bool(np.array_equal(np.asarray(a_k), np.asarray(jnp.argmin(d_r, axis=0)))))
        self.check_dispatch(ph, before, ("sketch_fused", "kmeans_assign", "kmeans_dists"))

        with self.jax.default_matmul_precision("highest"):
            _, want = fit(plan.replace(impl="ref"), x)
            exact_mean = jnp.mean(x, axis=0)
            # Thm 6 estimates the uncentered second moment, in the
            # preconditioned domain: compare against H·D·(XᵀX/n)·D·H
            y = ros.precondition(x, spec.signs_key(), impl="jnp")
            exact_cov = jnp.dot(y.T, y) / x.shape[0]
        ph.check("mean vs ref plan", self.rel(outs[0], want[0]), ESTIMATE_TOL,
                 sampling=self.rel(want[0], exact_mean))
        ph.check("dense cov vs ref plan", self.rel(outs[1], want[1]), ESTIMATE_TOL,
                 sampling=self.rel(want[1], exact_cov))
        ph.check("K-means centers vs ref plan", self.matched_rel(outs[2], want[2]),
                 ESTIMATE_TOL, sampling=self.matched_rel(want[2], centers))

    def phase_service(self, ph: Phase) -> None:
        from repro.api import Plan, SparsifiedKMeans, SparsifiedPCA, fit_many
        from repro.sketchserve import SketchService

        c = self.sizes["p3"]
        p, batch, groups, nreq = c["p"], c["batch"], c["groups"], c["requests"]
        plan = Plan(backend="stream", gamma=c["gamma"], batch_size=batch,
                    cov_path="lowrank", rank=c["rank"], impl=self.impl)
        data, queries = [], []
        for g in range(groups):
            x, _ = self.clustered_rows(10 + g, nreq * batch + c["predict_rows"], p,
                                       c["k"], scale=2.0)
            x = np.asarray(x, np.float32)
            data.append(x[:nreq * batch])
            queries.append(x[nreq * batch:])
        ph.line(f"shapes: p={p} gamma={c['gamma']} groups={groups} x (PCA "
                f"{c['comps']} comps, l={c['rank']} + minibatch K-means K={c['k']}); "
                f"{groups * nreq} ingest requests of ({batch}, {p}), "
                f"{4 * groups} queries")

        def direct(g, pl):
            run = fit_many(pl, [SparsifiedPCA(c["comps"], pl, key=20 + g),
                                SparsifiedKMeans(c["k"], pl, key=20 + g,
                                                 algorithm="minibatch")],
                           data[g], scan=True)
            return (np.asarray(run[0].explained_variance_), np.asarray(run[0].components_),
                    np.asarray(run[1].centers_), np.asarray(run[1].predict(queries[g])))

        before = self.dispatch_counts()
        t0 = time.perf_counter()
        with SketchService(max_batch=64) as svc:
            for g in range(groups):
                svc.create_tenant(f"pca{g}", "pca", plan=plan, key=20 + g,
                                  n_components=c["comps"], group=f"g{g}")
                svc.create_tenant(f"km{g}", "kmeans", plan=plan, key=20 + g, k=c["k"],
                                  algorithm="minibatch", group=f"g{g}")
            futs = [svc.ingest(f"g{g}", data[g][r * batch:(r + 1) * batch])
                    for r in range(nreq) for g in range(groups)]
            acks = [f.result() for f in futs]
            answers = []
            for g in range(groups):
                answers.append([svc.query(f"pca{g}", "components"),
                                svc.query(f"km{g}", "centers"),
                                svc.query(f"km{g}", "predict", queries[g]),
                                svc.query(f"pca{g}", "stats")])
        t_service = time.perf_counter() - t0
        responses = acks + [r for a in answers for r in a]
        bad = [f"{r.status}: {r.error}" for r in responses if not r.ok]
        ph.require(f"all {len(responses)} responses ok ({'; '.join(bad[:3]) or 'none'})",
                   not bad)
        ph.line(f"service_s={t_service:.3f} (compile and run together: "
                f"{len(acks)} ingests, max coalesced "
                f"{max(a.info.get('coalesced', 0) for a in acks)}; bring-up only)")
        if bad:
            return
        ph.line(f"tenant state: {answers[0][3].result['state_bytes']} bytes per PCA tenant")
        fits = [direct(g, plan) for g in range(groups)]
        for g, (ev, comps, ctrs, pred) in enumerate(fits):
            got = answers[g]
            ph.check(f"g{g} eigenvalues service vs fit_many",
                     self.rel(got[0].result["explained_variance"], ev), SAME_PATH_TOL)
            ph.check(f"g{g} components service vs fit_many",
                     self.subspace(got[0].result["components"], comps), SAME_PATH_TOL)
            ph.check(f"g{g} centers service vs fit_many", self.rel(got[1].result, ctrs),
                     SAME_PATH_TOL)
            ph.require(f"g{g} predictions service equal fit_many",
                       bool(np.array_equal(got[2].result, pred)))
        self.check_dispatch(ph, before, ("sketch_fused", "spmm", "spmm_t"))
        for g, (ev, _, ctrs, _) in enumerate(fits):
            with self.jax.default_matmul_precision("highest"):
                ev_r, _, ctrs_r, _ = direct(g, plan.replace(impl="ref"))
            ph.check(f"g{g} eigenvalues fit_many vs ref plan", self.rel(ev, ev_r),
                     ESTIMATE_TOL)
            ph.check(f"g{g} centers fit_many vs ref plan",
                     self.matched_rel(ctrs, ctrs_r), ESTIMATE_TOL)

    def phase_sharded(self, ph: Phase) -> None:
        from repro import lowrank
        from repro.api import Plan, SparsifiedPCA
        from repro.stream import sharded as sharded_mod

        jax = self.jax
        c = self.sizes["p1"]
        n_shards = 4
        p, batch = c["p"], c["batch"]
        steps = max(1, c["steps"] // n_shards)
        source, _, _ = self.spiked_source(p, batch, c["comps"])
        plan = Plan(backend="sharded", gamma=c["gamma"], batch_size=batch,
                    n_shards=n_shards, cov_path="lowrank", rank=c["rank"],
                    impl=self.impl)
        ph.line(f"shapes: p={p} gamma={c['gamma']} batch=({batch}, {p}) per shard, "
                f"{n_shards} shards x {steps} steps, rank={c['rank']}")

        # observe each step's sketch as it enters the sharded reduction
        seen = []
        reduce_step = sharded_mod.sharded_lowrank

        def spy(s, *a, **kw):
            seen.append(len(s.values.sharding.device_set))
            return reduce_step(s, *a, **kw)

        before = self.dispatch_counts()
        sharded_mod.sharded_lowrank = spy
        try:
            _, t_compile = self.timed(lambda: SparsifiedPCA(c["comps"], plan, key=3)
                                      .fit_stream(source, steps=1).explained_variance_)
            got, t_run = self.timed(lambda: SparsifiedPCA(c["comps"], plan, key=3)
                                    .fit_stream(source, steps=steps))
        finally:
            sharded_mod.sharded_lowrank = reduce_step
        ph.require(f"every step's sketch spans {n_shards} devices (saw {seen})",
                   bool(seen) and all(n == n_shards for n in seen))
        self.check_dispatch(ph, before, ("hd_precondition", "sketch_fused", "spmm", "spmm_t"))
        single = plan.replace(backend="stream")
        _, t_compile1 = self.timed(lambda: SparsifiedPCA(c["comps"], single, key=3)
                                   .fit_stream(source, steps=1).explained_variance_)
        one, t_run1 = self.timed(lambda: SparsifiedPCA(c["comps"], single, key=3)
                                 .fit_stream(source, steps=steps))
        ph.line(f"sharded: compile_s={t_compile:.3f} run_s={t_run:.3f}; one device: "
                f"compile_s={t_compile1:.3f} run_s={t_run1:.3f} (bring-up only)")
        a, b = got._reducer.state, one._reducer.state
        for f in ("y", "diag", "sum_w"):
            ph.check(f"range state {f} sharded vs one device",
                     self.rel(getattr(a, f), getattr(b, f)), SAME_PATH_TOL)
        # The fits' eigenmodels keep r = l/2 basis directions, a cut inside
        # the near-degenerate noise bulk of the sketch's singular values, and
        # that cut turns the states' float-sum-order gap into a far larger
        # change of the top-k subspace. The eigenpairs are compared where
        # the spikes stand clear of the bulk: finalized at rank k.
        spec, k = got.spec_, c["comps"]
        om = lowrank.omega(spec.key, spec.p_pad, c["rank"])
        (va, ea), (vb, eb) = (lowrank.range_finalize(s, spec.m, om, rank=k).top(k)
                              for s in (a, b))
        ph.check("rank-k eigenvalues sharded vs one device", self.rel(ea, eb),
                 SAME_PATH_TOL)
        ph.check("rank-k subspace sharded vs one device", self.subspace(va, vb),
                 SAME_PATH_TOL)
        yp = (np.asarray(b.y, np.float64) - (spec.p_pad - spec.m) / (spec.p_pad - 1)
              * np.asarray(b.diag, np.float64)[:, None] * np.asarray(om, np.float64))
        sv = np.linalg.svd(yp, compute_uv=False)
        r = c["rank"] // 2
        ph.line(f"fit outputs (rank l/2 = {r}): eigenvalues "
                f"{self.rel(got.explained_variance_, one.explained_variance_):.3e}, "
                f"top-k subspace {self.subspace(got.components_, one.components_):.3e} "
                f"apart; singular values at the cut {sv[r - 1]:.6g}, {sv[r]:.6g} "
                f"(gap {1 - sv[r] / sv[r - 1]:.2e})")
        if self.path == "kernel":   # host devices report no memory stats
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.devices()[:n_shards]]
            ph.require(f"every device reports nonzero peak_bytes_in_use ({peaks})",
                       all(b > 0 for b in peaks))


if __name__ == "__main__":
    sys.exit(main())
