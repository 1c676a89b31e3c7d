"""Low-rank spectral subsystem (repro.lowrank): spmm kernels vs oracles, the
range-finder's linear delta algebra, FD's deterministic guarantee, lowrank ≡
dense PCA subspace across batch/stream/sharded (ragged trailing step
included), engine + psum plumbing, O(l·p) memory, and the streaming K-means
satellites (reassignment-count convergence signal, decay/forgetting drift)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import lowrank as lr
from repro.api import Plan, SparsifiedCov, SparsifiedKMeans, SparsifiedPCA, fit_many, make_engine
from repro.core import estimators, sketch
from repro.core.sampling import SparseRows, sample_indices
from repro.kernels import ref, spmm as spmm_mod
from repro.stream import StreamEngine, StreamKMeansConfig, accumulators as acc
from repro.stream import sharded as sharded_mod
from tests.conftest import make_clusters, max_angle_sin, spiked as _spiked

KEY = jax.random.PRNGKey(0)
BACKENDS = ("batch", "stream", "sharded")


def spiked(n, p, k, **kw):
    return _spiked(KEY, n, p, k, **kw)


# ------------------------------------------------------- spmm kernels -------


@pytest.mark.parametrize("n,m,p,ell", [(16, 8, 64, 8), (8, 5, 32, 16), (33, 7, 128, 24)])
def test_spmm_kernels_match_oracle(n, m, p, ell):
    """Pallas spmm/spmm_t (interpret mode on CPU) == the jnp oracles; n=33
    exercises the ragged row-block padding (pad rows must contribute nothing)."""
    key = jax.random.fold_in(KEY, n * p)
    values = jax.random.normal(key, (n, m))
    indices = sample_indices(jax.random.fold_in(key, 1), n, p, m)
    dense = jax.random.normal(jax.random.fold_in(key, 2), (p, ell))

    t_ref = ref.ref_spmm(values, indices, dense)
    t_k = spmm_mod.spmm(values, indices, dense, interpret=True)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_ref), atol=1e-5)

    y_ref = ref.ref_spmm_t(values, indices, t_ref, p)
    y_k = spmm_mod.spmm_t(values, indices, t_ref, p, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-4)


# --------------------------------------------------- accumulator algebra ----


def test_range_state_delta_algebra_is_linear():
    """Folding per-batch deltas == one delta of the concatenation — the
    property the per-step psum (and streaming == batch) rests on."""
    p, m, ell = 64, 16, 8
    spec = sketch.make_spec(p, jax.random.PRNGKey(1), m=m)
    om = lr.omega(spec.key, spec.p_pad, ell)
    x = jax.random.normal(KEY, (120, p))
    parts = [sketch.sketch(x[i * 40:(i + 1) * 40], spec,
                           batch_key=sketch.batch_key(spec, i, 0)) for i in range(3)]
    st = lr.range_init(spec.p_pad, ell)
    for s in parts:
        st = lr.range_update(st, s, om)
    s_all = SparseRows(jnp.concatenate([s.values for s in parts]),
                       jnp.concatenate([s.indices for s in parts]), spec.p_pad)
    one = lr.range_delta(s_all, om)
    np.testing.assert_allclose(np.asarray(st.y), np.asarray(one.y), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st.diag), np.asarray(one.diag), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st.sum_w), np.asarray(one.sum_w),
                               rtol=1e-5, atol=1e-4)
    assert int(st.count) == int(one.count) == 120
    # mean finalize matches the Thm-4 estimator exactly
    np.testing.assert_allclose(np.asarray(lr.range_finalize_mean(st, m)),
                               np.asarray(estimators.mean_estimator(s_all)),
                               rtol=1e-5, atol=1e-5)


def test_fd_deterministic_guarantee():
    """Liberty's FD bound: 0 ≼ S − BᵀB ≼ (‖A‖_F²/(l−k))·I for every k < l."""
    p, m, ell = 32, 16, 12
    spec = sketch.make_spec(p, jax.random.PRNGKey(2), m=m)
    x = spiked(300, p, 3, noise=0.05)
    st = lr.fd_init(spec.p_pad, ell)
    parts = []
    for i in range(6):
        s = sketch.sketch(x[i * 50:(i + 1) * 50], spec,
                          batch_key=sketch.batch_key(spec, i, 0))
        parts.append(s)
        st = lr.fd_update(st, s)
    s_all = SparseRows(jnp.concatenate([s.values for s in parts]),
                       jnp.concatenate([s.indices for s in parts]), spec.p_pad)
    w = np.asarray(s_all.to_dense(), np.float64)
    s_mat = w.T @ w
    b = np.asarray(st.sketch, np.float64)
    gap = np.linalg.eigvalsh(s_mat - b.T @ b)
    fro2 = float(np.sum(w**2))
    assert gap.min() > -1e-2 * fro2 / ell          # PSD up to float error
    assert gap.max() <= fro2 / (ell - 3) + 1e-3 * fro2


# ----------------------------------- lowrank ≡ dense across the backends ----


@pytest.mark.parametrize("method", ("range", "fd"))
def test_lowrank_pca_subspace_all_backends(method):
    """cov_path="lowrank" recovers the dense-path top-k subspace on every
    backend; n=2150 with batch_size=200 leaves a ragged 150-row trailing step.
    Backends must agree on the lowrank result bit-for-bit (same linear folds,
    FD folds in the same sequential order everywhere)."""
    p, k, n, ell = 64, 4, 2150, 32
    x = spiked(n, p, k)
    dense = SparsifiedPCA(k, Plan(gamma=0.5, batch_size=200), key=3).fit(x)
    fits = {}
    for backend in BACKENDS:
        plan = Plan(backend=backend, gamma=0.5, batch_size=200, cov_path="lowrank",
                    rank=ell, lowrank_method=method)
        est = SparsifiedPCA(k, plan, key=3).fit(x)
        fits[backend] = est
        assert est.count_ == n
        assert est.cov_lowrank_ is not None
        assert est.components_.shape == (k, p)
        # small-scale bound; the tight 1e-3 acceptance bar runs in the slow
        # lane (test_lowrank_pca_acceptance_principal_angles) at its n
        assert max_angle_sin(est.components_, dense.components_) < 5e-2
        # eigenvalues track the dense spectrum (FD's shrink biases them low by
        # up to the accumulated δ — Liberty's bound — so it gets more slack)
        np.testing.assert_allclose(np.asarray(est.explained_variance_),
                                   np.asarray(dense.explained_variance_),
                                   rtol=0.1 if method == "range" else 0.3)
    for backend in ("stream", "sharded"):
        np.testing.assert_array_equal(np.asarray(fits[backend].components_),
                                      np.asarray(fits["batch"].components_))


@pytest.mark.slow
def test_lowrank_pca_acceptance_principal_angles():
    """The acceptance bar: Plan(cov_path="lowrank", rank=l ≥ 4k) recovers the
    dense-path top-k subspace to principal angles ≤ 1e-3 on the synthetic
    spiked model, on batch, stream, and sharded — with a ragged trailing
    step (80000 = 19.5 × 4096) and an O(l·p) accumulator throughout."""
    p, k, n, ell = 128, 4, 80000, 96
    x = spiked(n, p, k, noise=1e-3)
    plan0 = Plan(gamma=0.8, batch_size=4096)
    dense = SparsifiedPCA(k, plan0, key=3).fit(x)
    for backend in BACKENDS:
        plan = plan0.replace(backend=backend, cov_path="lowrank", rank=ell)
        est = SparsifiedPCA(k, plan, key=3).fit(x)
        sin = max_angle_sin(est.components_, dense.components_)
        assert sin <= 1e-3, (backend, sin)
        # the accumulator really is O(l·p): no leaf anywhere near (p, p)
        leaves = jax.tree.leaves(est._reducer.state)
        assert max(leaf.size for leaf in leaves) <= est.spec_.p_pad * ell


def test_lowrank_never_materializes_pp():
    """No (p, p) array exists anywhere in the lowrank reducer state."""
    p, ell = 256, 16
    x = spiked(1024, p, 4)
    est = SparsifiedPCA(4, Plan(backend="stream", gamma=0.25, batch_size=256,
                                cov_path="lowrank", rank=ell), key=1).fit(x)
    leaves = jax.tree.leaves(est._reducer.state)
    assert max(leaf.size for leaf in leaves) == p * ell  # y is the largest
    assert all(leaf.shape != (p, p) for leaf in leaves)
    assert est._reducer.retained is None                 # nothing retained
    assert est._reducer.state.nbytes() < 4 * p * p       # ≪ the (p,p) f32 acc
    assert est.cov_lowrank_.nbytes() <= (ell // 2 + 1) * p * 4 + ell * 4


# ---------------------------------------------------- engine + psum path ----


def test_engine_lowrank_matches_estimator_and_scan():
    """StreamEngine(cov_path="lowrank") == SparsifiedPCA.fit_stream over the
    identical (seed, step, shard) chunks, and run_scanned == run."""
    p, k, ell, b, steps = 64, 3, 16, 50, 8
    data = jax.random.normal(KEY, (steps, 1, b, p)) + 2.0

    def source(seed, step, shard):
        return np.asarray(data[step, shard])

    plan = Plan(backend="stream", gamma=0.5, batch_size=b, cov_path="lowrank", rank=ell)
    est = SparsifiedPCA(k, plan, key=9).fit_stream(source, steps=steps)

    eng = make_engine(plan, p, 9, source)
    res = eng.run(steps)
    assert res.cov is None and res.cov_lowrank is not None
    np.testing.assert_allclose(
        np.asarray(sketch.unmix_dense(res.mean[None], eng.spec)[0]),
        np.asarray(est.mean_), atol=1e-4)
    comps_pre, evals = res.cov_lowrank.top(k)
    comps = sketch.unmix_dense(comps_pre, eng.spec)
    # engine fuses sketch+delta+apply in ONE jit, the estimator in three —
    # float reordering through an eigensolve, so tight-but-not-bitwise
    assert max_angle_sin(comps, est.components_) < 1e-3
    np.testing.assert_allclose(np.asarray(evals),
                               np.asarray(est.explained_variance_), rtol=1e-4)

    res_scan = eng.run_scanned(np.asarray(data))
    np.testing.assert_allclose(np.asarray(res_scan.cov_lowrank.eigenvalues),
                               np.asarray(res.cov_lowrank.eigenvalues), rtol=1e-5)


def test_sharded_lowrank_psum_equals_local_delta():
    """sharded_lowrank (1-device mesh here; 8-device in the slow test) == the
    plain local delta, including the zero-pad ragged-rows path."""
    p, m, ell = 64, 16, 8
    spec = sketch.make_spec(p, jax.random.PRNGKey(4), m=m)
    om = lr.omega(spec.key, spec.p_pad, ell)
    s = sketch.sketch(jax.random.normal(KEY, (37, p)), spec)  # 37: pad path
    mesh = jax.make_mesh((1,), ("data",))
    st = sharded_mod.sharded_lowrank(s, om, mesh, ("data",))
    ref_delta = lr.range_delta(s, om)
    np.testing.assert_allclose(np.asarray(st.y), np.asarray(ref_delta.y), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(st.diag), np.asarray(ref_delta.diag), rtol=1e-5)
    assert int(st.count) == 37


@pytest.mark.slow
def test_sharded_lowrank_8dev_matches_single_device():
    """The fixed (p, l) delta psums across a REAL 8-device mesh to the
    single-device stream result (subprocess keeps this session on one device)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import sketch
        from repro.stream import StreamEngine

        mesh = jax.make_mesh((8,), ("data",))
        p, b, steps, ell = 128, 16, 5, 24
        spec = sketch.make_spec(p, jax.random.PRNGKey(1), gamma=0.25)
        data = jax.random.normal(jax.random.PRNGKey(0), (steps, 8, b, p))

        def source(seed, step, shard):
            return np.asarray(data[step, shard])

        cfg = dict(n_shards=8, cov_path="lowrank", rank=ell)
        eng1 = StreamEngine(spec, source, **cfg)
        eng8 = StreamEngine(spec, source, mesh=mesh, **cfg)
        res1, res8 = eng1.run(steps), eng8.run(steps)
        np.testing.assert_allclose(np.asarray(res8.mean), np.asarray(res1.mean), atol=1e-5)
        # the psum'd accumulator equals the sequential fold up to float
        # reordering (eigenVECTORS of this unstructured stream are nearly
        # degenerate, so the state — not the finalized basis — is the check)
        st1, st8 = eng1.state.lowrank, eng8.state.lowrank
        scale = float(jnp.abs(st1.y).max())
        np.testing.assert_allclose(np.asarray(st8.y), np.asarray(st1.y),
                                   atol=1e-5 * scale)
        np.testing.assert_allclose(np.asarray(st8.diag), np.asarray(st1.diag),
                                   rtol=1e-5)
        assert int(st8.count) == int(st1.count) == steps * 8 * b
        np.testing.assert_allclose(np.asarray(res8.cov_lowrank.eigenvalues),
                                   np.asarray(res1.cov_lowrank.eigenvalues), rtol=1e-4)
        print("sharded-lowrank-8dev OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


# ------------------------------------------------------ fit_many fan-out ----


def test_fit_many_mixes_lowrank_and_dense_consumers(monkeypatch):
    """One shared sketch pass can feed a lowrank PCA and a dense Cov at once —
    cov_path/rank are fold choices, not sketch geometry."""
    calls = {"n": 0}
    real = sketch.sketch

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(sketch, "sketch", counting)
    x = spiked(600, 64, 4)
    plan = Plan(gamma=0.5, batch_size=200)
    pca_lr = SparsifiedPCA(4, plan.replace(cov_path="lowrank", rank=16), key=7)
    cov_d = SparsifiedCov(plan, key=7)
    run = fit_many(plan, [pca_lr, cov_d], x)
    assert calls["n"] == 3 == run.n_sketches
    sep = SparsifiedPCA(4, plan.replace(cov_path="lowrank", rank=16), key=7).fit(x)
    np.testing.assert_array_equal(np.asarray(pca_lr.components_),
                                  np.asarray(sep.components_))
    assert cov_d.cov_.shape == (64, 64)


# ------------------------------------------------------------ validation ----


def test_plan_lowrank_validation():
    with pytest.raises(ValueError, match="rank"):
        Plan(gamma=0.1, cov_path="lowrank")                 # rank required
    with pytest.raises(ValueError, match="rank"):
        Plan(gamma=0.1, rank=8)                             # rank needs lowrank
    with pytest.raises(ValueError, match="lowrank_method"):
        Plan(gamma=0.1, cov_path="lowrank", rank=8, lowrank_method="nyst")
    with pytest.raises(ValueError, match="cov_path"):
        Plan(gamma=0.1, cov_path="sparse")
    with pytest.raises(ValueError, match="PCA-only"):
        SparsifiedCov(Plan(gamma=0.5, cov_path="lowrank", rank=8), key=0).fit(
            jnp.ones((8, 16)))
    with pytest.raises(ValueError, match="exceeds"):       # rank > p_pad
        SparsifiedPCA(2, Plan(gamma=0.5, cov_path="lowrank", rank=64),
                      key=0).fit(jnp.ones((8, 16)))
    with pytest.raises(ValueError, match="n_components"):  # k > model rank
        SparsifiedPCA(5, Plan(gamma=0.5, cov_path="lowrank", rank=8),
                      key=0).fit(jnp.ones((8, 16)))
    with pytest.raises(ValueError, match="estimator-layer"):
        make_engine(Plan(backend="stream", gamma=0.5, cov_path="lowrank", rank=8,
                         lowrank_method="fd"), 16, 0, lambda s, t, sh: None)
    with pytest.raises(ValueError, match="rank"):
        StreamEngine(sketch.make_spec(16, KEY, gamma=0.5), lambda s, t, sh: None,
                     cov_path="lowrank")                   # engine needs rank too


# ------------------------------- streaming K-means satellites ----------------


def test_minibatch_reassignment_counts_converge():
    """Overlapping clusters keep flipping assignments early; the per-step
    reassignment counts decay as the online means settle — the convergence
    signal of the ROADMAP streaming-K-means item."""
    x, _, _ = make_clusters(KEY, n=3000, p=16, k=4, sep=1.0, noise=1.2)
    plan = Plan(backend="stream", gamma=0.5, batch_size=100)
    est = SparsifiedKMeans(4, plan, key=5, algorithm="minibatch").fit(x)
    h = est.reassign_counts_
    assert h is not None and len(h) == 30 and h.dtype.kind == "i"
    assert h[:15].sum() > 4 * h[15:].sum()      # early churn, late quiet
    assert est.reassign_fraction_.shape == (30,)
    assert float(est.reassign_fraction_[-1]) <= 0.05
    # lloyd never tracks (it is not a streaming fold)
    ll = SparsifiedKMeans(4, plan, key=5).fit(x)
    assert ll.reassign_counts_ is None
    # and tracking can be turned off
    off = SparsifiedKMeans(4, plan, key=5, algorithm="minibatch",
                           track_reassignments=False).fit(x)
    assert off.reassign_counts_ is None
    np.testing.assert_array_equal(np.asarray(off.centers_), np.asarray(est.centers_))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kmeans_decay_tracks_drifting_stream(seed):
    """The forgetting factor: when the clusters jump halfway through the
    stream, decayed counts let the centers follow; undecayed counts anchor
    them to stale history. Reassignment counts spike exactly at the drift.

    Each cluster jumps to the midpoint between its old center and the next
    one, so every old center keeps winning rows after the jump. (A jump that
    leaves some old center winning no rows strands it: the minibatch fold
    only moves centers that are assigned rows, decay or not. The earlier
    ``c2 = -c1`` jump did that for half of all seeds.)"""
    from scipy.optimize import linear_sum_assignment

    k, p = 3, 32
    key = jax.random.fold_in(KEY, 100 + seed)
    c1 = jax.random.normal(jax.random.fold_in(key, 1), (k, p)) * 3.0
    c2 = 0.5 * (c1 + jnp.roll(c1, -1, axis=0))

    def phase(centers, sub):
        lab = jax.random.randint(jax.random.fold_in(key, 10 + sub), (2000,), 0, k)
        return centers[lab] + 0.3 * jax.random.normal(
            jax.random.fold_in(key, 20 + sub), (2000, p))

    x = jnp.concatenate([phase(c1, 0), phase(c2, 1)])
    plan = Plan(backend="stream", gamma=0.5, batch_size=100)

    def dist_to(est, target):
        d = np.linalg.norm(np.asarray(est.centers_)[:, None]
                           - np.asarray(target)[None], axis=-1)
        ri, ci = linear_sum_assignment(d)
        return float(d[ri, ci].mean())

    plain = SparsifiedKMeans(k, plan, key=5, algorithm="minibatch").fit(x)
    dec = SparsifiedKMeans(k, plan, key=5, algorithm="minibatch", decay=0.5).fit(x)
    assert dist_to(dec, c2) < 1.0 < dist_to(plain, c2)
    assert dec._km_state.counts.dtype == jnp.float32     # decay ⇒ float counts
    assert plain._km_state.counts.dtype == jnp.int32     # default stays exact
    # the drift announces itself in the convergence signal: the spike at the
    # phase boundary (step 20) dwarfs the settled tail before it
    h = dec.reassign_counts_
    assert h[20:26].sum() > 10 * max(1, h[14:20].sum())


def test_kmeans_decay_validation_and_engine_plumbing():
    with pytest.raises(ValueError, match="decay"):
        SparsifiedKMeans(3, Plan(gamma=0.5), decay=1.5)
    with pytest.raises(ValueError, match="decay"):
        SparsifiedKMeans(3, Plan(gamma=0.5), decay=0.9)   # lloyd can't forget
    with pytest.raises(ValueError, match="decay"):
        StreamKMeansConfig(k=3, decay=0.0)

    # engine accepts the decay config and the run stays finite
    p, b = 32, 40
    x = jax.random.normal(KEY, (5, 1, b, p))

    def source(seed, step, shard):
        return np.asarray(x[step, shard])

    spec = sketch.make_spec(p, jax.random.PRNGKey(3), gamma=0.5)
    eng = StreamEngine(spec, source, track_cov=False,
                       kmeans=StreamKMeansConfig(k=3, n_init=2, decay=0.8))
    res = eng.run(5)
    assert np.isfinite(np.asarray(res.centers)).all()
    assert eng.state.kmeans.counts.dtype == jnp.float32
