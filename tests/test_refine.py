"""Second-pass refinement subsystem (repro.refine): PCA power iteration and
two-pass (Alg. 2) K-means over the regenerable (seed, step, shard) source —
per-pass subspace convergence vs the dense path, bit-identical refined centers
across batch/stream/sharded, engine replay()/replay_scanned() parity, the
shared fit_many(refine=) replay, and the validation surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import refine as rf
from repro.api import Plan, SparsifiedKMeans, SparsifiedMean, SparsifiedPCA, fit_many, make_engine
from repro.core import sketch
from repro.stream import StreamEngine, StreamKMeansConfig
from repro.stream import accumulators as acc
from tests.conftest import make_clusters, max_angle_sin, spiked as _spiked

KEY = jax.random.PRNGKey(0)
BACKENDS = ("batch", "stream", "sharded")


def spiked(n, p, k, **kw):
    return _spiked(KEY, n, p, k, **kw)


# ------------------------------------------------------------ PCA algebra ---


def test_power_pass_squares_the_subspace_gap():
    """One fit_refine pass shrinks dense-vs-lowrank principal angles by ≥ 10×
    at a deliberately narrow rank (where the one-pass gap is visible), and
    more passes keep shrinking until the f32 core-solve floor."""
    p, k, n, ell = 64, 4, 4000, 12
    x = spiked(n, p, k)
    dense = SparsifiedPCA(k, Plan(gamma=0.5, batch_size=500), key=3).fit(x)
    plan = Plan(backend="stream", gamma=0.5, batch_size=500,
                cov_path="lowrank", rank=ell)
    a_one = max_angle_sin(SparsifiedPCA(k, plan, key=3).fit(x).components_,
                          dense.components_)
    ref = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=1)
    a_ref = max_angle_sin(ref.components_, dense.components_)
    assert a_one > 1e-2              # the gap is real at rank=3k
    assert a_ref * 10 < a_one, (a_one, a_ref)
    assert ref.refine_passes_ == 1
    assert ref.count_ == n           # the first-pass fit is intact
    # the per-pass diagnostic tracks convergence: strictly shrinking changes
    ref3 = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=3)
    ch = ref3.refine_subspace_change_
    assert ch.shape == (3,) and ch[0] > 10 * ch[1] > 0


def test_refined_pca_bit_identical_across_backends():
    """Replay folds the same linear deltas in the same per-step order on every
    backend, so the REFINED components agree bit-for-bit (as the one-pass
    lowrank components already do)."""
    p, k, n, ell = 64, 3, 1100, 16  # 1100/200 → ragged trailing chunk
    x = spiked(n, p, k)
    fits = {}
    for backend in BACKENDS:
        plan = Plan(backend=backend, gamma=0.5, batch_size=200,
                    cov_path="lowrank", rank=ell)
        fits[backend] = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=2)
    for backend in ("stream", "sharded"):
        np.testing.assert_array_equal(
            np.asarray(fits[backend].components_),
            np.asarray(fits["batch"].components_))
        np.testing.assert_array_equal(
            np.asarray(fits[backend].refine_subspace_change_),
            np.asarray(fits["batch"].refine_subspace_change_))


def test_fit_refine_from_stream_source():
    """fit_refine(source=...) = fit_stream + replay of the SAME source; the
    refined subspace beats the one-pass fit against the stream's dense PCA."""
    p, k, ell, b, steps = 64, 3, 12, 100, 10
    data = spiked(steps * b, p, k).reshape(steps, 1, b, p)

    def source(seed, step, shard):
        return np.asarray(data[step, shard])

    plan = Plan(backend="stream", gamma=0.5, batch_size=b,
                cov_path="lowrank", rank=ell)
    dense = SparsifiedPCA(k, Plan(gamma=0.5, batch_size=b), key=9).fit(
        data.reshape(-1, p))
    one = SparsifiedPCA(k, plan, key=9).fit_stream(source, steps=steps)
    ref = SparsifiedPCA(k, plan, key=9).fit_refine(source=source, steps=steps,
                                                   passes=2)
    assert (max_angle_sin(ref.components_, dense.components_)
            < max_angle_sin(one.components_, dense.components_) / 5)


# --------------------------------------------------------- two-pass kmeans --


def test_two_pass_kmeans_bit_identical_and_tracked():
    """Refined centers are BIT-IDENTICAL across backends (frozen-center deltas
    commute); reassignment counts continue the convergence signal: one entry
    per rebuild (the trailing measurement replay prices the last one), decaying
    as the rebuilds reach a Lloyd fixed point of the sketch."""
    x, _, _ = make_clusters(KEY, n=2100, p=16, k=4, sep=2.0, noise=0.8)
    fits = {}
    for backend in BACKENDS:
        plan = Plan(backend=backend, gamma=0.5, batch_size=100)
        fits[backend] = SparsifiedKMeans(4, plan, key=5,
                                         algorithm="minibatch").fit_refine(x, passes=3)
    for backend in ("stream", "sharded"):
        np.testing.assert_array_equal(np.asarray(fits[backend].centers_),
                                      np.asarray(fits["batch"].centers_))
    est = fits["stream"]
    assert est.refine_passes_ == 3
    assert est.refine_reassign_counts_.shape == (3,)
    assert est.refine_reassign_counts_[0] >= est.refine_reassign_counts_[-1]
    assert np.all(est.refine_reassign_fraction_ <= 1.0)
    # without tracking there is no trailing measurement replay: counts cover
    # only the first passes-1 rebuilds
    off = SparsifiedKMeans(4, Plan(backend="stream", gamma=0.5, batch_size=100),
                           key=5, algorithm="minibatch",
                           track_reassignments=False).fit_refine(x, passes=3)
    assert off.refine_reassign_counts_.shape == (2,)
    np.testing.assert_array_equal(np.asarray(off.centers_),
                                  np.asarray(est.centers_))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_two_pass_kmeans_beats_streaming_centers(seed):
    """The refinement target: consistent-assignment rebuilds move the centers
    closer to the true cluster means than the one-pass streaming fold, whose
    centers inherit assignment noise from the evolving first pass.

    The clusters overlap (sep 0.5 at unit noise), so the first pass does
    misassign rows. Well-separated clusters (sep 3) have no assignment noise
    to remove: there both fits land equally close to the truth, and which
    one wins is a coin toss over the draws."""
    from scipy.optimize import linear_sum_assignment

    x, _, centers = make_clusters(jax.random.fold_in(KEY, seed), n=4000, p=32,
                                  k=5, sep=0.5, noise=1.0)
    plan = Plan(backend="stream", gamma=0.5, batch_size=100)

    def dist_to_truth(est):
        d = np.linalg.norm(np.asarray(est.centers_)[:, None]
                           - np.asarray(centers)[None], axis=-1)
        ri, ci = linear_sum_assignment(d)
        return float(d[ri, ci].mean())

    one = SparsifiedKMeans(5, plan, key=5, algorithm="minibatch").fit(x)
    ref = SparsifiedKMeans(5, plan, key=5, algorithm="minibatch").fit_refine(x, passes=2)
    assert dist_to_truth(ref) < dist_to_truth(one)


# ------------------------------------------------------------ shared replay --


def test_fit_many_refine_shares_the_replay_sketches(monkeypatch):
    """fit_many(refine=) replays each (step, shard) sketch ONCE per pass and
    fans it out to both refiners; results equal the separate fit_refine calls.
    Non-refinable consumers (Mean) ride the forward pass untouched."""
    calls = {"n": 0}
    real = sketch.sketch

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(sketch, "sketch", counting)
    x = spiked(1000, 64, 4)          # 5 chunks of 200
    base = Plan(backend="stream", gamma=0.5, batch_size=200)
    plan_lr = base.replace(cov_path="lowrank", rank=12)
    pca = SparsifiedPCA(4, plan_lr, key=7)
    km = SparsifiedKMeans(3, base, key=7, algorithm="minibatch")
    mean = SparsifiedMean(base, key=7)
    fit_many(base, [pca, km, mean], x, refine=2)
    # 5 forward + 2 passes × 5 + 1 trailing measurement replay × 5 = 20
    assert calls["n"] == 20
    assert pca.refine_passes_ == 2 and km.refine_passes_ == 2
    assert not hasattr(mean, "refine_passes_") or mean.refine_passes_ == 0

    sep_pca = SparsifiedPCA(4, plan_lr, key=7).fit_refine(x, passes=2)
    np.testing.assert_array_equal(np.asarray(pca.components_),
                                  np.asarray(sep_pca.components_))
    sep_km = SparsifiedKMeans(3, base, key=7,
                              algorithm="minibatch").fit_refine(x, passes=2)
    np.testing.assert_array_equal(np.asarray(km.centers_),
                                  np.asarray(sep_km.centers_))
    np.testing.assert_array_equal(np.asarray(km.refine_reassign_counts_),
                                  np.asarray(sep_km.refine_reassign_counts_))


# ---------------------------------------------------------- engine replay ---


def test_engine_replay_matches_estimator_and_scan():
    """StreamEngine.replay() == the estimator-layer refine over the identical
    (seed, step, shard) chunks (engine fuses sketch+delta in one jit —
    tolerance, not bitwise), and replay_scanned == replay."""
    p, k, ell, b, steps = 64, 3, 12, 100, 8
    data = spiked(steps * b, p, k).reshape(steps, 1, b, p)

    def source(seed, step, shard):
        return np.asarray(data[step, shard])

    plan = Plan(backend="stream", gamma=0.5, batch_size=b,
                cov_path="lowrank", rank=ell)
    est = SparsifiedPCA(k, plan, key=9).fit_refine(source=source, steps=steps,
                                                   passes=2)
    eng = make_engine(plan, p, 9, source)
    eng.run(steps)
    res = eng.replay(steps, passes=2)
    assert res.refine_passes == 2 and res.cov is None
    comps = sketch.unmix_dense(res.cov_lowrank.top(k)[0], eng.spec)
    assert max_angle_sin(comps, est.components_) < 1e-3
    np.testing.assert_allclose(np.asarray(res.cov_lowrank.eigenvalues[:k]),
                               np.asarray(est.explained_variance_), rtol=1e-3)
    res_scan = eng.replay_scanned(np.asarray(data), passes=2)
    np.testing.assert_allclose(np.asarray(res_scan.cov_lowrank.eigenvalues),
                               np.asarray(res.cov_lowrank.eigenvalues), rtol=1e-5)
    # the replay re-accumulates the same Thm-4 sums: mean/count preserved
    res0 = eng.finalize()
    np.testing.assert_allclose(np.asarray(res.mean), np.asarray(res0.mean),
                               atol=1e-5)
    assert int(res.count) == int(res0.count) == steps * b


def test_engine_replay_kmeans_two_pass():
    """Engine K-means replay: frozen-assignment rebuilds with the in-pass flip
    counts (rebuilds 1..q-1; the trailing measurement is estimator-layer).
    One pass must equal a hand-rolled kmeans2 fold over the same sketches."""
    p, b, steps = 32, 100, 6
    x, _, _ = make_clusters(KEY, n=steps * b, p=p, k=3, sep=3.0, noise=0.8)
    data = np.asarray(x).reshape(steps, 1, b, p)

    def source(seed, step, shard):
        return np.asarray(data[step, shard])

    spec = sketch.make_spec(p, jax.random.PRNGKey(3), gamma=0.5)
    eng = StreamEngine(spec, source, track_cov=False,
                       kmeans=StreamKMeansConfig(k=3, n_init=2))
    res0 = eng.run(steps)
    res = eng.replay(steps, passes=3)
    assert res.refine_passes == 3
    assert len(res.refine_reassigned) == 2          # rebuilds 1 and 2
    assert res.refine_reassigned[0] >= res.refine_reassigned[-1]
    assert res.centers.shape == res0.centers.shape
    assert np.isfinite(np.asarray(res.centers)).all()
    # hand-rolled pass 1: same frozen centers, same regenerated sketches
    frozen, _ = acc.kmeans_finalize(eng.state.kmeans)
    st = rf.kmeans2_init(3, spec.p_pad)
    for step in range(steps):
        s = sketch.sketch(jnp.asarray(data[step, 0]), spec,
                          batch_key=sketch.batch_key(spec, step, 0))
        st = rf.kmeans2_apply(st, rf.kmeans2_delta(s, frozen))
    manual = rf.kmeans2_centers(st, frozen)
    res1 = eng.replay(steps, passes=1)
    np.testing.assert_allclose(np.asarray(res1.centers_pre), np.asarray(manual),
                               atol=1e-5)
    np.testing.assert_allclose(float(res1.kmeans_obj), float(st.obj), rtol=1e-5)


def test_engine_replay_sharded_psum_matches_stream():
    """Under a mesh the replay psums one fixed-size delta per step; a 1-device
    mesh must reproduce the meshless replay exactly."""
    p, k, ell, b, steps = 64, 3, 12, 50, 5
    data = spiked(steps * b, p, k).reshape(steps, 1, b, p)

    def source(seed, step, shard):
        return np.asarray(data[step, shard])

    plan = Plan(backend="stream", gamma=0.5, batch_size=b,
                cov_path="lowrank", rank=ell)
    eng1 = make_engine(plan, p, 9, source)
    eng1.run(steps)
    res1 = eng1.replay(steps, passes=2)
    plan8 = plan.replace(backend="sharded", n_shards=1)
    eng8 = make_engine(plan8, p, 9, source)
    eng8.run(steps)
    res8 = eng8.replay(steps, passes=2)
    np.testing.assert_allclose(np.asarray(res8.cov_lowrank.eigenvalues),
                               np.asarray(res1.cov_lowrank.eigenvalues),
                               rtol=1e-5)


def test_repeat_refine_resumes_not_restarts():
    """refine() twice ≡ refine(passes=2) — a repeat call continues the
    iteration from the refined state (bit-identically) instead of silently
    re-deriving pass 1 from the one-pass fit; refine_passes_ accumulates."""
    p, k, ell = 64, 3, 12
    x = spiked(1000, p, k)
    plan = Plan(backend="stream", gamma=0.5, batch_size=200,
                cov_path="lowrank", rank=ell)
    two = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=2)
    inc = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=1)
    inc.refine(x, passes=1)
    assert inc.refine_passes_ == 2
    np.testing.assert_array_equal(np.asarray(inc.components_),
                                  np.asarray(two.components_))
    np.testing.assert_allclose(inc.refine_subspace_change_,
                               two.refine_subspace_change_)

    base = Plan(backend="stream", gamma=0.5, batch_size=100)
    xc, _, _ = make_clusters(KEY, n=1500, p=16, k=4, sep=2.0, noise=0.9)
    km2 = SparsifiedKMeans(4, base, key=5, algorithm="minibatch").fit_refine(
        xc, passes=2)
    kmi = SparsifiedKMeans(4, base, key=5, algorithm="minibatch").fit_refine(
        xc, passes=1)
    kmi.refine(xc, passes=1)
    assert kmi.refine_passes_ == 2
    np.testing.assert_array_equal(np.asarray(kmi.centers_),
                                  np.asarray(km2.centers_))
    # the flip history continues without double-counting the measured rebuild
    np.testing.assert_array_equal(kmi.refine_reassign_counts_,
                                  km2.refine_reassign_counts_)
    # a re-FIT resets the refinement state: the next refine starts fresh
    kmi.fit(xc)
    assert kmi.refine_passes_ == 0


# -------------------------------------------------------------- validation --


def test_refine_validation_surface():
    x = spiked(400, 32, 2)
    base = Plan(gamma=0.5, batch_size=100)
    with pytest.raises(ValueError, match="refine_passes"):
        Plan(gamma=0.5, refine_passes=-1)
    with pytest.raises(ValueError, match="lowrank"):
        SparsifiedPCA(2, base, key=0).fit_refine(x)          # dense path: exact
    with pytest.raises(ValueError, match="fd"):
        SparsifiedPCA(2, base.replace(cov_path="lowrank", rank=8,
                                      lowrank_method="fd"), key=0).fit_refine(x)
    with pytest.raises(ValueError, match="lloyd"):
        SparsifiedKMeans(2, base, key=0).fit_refine(x)
    with pytest.raises(ValueError, match="forget"):
        # decayed fits deliberately forget; the uniform rebuild would not
        SparsifiedKMeans(2, base.replace(backend="stream"), key=0,
                         algorithm="minibatch", decay=0.9).fit_refine(x)
    with pytest.raises(ValueError, match="no consumer"):
        km_dec = SparsifiedKMeans(2, base.replace(backend="stream"), key=0,
                                  algorithm="minibatch", decay=0.9)
        fit_many(base.replace(backend="stream"), [km_dec], x, refine=True)
    with pytest.raises(ValueError, match="refinement"):
        SparsifiedMean(base, key=0).fit_refine(x)
    plan_lr = base.replace(cov_path="lowrank", rank=8)
    with pytest.raises(RuntimeError, match="fitted"):
        SparsifiedPCA(2, plan_lr, key=0).refine(x)           # not fitted yet
    with pytest.raises(ValueError, match="exactly one"):
        SparsifiedPCA(2, plan_lr, key=0).fit_refine()
    with pytest.raises(ValueError, match="passes"):
        SparsifiedPCA(2, plan_lr, key=0).fit_refine(x, passes=0)
    with pytest.raises(ValueError, match="steps"):
        SparsifiedPCA(2, plan_lr, key=0).fit_refine(source=lambda s, t, sh: x[:100])
    with pytest.raises(ValueError, match="no consumer"):
        fit_many(base, [SparsifiedMean(base, key=0)], x, refine=True)
    with pytest.raises(ValueError, match="FINALIZED"):
        fit_many(base, [SparsifiedPCA(2, plan_lr, key=0)], x, refine=True,
                 finalize=False)
    # plan default: refine_passes drives fit_refine when passes is omitted
    est = SparsifiedPCA(2, plan_lr.replace(refine_passes=2), key=0).fit_refine(x)
    assert est.refine_passes_ == 2
    # engine: replay before run, and replay with nothing to refine
    eng = make_engine(Plan(backend="stream", gamma=0.5, batch_size=100,
                           cov_path="lowrank", rank=8), 32, 0,
                      lambda s, t, sh: x[:100])
    with pytest.raises(RuntimeError, match="run"):
        eng.replay(4)
    eng_plain = make_engine(Plan(backend="stream", gamma=0.5, batch_size=100),
                            32, 0, lambda s, t, sh: np.asarray(x[:100]))
    eng_plain.run(4)
    with pytest.raises(ValueError, match="neither"):
        eng_plain.replay(4)
    # replay data must match the fitted geometry — p AND row count
    fitted = SparsifiedPCA(2, plan_lr, key=0).fit(x)
    with pytest.raises(ValueError, match="rows"):
        fitted.refine(jnp.ones((100, 16)))          # wrong n caught first
    with pytest.raises(ValueError, match="p="):
        fitted.refine(jnp.ones((400, 16)))          # right n, wrong p
    with pytest.raises(ValueError, match="rows"):
        fitted.refine(x[:200])                      # a different-length slice
    # ragged partial_fit histories REPLAY now: the cursor's recorded per-chunk
    # row counts drive the array re-chunking, so the replay folds exactly the
    # original (step, shard) masks (the old code rejected these outright)
    ragged = SparsifiedPCA(2, plan_lr, key=0)
    ragged.partial_fit(x[:130]).partial_fit(x[130:]).finalize()
    assert ragged._cursor.chunk_rows == [100, 30, 100, 100, 70]
    ragged.refine(x)
    assert ragged.refine_passes_ == 1
    # determinism: a twin with the same ragged history refines bit-identically
    twin = SparsifiedPCA(2, plan_lr, key=0)
    twin.partial_fit(x[:130]).partial_fit(x[130:]).finalize()
    twin.refine(x)
    np.testing.assert_array_equal(np.asarray(ragged.components_),
                                  np.asarray(twin.components_))
    # unequal-size calls whose chunks stay batch-aligned ≡ the equal-chunk
    # fit_refine, bitwise (the boundaries — not the call sizes — are the keys)
    aligned = SparsifiedPCA(2, plan_lr, key=0)
    aligned.partial_fit(x[:100]).partial_fit(x[100:]).finalize()
    aligned.refine(x)
    assert aligned.refine_passes_ == 1
    whole = SparsifiedPCA(2, plan_lr, key=0).fit_refine(x, passes=1)
    np.testing.assert_array_equal(np.asarray(aligned.components_),
                                  np.asarray(whole.components_))


# ------------------------------------------------------------ adaptive tol --


def test_refine_tol_converges_and_matches_fixed_passes():
    """refine(tol=) is pure loop control over the resuming single-pass
    machinery: it stops at the first pass whose convergence measurement drops
    to tol, and the result is bit-identical to refine(passes=q) for the q it
    settled on."""
    p, k, ell = 64, 3, 12
    x = spiked(1000, p, k)
    plan = Plan(backend="stream", gamma=0.5, batch_size=200,
                cov_path="lowrank", rank=ell)
    # tol sits above the f32 core-solve floor (~1e-3 subspace-change noise)
    # but far below the one-pass gap (~0.05): the loop must stop right when
    # the power iteration crosses it
    tol = 2e-3
    est = SparsifiedPCA(k, plan, key=3).fit_refine(x, tol=tol)
    assert est.refine_converged_
    q = est.refine_passes_
    assert 1 <= q < 16
    ch = np.asarray(est.refine_subspace_change_)
    assert ch[-1] <= tol and np.all(ch[:-1] > tol)     # stopped at the FIRST hit
    fixed = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=q)
    np.testing.assert_array_equal(np.asarray(est.components_),
                                  np.asarray(fixed.components_))
    # an unreachable tol runs to max_passes and says so
    capped = SparsifiedPCA(k, plan, key=3).fit_refine(x, tol=1e-30, max_passes=2)
    assert not capped.refine_converged_ and capped.refine_passes_ == 2


def test_refine_tol_kmeans_and_validation():
    xc, _, _ = make_clusters(KEY, n=1500, p=16, k=4, sep=2.0, noise=0.9)
    base = Plan(backend="stream", gamma=0.5, batch_size=100)
    km = SparsifiedKMeans(4, base, key=5, algorithm="minibatch").fit_refine(
        xc, tol=0.05)
    assert km.refine_converged_
    assert float(km.refine_reassign_fraction_[-1]) <= 0.05
    # the signal costs a trailing measurement replay per pass — it must exist
    with pytest.raises(ValueError, match="track_reassignments"):
        SparsifiedKMeans(4, base, key=5, algorithm="minibatch",
                         track_reassignments=False).fit_refine(xc, tol=0.05)
    x = spiked(400, 32, 2)
    plan_lr = Plan(gamma=0.5, batch_size=100, cov_path="lowrank", rank=8)
    with pytest.raises(ValueError, match="not both"):
        SparsifiedPCA(2, plan_lr, key=0).fit_refine(x, passes=2, tol=1e-3)
    with pytest.raises(ValueError, match="tol"):
        SparsifiedPCA(2, plan_lr, key=0).fit_refine(x, tol=0.0)
    with pytest.raises(ValueError, match="max_passes"):
        SparsifiedPCA(2, plan_lr, key=0).fit_refine(x, tol=1e-3, max_passes=0)


# ------------------------------------------------- slow-lane acceptance -----


@pytest.mark.slow
def test_refine_acceptance_n80k():
    """The acceptance bar on the n=80k spiked model: fit_refine(passes=1)
    shrinks dense-vs-lowrank principal angles ≥ 10×, and two-pass K-means
    centers are bit-identical across batch/stream/sharded."""
    # γ=0.25: the mask-noise floor of the sketched operator is what the
    # one-pass range-finder leaks (at γ→1 and n=80k the one-pass fit is
    # already within ~4× of the core-solve floor and no pass can buy 10×)
    p, k, n, ell = 128, 4, 80000, 12
    x = spiked(n, p, k, noise=1e-2)
    plan0 = Plan(gamma=0.25, batch_size=4096)
    dense = SparsifiedPCA(k, plan0, key=3).fit(x)
    angles = {}
    for backend in BACKENDS:
        plan = plan0.replace(backend=backend, cov_path="lowrank", rank=ell)
        a_one = max_angle_sin(SparsifiedPCA(k, plan, key=3).fit(x).components_,
                              dense.components_)
        ref = SparsifiedPCA(k, plan, key=3).fit_refine(x, passes=1)
        a_ref = max_angle_sin(ref.components_, dense.components_)
        angles[backend] = (a_one, a_ref)
        assert a_ref * 10 <= a_one, (backend, a_one, a_ref)

    xc, _, _ = make_clusters(KEY, n=80000, p=64, k=6, sep=2.5, noise=1.0)
    cents = {}
    for backend in BACKENDS:
        plan = Plan(backend=backend, gamma=0.25, batch_size=4096)
        km = SparsifiedKMeans(6, plan, key=5,
                              algorithm="minibatch").fit_refine(xc, passes=2)
        cents[backend] = np.asarray(km.centers_)
    for backend in ("stream", "sharded"):
        np.testing.assert_array_equal(cents[backend], cents["batch"])
