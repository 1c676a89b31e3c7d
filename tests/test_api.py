"""Unified repro.api estimator layer: backend equivalence (batch == stream ==
sharded at 1e-5 for mean/cov/PCA/K-means), the fit/partial_fit/finalize
contract, DCT end-to-end, spec validation, compact-path covariance, and the
one-PRNG-story gradient compressor."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

from repro.api import (
    GradCompressor,
    Plan,
    SparsifiedCov,
    SparsifiedKMeans,
    SparsifiedMean,
    SparsifiedPCA,
    fit_many,
    make_engine,
)
from repro.core import sketch
from repro.core.grad_compress import CompressConfig, mask_spec
from repro.core.sampling import sample_indices
from repro.core.sketch import batch_key
from tests.conftest import make_clusters

KEY = jax.random.PRNGKey(0)
BACKENDS = ("batch", "stream", "sharded")


def _plan(**kw):
    kw.setdefault("backend", "batch")
    kw.setdefault("gamma", 0.25)
    kw.setdefault("batch_size", 200)
    return Plan(**kw)


def _lowrank(n=1200, p=64, k=4):
    """Well-separated spectrum so eigenvectors are stable across reorderings."""
    u, _ = jnp.linalg.qr(jax.random.normal(KEY, (p, k)))
    lam = jnp.asarray([9.0, 6.0, 4.0, 2.5])
    z = jax.random.normal(jax.random.fold_in(KEY, 1), (n, k)) * lam
    return z @ u.T + 0.05 * jax.random.normal(jax.random.fold_in(KEY, 2), (n, p))


# ------------------------------------------------- backend equivalence ------


@pytest.mark.parametrize("backend", ("stream", "sharded"))
def test_mean_cov_backends_match_batch(backend):
    """The acceptance bar: flipping Plan.backend re-runs the same job to 1e-5
    (same per-(step, shard) sketches, different fold order)."""
    x = jax.random.normal(KEY, (1000, 64))
    ref = SparsifiedCov(_plan(), key=7).fit(x)
    alt = SparsifiedCov(_plan(backend=backend), key=7).fit(x)
    np.testing.assert_allclose(np.asarray(alt.mean_), np.asarray(ref.mean_),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(alt.cov_), np.asarray(ref.cov_),
                               rtol=1e-4, atol=1e-5)
    assert alt.count_ == ref.count_ == 1000

    m_ref = SparsifiedMean(_plan(), key=7).fit(x)
    m_alt = SparsifiedMean(_plan(backend=backend), key=7).fit(x)
    np.testing.assert_allclose(np.asarray(m_alt.mean_), np.asarray(m_ref.mean_),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ("stream", "sharded"))
def test_pca_backends_match_batch(backend):
    x = _lowrank()
    ref = SparsifiedPCA(4, _plan(), key=5).fit(x)
    alt = SparsifiedPCA(4, _plan(backend=backend), key=5).fit(x)
    np.testing.assert_allclose(np.asarray(alt.explained_variance_),
                               np.asarray(ref.explained_variance_), rtol=1e-5)
    # eigenvectors are sign-ambiguous: align, then compare
    signs = np.sign(np.sum(np.asarray(alt.components_) * np.asarray(ref.components_),
                           axis=1, keepdims=True))
    np.testing.assert_allclose(np.asarray(alt.components_) * signs,
                               np.asarray(ref.components_), atol=1e-5)


@pytest.mark.parametrize("backend", ("stream", "sharded"))
@pytest.mark.parametrize("algorithm", ("lloyd", "minibatch"))
def test_kmeans_backends_match_batch(backend, algorithm):
    """Hungarian-aligned centers and the objective agree across backends."""
    x, labels, _ = make_clusters(KEY, n=1000, p=64, k=4)
    ref = SparsifiedKMeans(4, _plan(), key=9, algorithm=algorithm).fit(x)
    alt = SparsifiedKMeans(4, _plan(backend=backend), key=9, algorithm=algorithm).fit(x)
    np.testing.assert_allclose(float(alt.objective_), float(ref.objective_), rtol=1e-5)
    d = np.linalg.norm(np.asarray(alt.centers_)[:, None]
                       - np.asarray(ref.centers_)[None], axis=-1)
    ri, ci = linear_sum_assignment(d)
    assert float(d[ri, ci].max()) < 1e-5 * (1 + float(np.abs(ref.centers_).max()))
    if algorithm == "lloyd":
        # assignments identical up to the same center permutation
        perm = np.empty(4, dtype=int)
        perm[ci] = ri
        assert np.array_equal(perm[np.asarray(alt.labels_)], np.asarray(ref.labels_))


def test_partial_fit_matches_fit():
    """Feeding the stream in batch_size pieces == one fit of the concatenation."""
    x = jax.random.normal(KEY, (600, 32))
    plan = _plan(backend="stream", batch_size=100)
    whole = SparsifiedCov(plan, key=3).fit(x)
    inc = SparsifiedCov(plan, key=3)
    for i in range(6):
        inc.partial_fit(x[i * 100:(i + 1) * 100])
    inc.finalize()
    np.testing.assert_array_equal(np.asarray(inc.cov_), np.asarray(whole.cov_))
    np.testing.assert_array_equal(np.asarray(inc.mean_), np.asarray(whole.mean_))


def test_fit_stream_consumes_pipeline_source():
    from repro.data.pipeline import VectorStreamSource

    src = VectorStreamSource(p=64, batch=128, seed=3)
    est = SparsifiedMean(_plan(backend="stream", batch_size=128), key=2)
    est.fit_stream(src, steps=3)
    assert est.count_ == 384 and est.mean_.shape == (64,)


# --------------------------------------------- fit_many: one shared sketch --


@pytest.mark.parametrize("backend", BACKENDS)
def test_fit_many_equals_separate_fits(backend):
    """The tentpole acceptance bar: ONE compression pass feeding every consumer
    reproduces the separate fits on every backend."""
    x, labels, _ = make_clusters(KEY, n=1000, p=64, k=4)
    plan = _plan(backend=backend)
    mean_c = SparsifiedMean(plan, key=7)
    cov_c = SparsifiedCov(plan, key=7)
    pca_c = SparsifiedPCA(4, plan, key=7)
    km_l = SparsifiedKMeans(4, plan, key=7)
    km_m = SparsifiedKMeans(4, plan, key=7, algorithm="minibatch")
    run = fit_many(plan, [mean_c, cov_c, pca_c, km_l, km_m], x)
    assert run.count == 1000 and run.n_sketches == 5 and len(run) == 5

    mean_s = SparsifiedMean(plan, key=7).fit(x)
    cov_s = SparsifiedCov(plan, key=7).fit(x)
    pca_s = SparsifiedPCA(4, plan, key=7).fit(x)
    km_ls = SparsifiedKMeans(4, plan, key=7).fit(x)
    km_ms = SparsifiedKMeans(4, plan, key=7, algorithm="minibatch").fit(x)

    np.testing.assert_allclose(np.asarray(mean_c.mean_), np.asarray(mean_s.mean_),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cov_c.cov_), np.asarray(cov_s.cov_),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pca_c.components_),
                               np.asarray(pca_s.components_), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pca_c.explained_variance_),
                               np.asarray(pca_s.explained_variance_), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(km_l.centers_), np.asarray(km_ls.centers_),
                               atol=1e-5)
    assert np.array_equal(np.asarray(km_l.labels_), np.asarray(km_ls.labels_))
    np.testing.assert_allclose(np.asarray(km_m.centers_), np.asarray(km_ms.centers_),
                               atol=1e-5)
    assert mean_c.count_ == cov_c.count_ == km_l.count_ == 1000


def test_fit_many_sketches_once_per_chunk(monkeypatch):
    """The whole point: sketch() runs once per (step, shard) chunk, NOT once
    per consumer per chunk."""
    calls = {"n": 0}
    real = sketch.sketch

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(sketch, "sketch", counting)
    x = jax.random.normal(KEY, (600, 64))
    plan = _plan()  # batch_size=200 → 3 chunks
    consumers = [SparsifiedPCA(4, plan, key=7), SparsifiedCov(plan, key=7),
                 SparsifiedKMeans(4, plan, key=7)]
    run = fit_many(plan, consumers, x)
    assert calls["n"] == 3 == run.n_sketches
    calls["n"] = 0
    SparsifiedPCA(4, plan, key=7).fit(x)
    SparsifiedCov(plan, key=7).fit(x)
    SparsifiedKMeans(4, plan, key=7).fit(x)
    assert calls["n"] == 9  # separate fits: one pass per consumer


def test_fit_many_from_source():
    """The (seed, step, shard) source contract through the shared pass."""
    from repro.data.pipeline import VectorStreamSource

    plan = _plan(backend="stream", batch_size=128)
    mean_c, cov_c = SparsifiedMean(plan, key=2), SparsifiedCov(plan, key=2)
    run = fit_many(plan, [mean_c, cov_c],
                   source=VectorStreamSource(p=64, batch=128, seed=3), steps=3)
    assert run.count == 384
    ref = SparsifiedMean(plan, key=2).fit_stream(
        VectorStreamSource(p=64, batch=128, seed=3), steps=3)
    np.testing.assert_array_equal(np.asarray(mean_c.mean_), np.asarray(ref.mean_))
    assert cov_c.cov_.shape == (64, 64)


def test_fit_many_continued_ingest():
    """finalize=False + run.partial_fit extends the SHARED pass for everyone."""
    x = jax.random.normal(KEY, (400, 32))
    plan = _plan(backend="stream", batch_size=100)
    mean_c, cov_c = SparsifiedMean(plan, key=3), SparsifiedCov(plan, key=3)
    run = fit_many(plan, [mean_c, cov_c], x[:200], finalize=False)
    run.partial_fit(x[200:]).finalize()
    whole = SparsifiedCov(plan, key=3).fit(x)
    np.testing.assert_array_equal(np.asarray(cov_c.cov_), np.asarray(whole.cov_))
    np.testing.assert_array_equal(np.asarray(mean_c.mean_), np.asarray(whole.mean_))
    assert mean_c.count_ == 400


@pytest.mark.parametrize("algorithm", ("minibatch", "lloyd"))
def test_sync_waits_for_every_consumer_state(monkeypatch, algorithm):
    """sync() is the ingest barrier: it returns only once the last sketch and
    every registered consumer's fold state are ready on the device."""
    x = np.asarray(jax.random.normal(KEY, (400, 32)))
    plan = _plan(backend="stream", batch_size=100)
    km = SparsifiedKMeans(3, plan, key=3, algorithm=algorithm)
    cov_c = SparsifiedCov(plan, key=3)
    run = fit_many(plan, [km, cov_c], x, finalize=False)
    waited = []
    real = jax.block_until_ready

    def spy(tree):
        waited.extend(jax.tree_util.tree_leaves(tree))
        return real(tree)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    run.sync()
    states = [km._km_state, km._reducer.parts, cov_c._reducer.state]
    leaves = jax.tree_util.tree_leaves(states)
    assert leaves and all(leaf.is_ready() for leaf in leaves)
    assert {id(leaf) for leaf in leaves} <= {id(w) for w in waited}


def test_reset_detaches_from_shared_cursor():
    """reset() must unregister from a live shared pass — the old run keeps
    feeding the OTHER consumers only, never the reset estimator."""
    x = jax.random.normal(KEY, (400, 32))
    plan = _plan(backend="stream", batch_size=100)
    mean_c, cov_c = SparsifiedMean(plan, key=3), SparsifiedCov(plan, key=3)
    run = fit_many(plan, [mean_c, cov_c], x[:200], finalize=False)
    mean_c.reset()
    run.partial_fit(x[200:])            # only cov_c still rides the shared pass
    assert mean_c.count_ == 0 and cov_c.count_ == 400
    run.finalize()                      # skips the detached mean_c, fits cov_c
    assert not mean_c._fitted and cov_c._fitted
    whole = SparsifiedCov(plan, key=3).fit(x)
    np.testing.assert_array_equal(np.asarray(cov_c.cov_), np.asarray(whole.cov_))
    # the reset estimator refits independently, untouched by the old run
    mean_c.fit(x[:100])
    assert mean_c.count_ == 100


def test_fit_many_validation():
    x = jnp.ones((8, 16))
    plan = _plan()
    with pytest.raises(ValueError, match="at least one"):
        fit_many(plan, [], x)
    with pytest.raises(ValueError, match="exactly one"):
        fit_many(plan, [SparsifiedMean(plan, key=0)])
    with pytest.raises(ValueError, match="exactly one"):
        fit_many(plan, [SparsifiedMean(plan, key=0)], x, source=lambda s, t, sh: x)
    with pytest.raises(ValueError, match="steps"):
        fit_many(plan, [SparsifiedMean(plan, key=0)], source=lambda s, t, sh: x)
    with pytest.raises(ValueError, match="same key"):
        fit_many(plan, [SparsifiedMean(plan, key=0), SparsifiedCov(plan, key=1)], x)
    with pytest.raises(ValueError, match="gamma"):
        fit_many(plan, [SparsifiedMean(_plan(gamma=0.5), key=0)], x)
    with pytest.raises(TypeError, match="SketchedEstimator"):
        fit_many(plan, [GradCompressor()], x)
    with pytest.raises(TypeError, match="SketchedEstimator"):
        fit_many(plan, [np.ones((4, 4))], x)  # key-less object in position 0


def test_sharded_moments_stream_constant_memory():
    """The sharded moment path is per-step psum streaming now — nothing is
    retained past its step (the old concat()-then-reduce kept everything)."""
    x = jax.random.normal(KEY, (1000, 64))
    est = SparsifiedCov(_plan(backend="sharded"), key=7).fit(x)
    assert est._reducer.parts == [] and est._reducer._step_parts == []
    assert int(est._reducer.state.count) == 1000
    # … while Lloyd K-means still retains the sketch it clusters (Alg. 1)
    km = SparsifiedKMeans(3, _plan(backend="sharded"), key=7).fit(x)
    assert len(km._reducer.parts) == 5


# -------------------------------------- satellite: minibatch tail flush -----


def test_minibatch_tail_flush_and_interleaved_finalize():
    """Row counts that are no multiple of batch_size·n_shards leave a pending
    half step; finalize() flushes it and acts as a checkpoint that
    partial_fit can continue from."""
    x, _, _ = make_clusters(KEY, n=1100, p=32, k=3)
    plan = _plan(backend="stream", batch_size=100, n_shards=2)
    est = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch")
    est.partial_fit(x[:500])            # 5 chunks = 2 full steps + 1 pending shard
    assert len(est._km_step_sketches) == 1
    est.finalize()
    assert est._km_step_sketches == [] and est.count_ == 500
    c1 = np.asarray(est.centers_)
    assert np.isfinite(c1).all()
    est.partial_fit(x[500:])            # 6 more chunks, ends on a half step again
    est.finalize()
    assert est.count_ == 1100 and est.centers_.shape == (3, 32)
    assert np.isfinite(np.asarray(est.centers_)).all()
    assert not np.allclose(np.asarray(est.centers_), c1)  # the tail data counted


def test_minibatch_ragged_tail_with_decay():
    """Ragged tails × decay < 1 (the forgetting factor): pending half steps
    flush correctly under float counts, the per-step reassignment history has
    one entry per APPLIED step at every partial_fit/finalize checkpoint, and
    the decayed counts stay positive and bounded by b·n_shards/(1−decay)."""
    decay = 0.8
    x, _, _ = make_clusters(KEY, n=1030, p=16, k=3)
    plan = _plan(backend="stream", batch_size=100, n_shards=2)
    est = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch", decay=decay)

    est.partial_fit(x[:330])            # 4 chunks: 2 applied steps incl. tail30
    est.finalize()                      #   → the pending (step 1, shard 1) flushes
    assert est.count_ == 330
    assert est.reassign_counts_ is not None and len(est.reassign_counts_) == 2
    counts = np.asarray(est._km_state.counts)
    assert counts.dtype == np.float32   # decay ⇒ float counts
    assert (counts >= 0).all() and counts.sum() > 0
    bound = 100 * 2 / (1 - decay)       # decay bounds any cell's count
    assert counts.max() <= bound + 1e-3

    est.partial_fit(x[330:])            # 7 more chunks, ends on a half step
    est.finalize()
    assert est.count_ == 1030
    # 11 chunks / 2 shards → 6 applied steps total (finalize flushed the tail)
    assert len(est.reassign_counts_) == 6
    assert (np.asarray(est.reassign_counts_) >= 0).all()
    assert est.reassign_fraction_.shape == (6,)
    assert np.all(est.reassign_fraction_ <= 1.0)
    counts = np.asarray(est._km_state.counts)
    assert (counts >= 0).all() and counts.max() <= bound + 1e-3
    assert np.isfinite(np.asarray(est.centers_)).all()


def test_minibatch_zero_row_batch_is_noop():
    x, _, _ = make_clusters(KEY, n=300, p=32, k=3)
    plan = _plan(backend="stream", batch_size=100)
    est = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch")
    est.partial_fit(x)
    st = est._km_state
    est.partial_fit(jnp.zeros((0, 32)))  # zero-row batch: nothing folds
    assert est._km_state is st and est.count_ == 300
    est.finalize()
    assert est.count_ == 300
    # zero rows as the ONLY input: spec exists but there is nothing to finalize
    est2 = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch")
    est2.partial_fit(jnp.zeros((0, 32)))
    with pytest.raises(RuntimeError, match="no batches"):
        est2.finalize()


# ------------------------------------------ satellite: sketch() utility -----


def test_sketch_on_unfitted_does_not_pin():
    """sketch() is a read-only utility: on a fresh estimator it derives a
    throwaway spec — no p pinning, no reducer allocation."""
    est = SparsifiedMean(_plan(), key=0)
    s = est.sketch(jnp.ones((4, 64)))
    assert s.n == 4
    assert est.spec_ is None and est._reducer is None
    est.partial_fit(jnp.ones((8, 32)))  # a different p still fits fine
    assert est.spec_.p == 32


def test_sketch_mask_key_per_call():
    """Repeated sketch() calls reuse the spec's one-shot mask (documented);
    mask_key= draws an independent mask per call."""
    est = SparsifiedMean(_plan(), key=0).fit(jax.random.normal(KEY, (64, 64)))
    x = jnp.ones((16, 64))
    s1, s2 = est.sketch(x), est.sketch(x)
    np.testing.assert_array_equal(np.asarray(s1.indices), np.asarray(s2.indices))
    s3 = est.sketch(x, mask_key=1)
    assert not np.array_equal(np.asarray(s3.indices), np.asarray(s1.indices))
    np.testing.assert_array_equal(
        np.asarray(est.sketch(x, mask_key=1).indices), np.asarray(s3.indices))


# ------------------------------------------------------ satellite: DCT ------


def test_dct_pca_end_to_end():
    """transform="dct" (no padding, η=0.5) through the full PCA path."""
    x = _lowrank(p=60)  # non-power-of-two: DCT needs no padding
    plan = _plan(transform="dct", gamma=0.3)
    est = SparsifiedPCA(4, plan, key=11).fit(x)
    assert est.components_.shape == (4, 60)
    from repro.core import pca

    ev = float(pca.explained_variance(est.components_, x))
    ev_dense = float(pca.explained_variance(pca.pca(x, 4).components, x))
    assert ev > 0.9 * ev_dense, (ev, ev_dense)
    # stream backend reproduces it
    est_s = SparsifiedPCA(4, plan.replace(backend="stream"), key=11).fit(x)
    signs = np.sign(np.sum(np.asarray(est_s.components_) * np.asarray(est.components_),
                           axis=1, keepdims=True))
    np.testing.assert_allclose(np.asarray(est_s.components_) * signs,
                               np.asarray(est.components_), atol=1e-5)


def test_dct_kmeans_end_to_end():
    x, labels, _ = make_clusters(KEY, n=900, p=48, k=3)
    from repro.core import kmeans as km

    est = SparsifiedKMeans(3, _plan(transform="dct", gamma=0.4), key=13).fit(x)
    acc = km.clustering_accuracy(est.labels_, labels, 3)
    assert acc > 0.95, acc
    # predict on fresh rows from the same clusters stays consistent
    pred = est.predict(x[:200])
    assert float(np.mean(np.asarray(pred) == np.asarray(est.labels_[:200]))) > 0.95


# ------------------------------------------- satellite: spec validation -----


def test_make_spec_validates_gamma_and_clamps_m():
    with pytest.raises(ValueError, match="gamma"):
        sketch.make_spec(64, KEY, gamma=1.5)
    with pytest.raises(ValueError, match="gamma"):
        sketch.make_spec(64, KEY, gamma=0.0)
    with pytest.raises(ValueError, match="m must be"):
        sketch.make_spec(64, KEY, m=65)
    with pytest.raises(ValueError, match="m must be"):
        sketch.make_spec(64, KEY, m=0)
    # gamma=1 rounds to exactly p_pad and stays a valid sampler
    spec = sketch.make_spec(60, KEY, gamma=1.0)
    assert spec.m == spec.p_pad == 64
    assert sketch.make_spec(64, KEY, gamma=1e-9).m == 1


def test_gamma_unified_and_compression_ratio_at_padded_p():
    """γ is canonically m / p_pad; storage ratio is against the ORIGINAL p."""
    spec = sketch.make_spec(1000, KEY, gamma=0.25)       # p_pad = 1024
    assert spec.p_pad == 1024 and spec.m == 256
    assert spec.gamma == 256 / 1024
    assert sketch.compression_ratio(spec) == pytest.approx(256 * 8 / 4000)
    # sketched rows live in the padded domain, where both definitions agree
    s = sketch.sketch(jnp.ones((4, 1000)), spec)
    assert s.p == spec.p_pad
    with pytest.warns(DeprecationWarning, match="p_pad"):
        assert s.gamma == spec.gamma


# -------------------------------------- satellite: compact-path cov ---------


@pytest.mark.parametrize("backend", BACKENDS)
def test_compact_cov_path_matches_dense(backend):
    """cov_path="compact" (no dense (b, p) intermediate) == "dense" on every
    backend — the γ ≪ 1 streaming memory fix behind MomentState."""
    x = jax.random.normal(KEY, (500, 64))
    dense = SparsifiedCov(_plan(backend=backend, gamma=0.1), key=4).fit(x)
    compact = SparsifiedCov(_plan(backend=backend, gamma=0.1, cov_path="compact"),
                            key=4).fit(x)
    np.testing.assert_allclose(np.asarray(compact.cov_), np.asarray(dense.cov_),
                               rtol=1e-4, atol=1e-4)


def test_engine_compact_cov_path():
    """The same fix through the StreamEngine plumbing (api.make_engine)."""
    x = jax.random.normal(KEY, (4, 1, 50, 64))

    def source(seed, step, shard):
        return np.asarray(x[step, shard])

    plan = Plan(backend="stream", gamma=0.1, batch_size=50)
    res_d = make_engine(plan, 64, jax.random.PRNGKey(2), source).run(4)
    res_c = make_engine(plan.replace(cov_path="compact"), 64,
                        jax.random.PRNGKey(2), source).run(4)
    np.testing.assert_allclose(np.asarray(res_c.cov), np.asarray(res_d.cov),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------- cov original domain ------


def test_cov_original_domain_roundtrip():
    """(HD)ᵀ Ĉ_pre (HD) lands near the dense empirical second moment."""
    x = _lowrank(n=4000, p=32)
    est = SparsifiedCov(_plan(gamma=0.5, batch_size=1000), key=6).fit(x)
    c = est.cov_original()
    assert c.shape == (32, 32)
    from repro.core import estimators

    c_emp = np.asarray(estimators.empirical_cov(x))
    rel = np.linalg.norm(np.asarray(c) - c_emp, 2) / np.linalg.norm(c_emp, 2)
    assert rel < 0.15, rel


# ---------------------------------------------- grad compressor story -------


def test_grad_compressor_shares_batch_key_discipline():
    """The compressor's per-step mask IS sample_indices(batch_key(spec, step, 0))
    — one PRNG/bookkeeping story with the data sketch (ROADMAP open item)."""
    cfg = CompressConfig(gamma=0.25, chunk_p=256, error_feedback=False)
    key = jax.random.PRNGKey(5)
    vec = jax.random.normal(KEY, (1024,))
    from repro.core import ros
    from repro.core.grad_compress import compress_decompress

    g_hat, vals = compress_decompress(vec, key, jnp.int32(7), cfg)
    spec = mask_spec(cfg, key)
    idx = sample_indices(batch_key(spec, jnp.int32(7), 0), 4, 256, cfg.m)
    y = ros.precondition(vec.reshape(4, 256), spec.signs_key(), "hadamard")
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.asarray(jnp.take_along_axis(y, idx, -1)))
    # unbiased round trip reconstructs the vector in expectation; here just
    # check the estimator's projection identity R Rᵀ y at kept coordinates
    assert g_hat.shape == vec.shape


def test_grad_compressor_stateful_front_door():
    g = {"a": jax.random.normal(KEY, (300,)), "b": jax.random.normal(KEY, (40, 10))}
    gc = GradCompressor(CompressConfig(gamma=0.1, chunk_p=256), key=3)
    g1 = gc.transform(g)
    assert gc.step_ == 1 and gc.residual_ is not None and gc.wire_floats_ > 0
    assert jax.tree.structure(g1) == jax.tree.structure(g)
    # error feedback: residual carries the un-sent mass
    vec = jnp.concatenate([g["a"], g["b"].reshape(-1)])
    v1 = jnp.concatenate([g1["a"], g1["b"].reshape(-1)])
    rvec = jnp.concatenate([gc.residual_["a"], gc.residual_["b"].reshape(-1)])
    np.testing.assert_allclose(np.asarray(v1 + rvec), np.asarray(vec), atol=1e-5)
    # deterministic per step: a reset compressor reproduces step 0 exactly
    g1b = GradCompressor(CompressConfig(gamma=0.1, chunk_p=256), key=3).transform(g)
    np.testing.assert_array_equal(np.asarray(g1["a"]), np.asarray(g1b["a"]))


# ----------------------------------------- pre-API entry points still work --


def test_preexisting_entry_points_import_and_run():
    """Every pre-API public entry point still imports and runs from its home
    (the distributed one-pass reductions live in repro.stream.sharded)."""
    from repro.core import estimators, kmeans as km_mod, pca as pca_mod
    from repro.stream import sharded as dist

    x = jax.random.normal(KEY, (64, 32))
    spec = sketch.make_spec(32, jax.random.PRNGKey(1), gamma=0.5)
    s = sketch.sketch(x, spec)
    mesh = jax.make_mesh((1,), ("data",))
    np.testing.assert_allclose(np.asarray(dist.sharded_mean(s, mesh)),
                               np.asarray(estimators.mean_estimator(s)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dist.sharded_cov(s, mesh)),
                               np.asarray(estimators.cov_estimator(s)), atol=1e-4)
    mu, a, obj, it = dist.sharded_kmeans(s, 3, jax.random.PRNGKey(2), mesh,
                                         n_init=2, max_iter=10)
    assert mu.shape == (3, 32)
    # batch_key is importable from its historical home too
    from repro.stream import batch_key as bk

    assert bk is batch_key
    res = pca_mod.sparsified_pca(s, spec, 2)
    assert res.components.shape == (2, 32)


def test_plan_validation():
    with pytest.raises(ValueError, match="backend"):
        Plan(backend="nope", gamma=0.1)
    with pytest.raises(ValueError, match="cov_path"):
        Plan(gamma=0.1, cov_path="sparse")
    with pytest.raises(ValueError, match="n_shards"):
        Plan(gamma=0.1, n_shards=0)
    with pytest.raises(ValueError, match="m >= 2"):
        SparsifiedCov(Plan(m=1), key=0).fit(jnp.ones((8, 16)))
    with pytest.raises(ValueError, match="p="):
        est = SparsifiedMean(_plan(), key=0)
        est.partial_fit(jnp.ones((8, 16)))
        est.partial_fit(jnp.ones((8, 32)))
    with pytest.raises(RuntimeError, match="no batches"):
        SparsifiedMean(_plan(), key=0).finalize()
    # an out-of-range CompressConfig fails at spec construction, not in the sampler
    with pytest.raises(ValueError, match="m must be"):
        mask_spec(CompressConfig(gamma=1.5, chunk_p=1024), KEY)


# ----------------------------------------------- sharded, for real ----------


@pytest.mark.slow
def test_sharded_backend_matches_batch_on_8_devices():
    """The acceptance test at real multi-device scale: Plan(backend="sharded",
    n_shards=8) over 8 forced host devices == batch, to 1e-5 (subprocess so
    the session keeps the single real device). 1160 rows / batch 80 = 15 chunks
    — NOT a multiple of n_shards, so the sharded moment path's trailing
    partial step must be psum-flushed at reduce time (dropping it would shift
    the mean/cov visibly)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from scipy.optimize import linear_sum_assignment
        from repro.api import Plan, SparsifiedCov, SparsifiedKMeans

        x = jax.random.normal(jax.random.PRNGKey(0), (1160, 64))
        plan = Plan(backend="batch", gamma=0.25, batch_size=80, n_shards=8)
        assert SparsifiedCov(plan.replace(backend="sharded"), key=7).fit(x).count_ == 1160
        ref = SparsifiedCov(plan, key=7).fit(x)
        alt = SparsifiedCov(plan.replace(backend="sharded"), key=7).fit(x)
        np.testing.assert_allclose(np.asarray(alt.mean_), np.asarray(ref.mean_), atol=1e-5)
        np.testing.assert_allclose(np.asarray(alt.cov_), np.asarray(ref.cov_), atol=1e-4)

        k1 = SparsifiedKMeans(4, plan, key=9).fit(x)
        k8 = SparsifiedKMeans(4, plan.replace(backend="sharded"), key=9).fit(x)
        np.testing.assert_allclose(float(k8.objective_), float(k1.objective_), rtol=1e-4)
        d = np.linalg.norm(np.asarray(k8.centers_)[:, None]
                           - np.asarray(k1.centers_)[None], axis=-1)
        ri, ci = linear_sum_assignment(d)
        assert float(d[ri, ci].max()) < 1e-4
        print("api-sharded-8dev OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


# ------------------------------------------------- scanned ingest (scan=) ---


def test_fit_many_scan_matches_host_loop():
    """fit_many(scan=True) — the lax.scan hot loop — reproduces the host
    chunk loop on every scan-eligible consumer: stream moments, lowrank-range
    PCA, minibatch K-means (with the reassignment signal), including a ragged
    tail that the host loop picks up after the scanned full steps."""
    x = _lowrank(n=440, p=64)
    plan = _plan(backend="stream", batch_size=100, n_shards=2)
    plan_lr = plan.replace(cov_path="lowrank", rank=16)

    def consumers():
        return [SparsifiedMean(plan, key=1),
                SparsifiedPCA(3, plan_lr, key=1),
                SparsifiedKMeans(3, plan, key=1, algorithm="minibatch")]

    host = consumers()
    scanned = consumers()
    fit_many(plan, host, x)
    run = fit_many(plan, scanned, x, scan=True)

    # the scan consumed 2 full steps (400 rows); the 40-row tail host-folded
    assert run.cursor.chunk_rows == [100, 100, 100, 100, 40]
    assert run.count == 440 and run.n_sketches == 5
    for h, s in zip(host, scanned):
        assert h.count_ == s.count_ == 440
    np.testing.assert_allclose(np.asarray(scanned[0].mean_),
                               np.asarray(host[0].mean_), atol=1e-5)
    np.testing.assert_allclose(np.abs(np.asarray(scanned[1].components_)),
                               np.abs(np.asarray(host[1].components_)), atol=1e-4)
    np.testing.assert_allclose(np.asarray(scanned[2].centers_),
                               np.asarray(host[2].centers_), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(scanned[2].reassign_counts_),
                                  np.asarray(host[2].reassign_counts_))


def _eager_minibatch(x, plan, k, key, *, decay, track):
    """The minibatch fold replayed one eager op at a time: per step, every
    shard's delta against the step-start state, summed and applied once, then
    the rows reassigned under the new centers (best hypothesis)."""
    from repro.api.estimators import as_key
    from repro.stream import accumulators as acc
    from repro.utils.prng import fold_in_str

    spec = plan.spec(x.shape[1], as_key(key))
    bs, ns = plan.batch_size, plan.n_shards
    sk = [sketch.sketch(x[i:i + bs], spec,
                        batch_key=batch_key(spec, *plan.step_shard(j)),
                        impl=plan.impl)
          for j, i in enumerate(range(0, x.shape[0], bs))]
    st = acc.kmeans_init(fold_in_str(spec.key, "api-kmeans"), sk[0], k, 3,
                         decay=decay)
    counts = []
    for t in range(0, len(sk), ns):
        step = sk[t:t + ns]
        pairs = [acc.kmeans_delta_with_assign(st, s) for s in step]
        delta = pairs[0][0]
        for d, _ in pairs[1:]:
            delta = jax.tree.map(jnp.add, delta, d)
        st = acc.kmeans_apply(st, delta, decay=decay)
        counts.append(sum(acc.kmeans_reassigned(st, s, a0)
                          for s, (_, a0) in zip(step, pairs)))
    best = int(np.argmin(np.asarray(st.obj)))
    return st, (np.array([int(c[best]) for c in counts]) if track else None)


@pytest.mark.parametrize("track", (True, False))
@pytest.mark.parametrize("decay", (1.0, 0.9))
@pytest.mark.parametrize("n_shards", (1, 2))
def test_fit_many_scan_matches_host_loop_kmeans(n_shards, decay, track):
    """Minibatch K-means folds one step the same way three times: the host
    loop's compiled step, the scan body, and an eager op-by-op replay of
    the accumulator algebra — a ragged tail (and, on 2 shards, a half step
    that finalize flushes) included."""
    x, _, _ = make_clusters(KEY, n=440, p=64, k=3)
    plan = _plan(backend="stream", batch_size=100, n_shards=n_shards)

    def km():
        return SparsifiedKMeans(3, plan, key=1, algorithm="minibatch",
                                decay=decay, track_reassignments=track)

    host, scanned = km(), km()
    fit_many(plan, [host], x)
    fit_many(plan, [scanned], x, scan=True)
    st, counts = _eager_minibatch(x, plan, 3, 1, decay=decay, track=track)

    assert host.count_ == scanned.count_ == 440
    for got, want in zip(jax.tree_util.tree_leaves(host._km_state),
                         jax.tree_util.tree_leaves(st)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(scanned.centers_),
                               np.asarray(host.centers_), atol=1e-5)
    if track:
        assert len(counts) == -(-5 // n_shards)     # steps of 5 chunks
        np.testing.assert_array_equal(host.reassign_counts_, counts)
        np.testing.assert_array_equal(scanned.reassign_counts_, counts)
    else:
        assert host.reassign_counts_ is None and scanned.reassign_counts_ is None


def test_minibatch_snapshot_round_trips_device_history():
    """state_arrays reads the reassignment history the folds left on the
    device; load_state_arrays into a fresh estimator continues the fit as
    if uninterrupted (counts, centers and history)."""
    x, _, _ = make_clusters(KEY, n=800, p=32, k=3)
    plan = _plan(backend="stream", batch_size=100, n_shards=2)
    whole = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch").fit(x)

    est = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch")
    est.partial_fit(x[:400])
    assert len(est._reassign_device) == 2 and est._reassign_history == []
    arrs = est.state_arrays()
    assert arrs["km.reassign_counts"].shape == (2, 3)
    np.testing.assert_array_equal(arrs["km.reassign_rows"], [200, 200])

    back = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch")
    back._cursor.ensure_spec(32)
    back.load_state_arrays(arrs)
    back._cursor.chunk, back._cursor.count = 4, 400
    back.partial_fit(x[400:]).finalize()
    assert back.count_ == 800
    np.testing.assert_array_equal(back.reassign_counts_, whole.reassign_counts_)
    np.testing.assert_allclose(np.asarray(back.centers_),
                               np.asarray(whole.centers_), atol=1e-6)
    est.partial_fit(x[400:401])          # mid-step: a shard sketch is buffered
    with pytest.raises(RuntimeError, match="mid-step"):
        est.state_arrays()


def test_minibatch_history_drains_from_the_device(monkeypatch):
    """A long ingest with no finalize reads the reassignment counts back in
    batches of _HISTORY_ON_DEVICE steps, so the device buffers it holds stay
    bounded; the history is the same as when read at the end."""
    from repro.api import estimators

    x, _, _ = make_clusters(KEY, n=500, p=32, k=3)
    plan = _plan(backend="stream", batch_size=100)
    whole = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch").fit(x)
    monkeypatch.setattr(estimators, "_HISTORY_ON_DEVICE", 2)
    est = SparsifiedKMeans(3, plan, key=5, algorithm="minibatch")
    est.partial_fit(x)                  # 5 steps: drained at steps 2 and 4
    assert len(est._reassign_history) == 4 and len(est._reassign_device) == 1
    est.finalize()
    np.testing.assert_array_equal(est.reassign_counts_, whole.reassign_counts_)


def test_fit_many_scan_extends_the_pass():
    """SharedSketchRun.partial_fit keeps scanning: two scanned feeds ≡ one
    host-loop fit of the concatenation (same chunks, same keys)."""
    x = _lowrank(n=800, p=64)
    plan = _plan(backend="stream", batch_size=100, n_shards=2)
    whole = SparsifiedMean(plan, key=1)
    fit_many(plan, [whole], x)
    piecewise = SparsifiedMean(plan, key=1)
    run = fit_many(plan, [piecewise], x[:400], finalize=False, scan=True)
    run.partial_fit(x[400:]).finalize()
    assert piecewise.count_ == 800
    np.testing.assert_allclose(np.asarray(piecewise.mean_),
                               np.asarray(whole.mean_), atol=1e-5)


def test_fit_many_scan_validation():
    """scan=True rejects consumers whose folds can't run inside lax.scan
    (retained sketches / shard_map reductions) and source-driven ingest."""
    x = _lowrank(n=400, p=64)
    plan = _plan(backend="stream", batch_size=100)
    with pytest.raises(ValueError, match="lax.scan"):
        fit_many(plan, [SparsifiedKMeans(3, plan, key=1)], x, scan=True)  # lloyd
    batch = _plan(backend="batch", batch_size=100)
    with pytest.raises(ValueError, match="lax.scan"):
        fit_many(batch, [SparsifiedCov(batch, key=1)], x, scan=True)
    with pytest.raises(ValueError, match="scan=True"):
        fit_many(plan, [SparsifiedMean(plan, key=1)],
                 source=lambda s, t, sh: x[:100], steps=2, seed=0, scan=True)
