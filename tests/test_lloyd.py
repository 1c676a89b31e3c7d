"""Alg. 1's Lloyd over a retained sketch, against the plain gather/scatter forms.

The passes (``kernels.sparse_assign`` in interpret mode, and the jnp oracles
the CPU runs) on seeded random sketches at small n, K and p: the
assignment's argmin and minimum (n and K off the tiles, exact and near
ties), the Eq. 39 sums and counts, the K-means++ seed rows under one key, a
whole ``SparsifiedKMeans(algorithm="lloyd")`` fit against a plain Lloyd
written here, the retained sketch's one buffer, the finalize's spans and
counters, and ``sharded_kmeans`` on four host devices.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Plan, SparsifiedKMeans
from repro.core import kmeans as km
from repro.kernels import ops, ref
from repro.kernels import sparse_assign as sa

KEY = jax.random.PRNGKey(0)
TILE = sa.ROW_TILE


def sketch_t(n, p, m, seed=0):
    """(values_t, indices_t) (m, n) of n random rows, m distinct sorted
    coordinates of p each, and the same padded to whole row tiles."""
    kv, ki = jax.random.split(jax.random.PRNGKey(seed))
    vals = jax.random.normal(kv, (n, m))
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(ki, (n, p)), m)[1].astype(jnp.int32), -1)
    return vals.T, idx.T, km.rows_transposed(vals, idx)


def gather_dists(vt, it, centers):
    """(n, K) ‖z_i − R_iᵀμ_k‖² by the plain gather, in float64."""
    v, i, c = (np.asarray(a, np.float64) for a in (vt, it, centers))
    return np.sum((v[..., None] - c.T[i.astype(np.int64)]) ** 2, axis=0)


@pytest.mark.parametrize("shape", [(700, 64, 5, 3, 2), (1024, 128, 9, 300, 3),
                                   (512, 256, 13, 7, 1), (90, 64, 4, 1, 2)])
def test_assign_matches_gather(shape):
    """argmin and minimum of every row and group, n and K off the tiles."""
    n, p, m, k, g = shape
    vt, it, (vt_p, it_p) = sketch_t(n, p, m, seed=n + k)
    ctr = jax.random.normal(jax.random.PRNGKey(k), (g, k, p))
    lab, mins = ops.kmeans_assign(vt_p, it_p, ctr, mode="interpret")
    lab_r, mins_r = ref.ref_kmeans_assign(vt, it, ctr)
    np.testing.assert_array_equal(np.asarray(lab)[:, :n], np.asarray(lab_r))
    for h in range(g):
        d = gather_dists(vt, it, ctr[h])
        np.testing.assert_array_equal(np.asarray(lab_r)[h], np.argmin(d, axis=1))
        np.testing.assert_allclose(np.asarray(mins)[h, :n], d.min(axis=1), rtol=1e-5, atol=1e-4)


def test_assign_ties_keep_the_lowest_index():
    """A center repeated in a later K tile, and one a hair away: the exact
    tie goes to the lower index across tiles, the near tie to the nearer."""
    n, p, m, k = 512, 64, 6, 300
    vt, it, (vt_p, it_p) = sketch_t(n, p, m, seed=3)
    zero = jnp.zeros((p,))
    ctr = (jax.random.normal(KEY, (k, p)) * 5.0).at[10].set(zero).at[290].set(zero)
    ctr = ctr.at[40].set(zero + 1e-3)
    lab, _ = ops.kmeans_assign(vt_p, it_p, ctr[None], mode="interpret")
    lab = np.asarray(lab)[0, :n]
    np.testing.assert_array_equal(lab, np.argmin(gather_dists(vt, it, ctr), axis=1))
    assert 10 in lab and 40 in lab and 290 not in lab


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_update_matches_scatter(mode):
    """Per-cluster sums and per-coordinate counts; a label of -1 adds nothing."""
    n, p, m, k, g = 600, 128, 7, 9, 2
    vt, it, (vt_p, it_p) = sketch_t(n, p, m, seed=5)
    labels = jax.random.randint(KEY, (g, vt_p.shape[1]), -1, k)
    labels = jnp.where(jnp.arange(vt_p.shape[1]) < n, labels, -1)
    sums, counts = ops.kmeans_update(vt_p, it_p, labels, k, p, mode=mode)
    v, i, lab = np.asarray(vt, np.float64), np.asarray(it), np.asarray(labels)[:, :n]
    for h in range(g):
        want_s, want_c = np.zeros((k + 1, p)), np.zeros((k + 1, p))
        rows = np.broadcast_to(np.where(lab[h] < 0, k, lab[h]), i.shape)
        np.add.at(want_s, (rows, i), v)
        np.add.at(want_c, (rows, i), 1.0)
        np.testing.assert_allclose(np.asarray(sums)[h], want_s[:k], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(counts)[h], want_c[:k])


def test_step_keeps_unsampled_coordinates():
    """Eq. 39: a coordinate no row of a cluster kept keeps its value; the
    others move to the mean of the kept values."""
    n, p, m, k = 300, 64, 3, 4
    vt, it, (vt_p, it_p) = sketch_t(n, p, m, seed=7)
    mu = jax.random.normal(KEY, (1, k, p))
    st = km.LloydState(mu, mu, jnp.full((1,), jnp.inf), jnp.zeros((1,), jnp.int32))
    out = km.lloyd_step(vt_p, it_p, st, jnp.float32(0.0), n=n, max_iter=5, impl="interpret",
                        mesh=None, axes=None)
    lab = np.argmin(gather_dists(vt, it, mu[0]), axis=1)
    v, i = np.asarray(vt, np.float64), np.asarray(it)
    for c in range(k):
        kept = np.zeros(p, bool)
        for j in np.flatnonzero(lab == c):
            kept[i[:, j]] = True
        got, old = np.asarray(out.centers)[0, c], np.asarray(mu)[0, c]
        np.testing.assert_array_equal(got[~kept], old[~kept])
        for coord in np.flatnonzero(kept):
            sel = (i == coord) & (lab == c)[None, :]
            np.testing.assert_allclose(got[coord], v[sel].mean(), rtol=1e-5, atol=1e-6)
    assert int(out.n_iter[0]) == 1
    np.testing.assert_array_equal(np.asarray(out.prev), np.asarray(mu))


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_seed_rows_match_plain_kmeans_pp(impl):
    """Under one key the seeding picks the rows the plain gather-form
    K-means++ (``kpp_init_sparse``) picks, hypothesis by hypothesis, and
    each center is its seed row densified."""
    n, p, m, k, r = 400, 64, 8, 6, 3
    vt, it, (vt_p, it_p) = sketch_t(n, p, m, seed=11)
    keys = jax.random.split(KEY, r)
    centers, seeds = km.kmeans_seed(vt_p, it_p, keys, n=n, k=k, p=p, impl=impl, mesh=None,
                                    axes=None)
    dense = np.zeros((n, p))
    np.put_along_axis(dense, np.asarray(it).T, np.asarray(vt).T, axis=1)
    for h in range(r):
        plain = km.kpp_init_sparse(keys[h], vt.T, it.T, p, k)
        np.testing.assert_allclose(np.asarray(centers)[h], np.asarray(plain), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(centers)[h], dense[np.asarray(seeds)[h]])
    assert len(set(np.asarray(seeds)[0].tolist())) == k


def plain_lloyd(values, indices, p, k, key, n_init, max_iter, tol):
    """Alg. 1 written out with the gather assignment and scatter-add update."""
    best = None
    for rkey in jax.random.split(key, n_init):
        mu = np.asarray(km.kpp_init_sparse(rkey, values, indices, p, k), np.float64)
        v, i = np.asarray(values, np.float64), np.asarray(indices)
        it, shift = 0, np.inf
        while it < max_iter and shift > tol:
            a = np.argmin(gather_dists(v.T, i.T, mu), axis=1)
            sums, cnt = np.zeros((k, p)), np.zeros((k, p))
            np.add.at(sums, (np.repeat(a, i.shape[1]), i.ravel()), v.ravel())
            np.add.at(cnt, (np.repeat(a, i.shape[1]), i.ravel()), 1.0)
            new = np.where(cnt > 0, sums / np.maximum(cnt, 1), mu)
            shift, mu, it = np.max(np.abs(new - mu)), new, it + 1
        d = gather_dists(v.T, i.T, mu)
        fit = (d.min(axis=1).sum(), mu, np.argmin(d, axis=1), it)
        if best is None or fit[0] < best[0]:
            best = fit
    return best


@pytest.mark.parametrize("impl", ["auto", "interpret"])
def test_lloyd_fit_matches_plain(impl):
    """A whole fit through the estimator equals the plain Alg. 1 on the
    retained sketch: labels, centers, objective and iterations."""
    from tests.conftest import make_clusters

    x, _, _ = make_clusters(KEY, n=1200, p=64, k=4)
    plan = Plan(backend="stream", gamma=0.25, batch_size=256, impl=impl)
    est = SparsifiedKMeans(4, plan, key=3, n_init=2, max_iter=6, tol=0.0).fit(x)
    s = est._reducer.retained.rows()
    from repro.utils.prng import fold_in_str

    obj, mu, lab, it = plain_lloyd(s.values, s.indices, s.p, 4,
                                   fold_in_str(est.spec_.key, "api-kmeans"), 2, 6, 0.0)
    np.testing.assert_array_equal(est.labels_, lab)
    np.testing.assert_allclose(np.asarray(est.centers_pre_), mu, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(est.objective_), obj, rtol=1e-5)
    assert est.n_iter_ == it
    assert est.seed_rows_.shape == (2, 4)


def test_retained_sketch_is_one_buffer():
    """The retained rows live in one (m, capacity) buffer pair that doubles,
    in whole row tiles, with the columns past the rows left zero."""
    x = np.asarray(jax.random.normal(KEY, (5 * 300, 32)))
    est = SparsifiedKMeans(3, Plan(backend="stream", gamma=0.25, batch_size=300), key=1)
    caps = []
    for b in range(5):
        est.partial_fit(x[b * 300:(b + 1) * 300])
        caps.append(est._reducer.retained.capacity)
    r = est._reducer.retained
    assert caps == [TILE, TILE * 2, TILE * 2, TILE * 4, TILE * 4] and r.n == 1500
    m = r.values_t.shape[0]
    assert r.values_t.shape == r.indices_t.shape == (m, r.capacity)
    np.testing.assert_array_equal(np.asarray(r.values_t)[:, 1500:], 0.0)
    rows = r.rows()
    np.testing.assert_array_equal(np.asarray(rows.values), np.asarray(r.values_t)[:, :1500].T)
    assert rows.indices.shape == (1500, m)


def test_finalize_spans_and_counters(tmp_path):
    """The finalize's spans nest under ``finalize.kmeans`` (seed, lloyd, and
    one labels readback), and the counters count the iterations and the
    retained rows and bytes."""
    from repro import obs
    from tests.test_obs import _host_spans

    x = np.asarray(jax.random.normal(KEY, (600, 32)))
    plan = Plan(backend="stream", gamma=0.25, batch_size=200)
    SparsifiedKMeans(3, plan, key=1, max_iter=4).fit(x)          # compiles
    reg = obs.default_registry()
    before = reg.counter("kmeans.lloyd.iterations").value
    jax.profiler.start_trace(str(tmp_path))
    try:
        est = SparsifiedKMeans(3, plan, key=1, max_iter=4).fit(x)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, "finalize.kmeans")
    (outer,) = [h for h in spans if h[0] == "finalize.kmeans"]
    inner = {h[0]: h for h in spans if h[0] != "finalize.kmeans"}
    assert set(inner) == {"finalize.kmeans.seed", "finalize.kmeans.lloyd",
                          "finalize.kmeans.readback"}
    assert all(outer[1] <= h[1] and h[2] <= outer[2] for h in inner.values())
    assert inner["finalize.kmeans.readback"][3]["site"] == "labels"
    assert inner["finalize.kmeans.seed"][2] <= inner["finalize.kmeans.lloyd"][1]
    r = est._reducer.retained
    assert reg.counter("kmeans.lloyd.iterations").value - before == 4
    assert reg.gauge("kmeans.retained.rows").value == 600
    assert reg.gauge("kmeans.retained.bytes").value == r.capacity * r.values_t.shape[0] * 8


def test_sharded_kmeans_on_four_host_devices():
    """``sharded_kmeans`` spreads the retained rows over four host devices
    (each assigns its own, the update psums) and lands where the one-device
    solver does, on the oracles and on the kernels."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import kmeans as km, sketch
        from repro.launch.mesh import auto_mesh
        from repro.stream import sharded
        from tests.conftest import make_clusters

        mesh = auto_mesh((4,), ("data",))
        x, _, _ = make_clusters(jax.random.PRNGKey(0), n=1000, p=64, k=4)
        s = sketch.sketch(x, sketch.make_spec(64, jax.random.PRNGKey(3), gamma=0.25))
        for impl in ("ref", "interpret"):
            one = km.sparse_kmeans_core(s.values, s.indices, s.p, 4, jax.random.PRNGKey(4),
                                        n_init=2, max_iter=8, impl=impl)
            four = sharded.sharded_kmeans(s, 4, jax.random.PRNGKey(4), mesh, n_init=2,
                                          max_iter=8, impl=impl)
            assert four[1].shape == (1000,)
            np.testing.assert_array_equal(np.asarray(one[1]), np.asarray(four[1]))
            np.testing.assert_allclose(np.asarray(one[0]), np.asarray(four[0]), atol=1e-5)
            np.testing.assert_allclose(float(one[2]), float(four[2]), rtol=1e-5)
            assert int(one[3]) == int(four[3])
        print("OK")
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"src{os.pathsep}.", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]
