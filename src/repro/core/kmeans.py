"""Sparsified K-means (paper §VI, Algs. 1–2) plus the comparison baselines of §VII.

All cluster solvers share the same shape conventions:
  data rows = samples; centers (K, p); assignments (n,) int32.

Solvers
-------
- :func:`kmeans`                    — standard Lloyd + K-means++ (the reference).
- :func:`sparsified_kmeans`         — Alg. 1: one pass (precondition→sample→cluster
                                      on the sparse matrix), optional Alg. 2 second pass.
- :func:`feature_extraction_kmeans` — Boutsidis et al. [36]: Z = XΩᵀ, Ω random signs.
- :func:`feature_selection_kmeans`  — [36]: leverage-score row (feature) sampling.

Alg. 1's Lloyd (:func:`lloyd`) runs over a retained sketch held transposed,
(m, n) values and indices, one row per column. Its three passes (K-means++
candidate distances, assignment, Eq. 39 update) go through
``repro.kernels.ops`` (``kmeans_dists``/``kmeans_assign``/``kmeans_update``:
the ``kernels.sparse_assign`` Pallas kernels on a TPU, the gather/scatter
oracles elsewhere). The streaming minibatch fold keeps the gather form
(:func:`sparse_sq_dists`, :func:`kpp_init_sparse`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ros, sketch
from repro.core.sampling import SparseRows, subsample


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KMeansResult:
    assignments: jax.Array          # (n,) int32
    centers: jax.Array              # (K, p) in the ORIGINAL domain
    objective: jax.Array            # final value of the solver's objective
    n_iter: jax.Array               # iterations of the final (best) run
    centers_pre: jax.Array | None = None  # (K, p_pad) preconditioned domain (sparsified only)

    def tree_flatten(self):
        return (self.assignments, self.centers, self.objective, self.n_iter, self.centers_pre), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# ------------------------------------------------------------ distances -----

def dense_sq_dists(x: jax.Array, centers: jax.Array) -> jax.Array:
    """(n, K) squared Euclidean distances."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)
    return x2 - 2.0 * x @ centers.T + c2[None, :]


def sparse_sq_dists(values: jax.Array, indices: jax.Array, centers: jax.Array) -> jax.Array:
    """(n, K) sparsified distances ‖z_i − R_iᵀ μ_k‖² (Eq. 35), gather reference.

    Only the sampled coordinates of each row participate — this is what realizes
    the γ = m/p flop reduction (O(nmK) instead of O(npK)).
    """
    g = centers.T[indices]                                   # (n, m, K)
    return jnp.sum((values[..., None] - g) ** 2, axis=1)


# ----------------------------------------------------------- K-means++ ------

def _kpp_init(key: jax.Array, dist_to_center: Callable[[int], jax.Array], n: int, k: int,
              gather_row: Callable[[jax.Array], jax.Array], p: int, dtype) -> jax.Array:
    """Greedy K-means++ D²-seeding (kmeans++ with ``n_cand`` trial centers per
    step, keeping the one that most reduces the potential — as in sklearn).

    dist_to_center(row_dense) -> (n,) squared distances of every sample to a
    candidate center given as a dense p-vector. gather_row(i) -> dense p-vector
    for sample i.
    """
    n_cand = 2 + int(np.ceil(np.log(max(k, 2))))
    k0, key = jax.random.split(key)
    first = gather_row(jax.random.randint(k0, (), 0, n))
    centers = jnp.zeros((k, p), dtype).at[0].set(first)
    min_d = dist_to_center(first)

    def body(j, carry):
        centers, min_d, key = carry
        key, kc = jax.random.split(key)
        # D² sampling of n_cand candidates (guard all-zero with the floor)
        logits = jnp.log(jnp.maximum(min_d, 1e-30))
        idxs = jax.random.categorical(kc, logits, shape=(n_cand,))
        cands = jax.vmap(gather_row)(idxs)                   # (n_cand, p)
        new_ds = jax.vmap(dist_to_center)(cands)             # (n_cand, n)
        pots = jnp.sum(jnp.minimum(min_d[None, :], new_ds), axis=1)
        best = jnp.argmin(pots)
        centers = centers.at[j].set(cands[best])
        min_d = jnp.minimum(min_d, new_ds[best])
        return centers, min_d, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers, min_d, key))
    return centers


def kpp_init_dense(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    n, p = x.shape

    def dist(c):
        return jnp.sum((x - c[None, :]) ** 2, axis=1)

    return _kpp_init(key, dist, n, k, lambda i: x[i], p, x.dtype)


def kpp_init_sparse(key: jax.Array, values: jax.Array, indices: jax.Array, p: int, k: int) -> jax.Array:
    """K-means++ under the sparsified metric: candidate centers are scattered
    sparse rows; distances use only each row's sampled coordinates (Eq. 35)."""
    n, m = values.shape

    def gather_row(i):
        return jnp.zeros((p,), values.dtype).at[indices[i]].set(values[i])

    def dist(c):
        g = c[indices]                                       # (n, m)
        return jnp.sum((values - g) ** 2, axis=1)

    return _kpp_init(key, dist, n, k, gather_row, p, values.dtype)


# ------------------------------------------------------------ Lloyd loops ---

def _lloyd_dense(x: jax.Array, mu0: jax.Array, max_iter: int, tol: float):
    n, p = x.shape
    k = mu0.shape[0]

    def cond(c):
        it, _, shift = c[0], c[1], c[2]
        return (it < max_iter) & (shift > tol)

    def body(c):
        it, mu, _ = c
        d = dense_sq_dists(x, mu)
        a = jnp.argmin(d, axis=1)
        oh = jax.nn.one_hot(a, k, dtype=x.dtype)             # (n, K)
        sums = oh.T @ x                                      # (K, p)
        counts = jnp.sum(oh, axis=0)
        new_mu = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], mu)
        shift = jnp.max(jnp.abs(new_mu - mu))
        return it + 1, new_mu, shift

    it, mu, _ = jax.lax.while_loop(cond, body, (jnp.zeros((), jnp.int32), mu0, jnp.full((), jnp.inf, x.dtype)))
    d = dense_sq_dists(x, mu)
    a = jnp.argmin(d, axis=1).astype(jnp.int32)
    obj = jnp.sum(jnp.min(d, axis=1))
    return mu, a, obj, it


# ------------------------------------------------------------- solvers ------

@functools.partial(jax.jit, static_argnames=("k", "n_init", "max_iter"))
def kmeans(x: jax.Array, k: int, key: jax.Array, n_init: int = 5,
           max_iter: int = 100, tol: float = 1e-6) -> KMeansResult:
    """Standard K-means (Lloyd) with K-means++ seeding, best of ``n_init`` runs."""

    def one_run(rkey):
        mu0 = kpp_init_dense(rkey, x, k)
        return _lloyd_dense(x, mu0, max_iter, tol)

    mus, assigns, objs, iters = jax.lax.map(one_run, jax.random.split(key, n_init))
    best = jnp.argmin(objs)
    return KMeansResult(assigns[best], mus[best], objs[best], iters[best])


def sparse_kmeans_core(values: jax.Array, indices: jax.Array, p: int, k: int, key: jax.Array,
                       n_init: int = 5, max_iter: int = 100, tol: float = 1e-6,
                       impl: str = "auto"):
    """Lloyd (Alg. 1) on an already-sketched (n, m) matrix; best of n_init.

    Returns (centers (K, p), labels (n,), objective, n_iter) of the best
    hypothesis."""
    vt, it = rows_transposed(values, indices)
    fit = lloyd(vt, it, values.shape[0], p, k, key, n_init=n_init, max_iter=max_iter,
                tol=tol, impl=impl)
    return fit.centers, fit.labels, fit.objective, fit.n_iter


def sparsified_kmeans(x: jax.Array, k: int, key: jax.Array, gamma: float | None = None,
                      m: int | None = None, transform: ros.Transform = "hadamard",
                      precondition: bool = True, two_pass: bool = False,
                      n_init: int = 5, max_iter: int = 100, tol: float = 1e-6,
                      impl: str = "auto") -> KMeansResult:
    """Alg. 1 (one-pass) / Alg. 2 (``two_pass=True``) sparsified K-means.

    ``precondition=False`` gives the paper's no-ROS ablation baseline.
    """
    n, p = x.shape
    spec = sketch.make_spec(p, key, gamma=gamma, m=m,
                            transform=transform if precondition else "dct")
    if precondition:
        s = sketch.sketch(x, spec)
        pp = spec.p_pad
    else:
        s = subsample(x, spec.mask_key(), spec.m)
        pp = p

    mu_pre, a, obj, it = sparse_kmeans_core(
        s.values, s.indices, pp, k, spec.signs_key(), n_init, max_iter, tol, impl
    )
    centers = sketch.unmix_dense(mu_pre, spec) if precondition else mu_pre

    if two_pass:
        # Alg. 2: one more pass over the ORIGINAL data — recompute centers as
        # true sample means of assigned points, and reassign in the original domain.
        oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
        sums = oh.T @ x.astype(jnp.float32)
        counts = jnp.sum(oh, axis=0)
        centers2 = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers)
        d = dense_sq_dists(x.astype(jnp.float32), centers)   # reassign w/ 1-pass centers
        a = jnp.argmin(d, axis=1).astype(jnp.int32)
        obj = jnp.sum(jnp.min(d, axis=1))
        centers = centers2

    return KMeansResult(a, centers, obj, it, centers_pre=mu_pre)


def feature_extraction_kmeans(x: jax.Array, k: int, m: int, key: jax.Array,
                              two_pass: bool = False, n_init: int = 5,
                              max_iter: int = 100, tol: float = 1e-6) -> KMeansResult:
    """Boutsidis et al. feature extraction: cluster Z = XΩᵀ/√m, Ω ∈ {±1}^{m×p}.

    One-pass center estimates use the pseudo-inverse lift Ω⁺ (the paper's Fig. 9
    shows these are poor — kept faithful); ``two_pass`` recomputes them from X.
    """
    n, p = x.shape
    komega, krun = jax.random.split(key)
    omega = jax.random.rademacher(komega, (m, p), dtype=jnp.float32) / np.sqrt(m)
    z = x.astype(jnp.float32) @ omega.T
    res = kmeans(z, k, krun, n_init=n_init, max_iter=max_iter, tol=tol)
    # lift centers with the pseudo-inverse (rank-m, inconsistent — see §VII-B)
    centers = res.centers @ jnp.linalg.pinv(omega).T
    a, obj = res.assignments, res.objective
    if two_pass:
        oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
        counts = jnp.sum(oh, axis=0)
        centers = jnp.where(counts[:, None] > 0,
                            (oh.T @ x.astype(jnp.float32)) / jnp.maximum(counts, 1.0)[:, None],
                            centers)
    return KMeansResult(a, centers, obj, res.n_iter)


def leverage_scores(x: jax.Array, rank: int, key: jax.Array, oversample: int = 10) -> jax.Array:
    """Approximate row (feature) leverage scores via a randomized range finder [7].

    Returns (p,) scores of Xᵀ's rows = feature importances for feature selection.
    """
    n, p = x.shape
    xt = x.astype(jnp.float32).T                             # (p, n) features-as-rows
    g = jax.random.normal(key, (n, rank + oversample), jnp.float32)
    ys = xt @ g                                              # (p, r+o)
    q, _ = jnp.linalg.qr(ys)                                 # (p, r+o) orthonormal
    scores = jnp.sum(q[:, :rank] ** 2, axis=1)
    return scores / jnp.sum(scores)


def feature_selection_kmeans(x: jax.Array, k: int, m: int, key: jax.Array,
                             two_pass: bool = False, n_init: int = 5,
                             max_iter: int = 100, tol: float = 1e-6) -> KMeansResult:
    """[36] feature selection: sample m features by leverage scores, cluster there.

    Requires ≥3 passes over the data (score pass, sampling pass, clustering) —
    included as the paper's multi-pass baseline.
    """
    n, p = x.shape
    kscore, ksel, krun = jax.random.split(key, 3)
    scores = leverage_scores(x, rank=k, key=kscore)
    sel = jax.random.choice(ksel, p, (m,), replace=False, p=scores)
    # rescale by 1/sqrt(m q_j) as in [36]
    z = x[:, sel].astype(jnp.float32) / jnp.sqrt(m * scores[sel])[None, :]
    res = kmeans(z, k, krun, n_init=n_init, max_iter=max_iter, tol=tol)
    centers = jnp.zeros((k, p), jnp.float32).at[:, sel].set(res.centers * jnp.sqrt(m * scores[sel])[None, :])
    a = res.assignments
    if two_pass:
        oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
        counts = jnp.sum(oh, axis=0)
        centers = jnp.where(counts[:, None] > 0,
                            (oh.T @ x.astype(jnp.float32)) / jnp.maximum(counts, 1.0)[:, None],
                            centers)
    return KMeansResult(a, centers, res.objective, res.n_iter)


# ------------------------------------------ Lloyd over a retained sketch ----
# Alg. 1 at scale: the sketch is held once, transposed ((m, n) values and
# indices, one row per column, n padded to the kernels' row tile), and every
# step is a pass over it. The n_init hypotheses share each pass. Three
# programs run on the device, named for the trace: ``kmeans_seed`` (all of
# K-means++), ``lloyd_step`` (one iteration, dispatched max_iter times with
# no read between them: a hypothesis that has stopped keeps its state, and a
# step in which every one has stopped does nothing) and ``lloyd_labels``
# (the labels and objective under the final centers, and the best
# hypothesis).


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LloydState:
    """Every hypothesis's Lloyd state: centers (r, K, p), the centers that
    entered its last iteration, the last iteration's largest move, and its
    iteration count."""

    centers: jax.Array
    prev: jax.Array
    shift: jax.Array
    n_iter: jax.Array

    def tree_flatten(self):
        return (self.centers, self.prev, self.shift, self.n_iter), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LloydFit:
    """The best hypothesis of a :func:`lloyd` run (device arrays): its
    centers, labels (n,), objective and iteration count, the centers that
    entered its last iteration (``prev_centers``), every hypothesis's seed
    rows (r, K) and the iterations the longest one ran (``steps``)."""

    centers: jax.Array
    labels: jax.Array
    objective: jax.Array
    n_iter: jax.Array
    prev_centers: jax.Array
    seed_rows: jax.Array
    steps: jax.Array

    def tree_flatten(self):
        return (self.centers, self.labels, self.objective, self.n_iter, self.prev_centers,
                self.seed_rows, self.steps), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def row_tile() -> int:
    """The row count a retained sketch's columns are padded to a multiple of."""
    from repro.kernels import sparse_assign

    return sparse_assign.ROW_TILE


def rows_transposed(values: jax.Array, indices: jax.Array, mesh=None, axes=None):
    """(values_t, indices_t) (m, n_pad): (n, m) compact rows transposed and
    padded to whole row tiles (on each device of ``mesh``'s ``axes``)."""
    n = values.shape[0]
    tile = row_tile() * (1 if mesh is None else _axis_size(mesh, axes))
    pad = -n % tile
    vt = jnp.pad(values.astype(jnp.float32).T, ((0, 0), (0, pad)))
    it = jnp.pad(indices.astype(jnp.int32).T, ((0, 0), (0, pad)))
    if mesh is not None:
        from jax.sharding import NamedSharding

        spec = NamedSharding(mesh, _row_spec(axes))
        vt, it = jax.device_put(vt, spec), jax.device_put(it, spec)
    return vt, it


def _axis_size(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def _row_spec(axes):
    from jax.sharding import PartitionSpec as P

    return P(None, axes if len(axes) > 1 else axes[0])


def _passes(impl: str, mesh, axes):
    """(assign, update, dists) over the retained sketch; with a mesh each
    runs on every device's columns, the update's sums and counts psum'd."""
    from repro.kernels import ops

    def assign(vt, it, centers):
        return ops.kmeans_assign(vt, it, centers, mode=impl)

    def update(vt, it, labels, k, p):
        return ops.kmeans_update(vt, it, labels, k, p, mode=impl)

    def dists(vt, it, centers):
        return ops.kmeans_dists(vt, it, centers, mode=impl)

    if mesh is None:
        return assign, update, dists
    from jax.sharding import PartitionSpec as P

    cols, rep = _row_spec(axes), P()
    names = tuple(axes)

    def sharded(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)

    def update_psum(vt, it, labels, k, p):
        def local(v, i, lab):
            return jax.tree.map(lambda a: jax.lax.psum(a, names), update(v, i, lab, k, p))

        return sharded(local, (cols, cols, cols), (rep, rep))(vt, it, labels)

    return (sharded(assign, (cols, cols, rep), (cols, cols)), update_psum,
            sharded(dists, (cols, cols, rep), cols))


def _rows_dense(values_t, indices_t, rows, p: int):
    """(*rows.shape, p): the retained rows ``rows`` scattered to dense
    vectors (their kept coordinates set, every other 0)."""
    v, ix = values_t[:, rows], indices_t[:, rows]
    hit = ix[..., None] == jnp.arange(p, dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, v[..., None], 0.0), axis=0)


_STATIC = ("n", "k", "p", "impl", "mesh", "axes")


@functools.partial(jax.jit, static_argnames=_STATIC)
def kmeans_seed(values_t, indices_t, keys, *, n, k, p, impl, mesh, axes):
    """K-means++ D² seeding (``_kpp_init``'s algorithm, with its candidates a
    step and its randomness) of every hypothesis at once, one key each:
    each step is one pass over the retained sketch against every
    hypothesis's candidates. Returns (centers (r, K, p), seed rows (r, K))."""
    _, _, dists = _passes(impl, mesh, axes)
    r = keys.shape[0]
    hyp = jnp.arange(r)
    n_cand = 2 + int(np.ceil(np.log(max(k, 2))))
    split = jax.vmap(jax.random.split)
    ks = split(keys)
    first = jax.vmap(lambda kk: jax.random.randint(kk, (), 0, n))(ks[:, 0])
    c0 = _rows_dense(values_t, indices_t, first, p)
    centers = jnp.zeros((r, k, p), jnp.float32).at[:, 0].set(c0)
    seeds = jnp.zeros((r, k), jnp.int32).at[:, 0].set(first)
    min_d = dists(values_t, indices_t, c0)[:, :n]

    def step(j, carry):
        centers, seeds, min_d, key = carry
        ks = split(key)
        logits = jnp.log(jnp.maximum(min_d, 1e-30))
        cand = jax.vmap(lambda kk, lg: jax.random.categorical(kk, lg, shape=(n_cand,)))(
            ks[:, 1], logits)                                # (r, n_cand)
        rows = _rows_dense(values_t, indices_t, cand, p)      # (r, n_cand, p)
        d = dists(values_t, indices_t, rows.reshape(r * n_cand, p))[:, :n]
        d = d.reshape(r, n_cand, n)
        best = jnp.argmin(jnp.sum(jnp.minimum(min_d[:, None], d), axis=2), axis=1)
        return (centers.at[:, j].set(rows[hyp, best]), seeds.at[:, j].set(cand[hyp, best]),
                jnp.minimum(min_d, d[hyp, best]), ks[:, 0])

    centers, seeds, _, _ = jax.lax.fori_loop(1, k, step, (centers, seeds, min_d, ks[:, 1]))
    return centers, seeds


@functools.partial(jax.jit, static_argnames=("n", "max_iter", "impl", "mesh", "axes"))
def lloyd_step(values_t, indices_t, state: LloydState, tol, *, n, max_iter, impl, mesh,
               axes) -> LloydState:
    """One Lloyd iteration of every hypothesis still running (fewer than
    max_iter iterations, and its last one moved a center by more than tol):
    Eq. 36's assignment, then Eq. 39's update, where a coordinate no row of
    a cluster kept keeps its value."""
    assign, update, _ = _passes(impl, mesh, axes)
    r, k, p = state.centers.shape
    active = (state.n_iter < max_iter) & (state.shift > tol)

    def run(st):
        labels, _ = assign(values_t, indices_t, st.centers)
        labels = jnp.where(jnp.arange(labels.shape[1]) < n, labels, -1)
        sums, counts = update(values_t, indices_t, labels, k, p)
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), st.centers)
        shift = jnp.max(jnp.abs(new - st.centers), axis=(1, 2))
        a = active[:, None, None]
        return LloydState(jnp.where(a, new, st.centers), jnp.where(a, st.centers, st.prev),
                          jnp.where(active, shift, st.shift), st.n_iter + active)

    return jax.lax.cond(jnp.any(active), run, lambda st: st, state)


@functools.partial(jax.jit, static_argnames=("n", "impl", "mesh", "axes"))
def lloyd_labels(values_t, indices_t, state: LloydState, seeds, *, n, impl, mesh,
                 axes) -> LloydFit:
    """Labels and objective of every hypothesis under its final centers,
    and the best hypothesis (least objective)."""
    assign, _, _ = _passes(impl, mesh, axes)
    labels, mins = assign(values_t, indices_t, state.centers)
    obj = jnp.sum(mins[:, :n], axis=1)
    b = jnp.argmin(obj)
    return LloydFit(state.centers[b], labels[b, :n], obj[b], state.n_iter[b], state.prev[b],
                    seeds, jnp.max(state.n_iter))


def lloyd(values_t, indices_t, n: int, p: int, k: int, key: jax.Array, *, n_init: int = 3,
          max_iter: int = 100, tol: float = 1e-6, impl: str = "auto", mesh=None,
          axes=None) -> LloydFit:
    """Alg. 1's clustering of n retained rows (the first n columns of
    ``values_t``/``indices_t``, (m, n_pad)): K-means++ seeding, then Lloyd,
    best of ``n_init``. Spans ``seed`` and ``lloyd`` cover the dispatch of
    each; nothing here waits for the device."""
    from repro import obs

    kw = dict(n=int(n), impl=impl, mesh=mesh, axes=None if axes is None else tuple(axes))
    with obs.span("seed"):
        centers, seeds = kmeans_seed(values_t, indices_t, jax.random.split(key, n_init),
                                     k=int(k), p=int(p), **kw)
    state = LloydState(centers, centers, jnp.full((n_init,), jnp.inf, jnp.float32),
                       jnp.zeros((n_init,), jnp.int32))
    with obs.span("lloyd"):
        for _ in range(max_iter):
            state = lloyd_step(values_t, indices_t, state, jnp.float32(tol),
                               max_iter=int(max_iter), **kw)
    return lloyd_labels(values_t, indices_t, state, seeds, **kw)


# -------------------------------------------------------------- metrics -----

def clustering_accuracy(pred: jax.Array, true: jax.Array, k: int) -> float:
    """Best-permutation label accuracy (Hungarian matching), as in §VII-B."""
    from scipy.optimize import linear_sum_assignment

    pred = np.asarray(pred)
    true = np.asarray(true)
    conf = np.zeros((k, k))
    for i in range(k):
        for j in range(k):
            conf[i, j] = np.sum((pred == i) & (true == j))
    ri, ci = linear_sum_assignment(-conf)
    return float(conf[ri, ci].sum() / len(true))
