"""Constant-memory accumulators for the one-pass streaming estimators.

The paper's single-pass story (§I, §IV–V) reduces every workload to the same
shape: fold a sketched batch into a fixed-size accumulator, then finalize.
This module holds the accumulator algebra — pure, jit/scan/shard_map friendly,
and split into

    delta(batch)  →  local, embarrassingly parallel (no collectives), then
    apply(state, delta)  →  the only state mutation,

so a step's deltas can be summed (psum'd under a mesh: the fixed-size
cross-shard traffic) and applied once; :mod:`repro.stream.step` is where that
step rule is written. Streaming-equals-batch (tests/test_stream.py) holds
because finalize uses exactly the Thm-4 / Thm-6 formulas of
repro.core.estimators.

Three accumulators:

- :class:`MomentState` — Σ R_iR_iᵀx_i (p,) and Σ w_iw_iᵀ (p,p) for the Thm-4
  mean and Thm-6 covariance estimators;
- :class:`KMeansState` — mini-batch streaming sparsified K-means: per-cluster,
  per-coordinate running means in the *preconditioned* domain (the online form
  of the paper's Eq. 39 update), with ``r`` independent center hypotheses
  folded in parallel and the best kept at finalize.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import estimators as _est
from repro.core.kmeans import kpp_init_sparse, sparse_sq_dists
from repro.core.sampling import SparseRows

# ------------------------------------------------------------- moments ------
# The moment accumulator IS estimators.StreamState — one source of truth for
# the Thm-4/Thm-6 algebra; this module only re-exports it under the engine's
# delta/apply naming and adds the K-means accumulator below.

MomentState = _est.StreamState
moment_init = _est.stream_init
moment_delta = _est.stream_delta
moment_apply = _est.stream_apply
moment_finalize_mean = _est.stream_finalize_mean
moment_finalize_cov = _est.stream_finalize_cov


# -------------------------------------------- mini-batch streaming K-means --


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KMeansState:
    """r parallel center hypotheses in the preconditioned domain.

    centers: (r, K, p) — per-cluster, per-coordinate running means;
    counts:  (r, K, p) — per-coordinate observation counts (Eq. 39 weights);
                         int32: the running-mean weights must stay exact —
                         f32 would saturate at 2^24 and silently turn the
                         mean update into a fixed-rate EMA. With a decay
                         (forgetting) factor they ARE float32: decay bounds
                         the counts by b·n_shards/(1−decay), far below the
                         2^24 saturation point, so exactness survives;
    obj:     (r,)      — accumulated mini-batch objective (hypothesis selector);
    count:   ()        — samples folded so far (int32, exact to 2^31 rows).
    """

    centers: jax.Array
    counts: jax.Array
    obj: jax.Array
    count: jax.Array

    def tree_flatten(self):
        return (self.centers, self.counts, self.obj, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def kmeans_init(key: jax.Array, first_batch: SparseRows, k: int, n_init: int = 3,
                decay: float = 1.0) -> KMeansState:
    """Seed r = n_init hypotheses with K-means++ on the first sketched batch.

    Runs on replicated data so sharded and single-device engines start from
    bit-identical centers. ``decay`` < 1 switches the count accumulators to
    float32 (see :class:`KMeansState`); pass the same value to
    :func:`kmeans_apply`.
    """

    def one(rkey):
        return kpp_init_sparse(rkey, first_batch.values, first_batch.indices,
                               first_batch.p, k)

    centers = jax.lax.map(one, jax.random.split(key, n_init))
    return KMeansState(
        centers=centers.astype(jnp.float32),
        counts=jnp.zeros(centers.shape, jnp.int32 if decay == 1.0 else jnp.float32),
        obj=jnp.zeros((n_init,), jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def kmeans_delta_with_assign(state: KMeansState, batch: SparseRows, mask=None):
    """(delta, assign) for one batch under every hypothesis.

    ``assign`` (r, n) int32 are the nearest-center labels under the
    step-start centers — already computed inside the delta, returned for
    callers that also track reassignment counts (so the convergence signal
    costs ONE extra assignment pass after the apply, not a recomputation of
    this one).

    ``mask`` (n,) marks the real rows of a zero-padded batch. Pad rows are
    real points at the origin to the scatter adds (unlike the linear moment
    deltas), so the mask zeroes their values, counts and objective terms.
    """
    values, indices = batch.values, batch.indices
    k, p = state.centers.shape[1:]

    def one(centers):
        d = sparse_sq_dists(values, indices, centers)          # (n, K)
        a = jnp.argmin(d, axis=1)
        rows = jnp.broadcast_to(a[:, None], indices.shape)
        w, hits = values.astype(jnp.float32), 1
        if mask is not None:
            w = w * mask.astype(jnp.float32)[:, None]
            hits = jnp.broadcast_to(mask.astype(jnp.int32)[:, None], indices.shape)
        sums = jnp.zeros((k, p), jnp.float32).at[rows, indices].add(w)
        cnts = jnp.zeros((k, p), jnp.int32).at[rows, indices].add(hits)
        dmin = jnp.min(d, axis=1)
        if mask is not None:
            dmin = dmin * mask.astype(jnp.float32)
        return sums, cnts, jnp.sum(dmin).astype(jnp.float32), a.astype(jnp.int32)

    sums, cnts, obj, assign = jax.vmap(one)(state.centers)
    n = (jnp.int32(values.shape[0]) if mask is None
         else jnp.sum(mask).astype(jnp.int32))
    return (sums, cnts, obj, n), assign


def kmeans_delta(state: KMeansState, batch: SparseRows):
    """Assignment + scatter sums for one batch under every hypothesis.

    Assignment (the hot, O(n·m·K) step) stays local to the shard; only the
    returned (sums, cnts, obj, n) — fixed-size in the batch — ever needs a psum
    (the per-row labels of :func:`kmeans_delta_with_assign` are dead code here,
    eliminated under jit).
    """
    delta, _ = kmeans_delta_with_assign(state, batch)
    return delta


def kmeans_apply(state: KMeansState, delta, decay: float = 1.0) -> KMeansState:
    """Online per-coordinate mean update — the streaming form of Eq. 39.

    new_center = (count·center + batch_sum) / (count + batch_count) wherever the
    batch touched the coordinate; untouched coordinates keep their value (the
    paper's never-sampled-coordinate convention).

    ``decay`` < 1 is the forgetting factor for non-stationary streams: the
    accumulated counts shrink BEFORE the delta is applied, so older
    observations are geometrically down-weighted (effective memory
    ≈ 1/(1−decay) steps) and the centers can track drifting clusters. The
    state must have been built with ``kmeans_init(..., decay=...)`` (float
    counts). Decay is applied once per psum'd step — the same place the delta
    is — so sharded and single-device streams stay identical.
    """
    sums, cnts, obj, n = delta
    old_counts = state.counts if decay == 1.0 else state.counts * decay
    new_counts = old_counts + cnts.astype(state.counts.dtype)
    cnts_f = cnts.astype(jnp.float32)
    centers = jnp.where(
        cnts > 0,
        state.centers + (sums - cnts_f * state.centers)
        / jnp.maximum(new_counts, 1).astype(jnp.float32),
        state.centers,
    )
    return KMeansState(centers, new_counts, state.obj + obj, state.count + n)


def kmeans_reassigned(state: KMeansState, batch: SparseRows,
                      prev_assign: jax.Array, mask=None) -> jax.Array:
    """(r,) int32 — how many of the batch's rows change nearest center across
    one apply: labels under ``state.centers`` (post-update) vs ``prev_assign``
    (the labels :func:`kmeans_delta_with_assign` computed pre-update).

    The mini-batch convergence signal (ROADMAP streaming-K-means item): as the
    per-coordinate means settle, the count decays toward zero; a persistently
    high count means the stream is still reshaping the solution (or drifting,
    under a decay factor). ``mask`` (n,) leaves pad rows uncounted.
    """

    def one(c_new, a_prev):
        a1 = jnp.argmin(sparse_sq_dists(batch.values, batch.indices, c_new), axis=1)
        moved = a1.astype(jnp.int32) != a_prev
        if mask is not None:
            moved = moved * mask.astype(jnp.int32)
        return jnp.sum(moved).astype(jnp.int32)

    return jax.vmap(one)(state.centers, prev_assign)


def kmeans_finalize(state: KMeansState):
    """(best centers (K, p) in the preconditioned domain, best accumulated obj)."""
    best = jnp.argmin(state.obj)
    return state.centers[best], state.obj[best]


def kmeans_assign(centers_pre: jax.Array, batch: SparseRows) -> jax.Array:
    """Nearest-center labels for sketched rows under the sparsified metric."""
    d = sparse_sq_dists(batch.values, batch.indices, centers_pre)
    return jnp.argmin(d, axis=1).astype(jnp.int32)
