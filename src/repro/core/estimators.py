"""Unbiased estimators recovered from sparsified data (paper §IV–V).

Mean (Thm 4):      x̄̂ = (p/m)·(1/n) Σ_i R_iR_iᵀ x_i
Covariance (Thm 6): Ĉ_emp = p(p−1)/(m(m−1))·(1/n) Σ_i w_i w_iᵀ,
                   Ĉ_n = Ĉ_emp − (p−m)/(p−1)·diag(Ĉ_emp)   (unbiased)

Both have a *streaming* form (constant-memory accumulators, one pass) and a
*batch* form. The batch covariance offers two equivalent computation paths:

- ``dense``: scatter to (n, p) then one MXU matmul WᵀW — the right choice on TPU
  for n·p activations that fit;
- ``compact``: scatter n·m² outer-product entries — the right choice when γ ≪ 1
  and p is large (CPU / host aggregation).

Estimates live in the *preconditioned* domain when the data was sketched with a
ROS; PCA consumers either unmix eigenvectors (U = (HD)ᵀ Û) or work directly in
the preconditioned domain (the spectrum is unchanged — HD is orthonormal).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.sampling import SparseRows


# ---------------------------------------------------------------- mean ------

def mean_estimator(s: SparseRows) -> jax.Array:
    """Unbiased estimate of the sample mean (length p), Thm 4."""
    n, m = s.values.shape
    acc = jnp.zeros((s.p,), jnp.promote_types(s.values.dtype, jnp.float32))
    acc = acc.at[s.indices.reshape(-1)].add(s.values.reshape(-1).astype(acc.dtype))
    return acc * (s.p / (m * n))


# ---------------------------------------------------------- covariance ------

def _cov_scale(p: int, m: int) -> float:
    if m < 2:
        raise ValueError("covariance estimator needs m >= 2 (Thm B4, Eq. 50)")
    return (p * (p - 1)) / (m * (m - 1))


def _debias(c_emp_hat: jax.Array, p: int, m: int) -> jax.Array:
    corr = (p - m) / (p - 1)
    d = jnp.diagonal(c_emp_hat)
    return c_emp_hat - corr * jnp.diag(d)


def _scatter_outer(values: jax.Array, indices: jax.Array, p: int) -> jax.Array:
    """Σ_i w_i w_iᵀ via n·m² outer-product scatter-adds — the compact path's
    (p, p) accumulation with no dense (n, p) intermediate."""
    v = values.astype(jnp.float32)
    outer = v[:, :, None] * v[:, None, :]                     # (n, m, m)
    rows = jnp.broadcast_to(indices[:, :, None], outer.shape)
    cols = jnp.broadcast_to(indices[:, None, :], outer.shape)
    return jnp.zeros((p, p), jnp.float32).at[
        rows.reshape(-1), cols.reshape(-1)].add(outer.reshape(-1))


@functools.partial(jax.jit, static_argnames=("path",))
def cov_estimator(s: SparseRows, path: Literal["dense", "compact"] = "dense") -> jax.Array:
    """Unbiased estimate Ĉ_n (p×p) of the empirical covariance (1/n)·XᵀX, Thm 6."""
    n, m = s.values.shape
    scale = _cov_scale(s.p, m)
    if path == "dense":
        w = s.to_dense().astype(jnp.float32)
        c_emp_hat = scale / n * (w.T @ w)
    else:
        c_emp_hat = scale / n * _scatter_outer(s.values, s.indices, s.p)
    return _debias(c_emp_hat, s.p, m)


# ----------------------------------------------------------- streaming ------
# The moment accumulator's delta/apply algebra. Every streaming driver folds
# it a step at a time through repro.stream.step (one delta per shard sketch,
# summed or psum'd, applied once); stream_update is that fold for one sketch.

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StreamState:
    """Constant-memory accumulators for one-pass mean+covariance estimation.

    sum_w:    (p,)   Σ R_iR_iᵀ x_i
    sum_wwt:  (p, p) Σ w_i w_iᵀ       (only if track_cov)
    count:    scalar n so far — int32, exact to 2^31 rows (f32 would silently
              stop counting past 2^24 on the long streams the engine targets)
    """

    sum_w: jax.Array
    sum_wwt: jax.Array | None
    count: jax.Array

    def tree_flatten(self):
        return (self.sum_w, self.sum_wwt, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def stream_init(p: int, track_cov: bool = True) -> StreamState:
    return StreamState(
        sum_w=jnp.zeros((p,), jnp.float32),
        sum_wwt=jnp.zeros((p, p), jnp.float32) if track_cov else None,
        count=jnp.zeros((), jnp.int32),
    )


def stream_delta(batch: SparseRows, track_cov: bool = True,
                 cov_path: Literal["dense", "compact"] = "dense") -> StreamState:
    """One batch's contribution as a StreamState — local, no collectives, so a
    distributed caller can psum it before :func:`stream_apply`.

    ``cov_path="compact"`` scatters the n·m² outer products straight into the
    (p, p) accumulator instead of materializing the dense (n, p) scatter of the
    batch first — the right choice when γ ≪ 1 and p is large, where the n·p
    intermediate (not the accumulator) dominates the step's memory.
    """
    n = batch.values.shape[0]
    sum_w = jnp.zeros((batch.p,), jnp.float32).at[batch.indices.reshape(-1)].add(
        batch.values.reshape(-1).astype(jnp.float32)
    )
    sum_wwt = None
    if track_cov:
        if cov_path == "compact":
            sum_wwt = _scatter_outer(batch.values, batch.indices, batch.p)
        else:
            w = batch.to_dense().astype(jnp.float32)
            sum_wwt = w.T @ w
    return StreamState(sum_w, sum_wwt, jnp.int32(n))


def stream_apply(state: StreamState, delta: StreamState) -> StreamState:
    """Fold a (possibly psum'd) delta into the accumulator."""
    sum_wwt = state.sum_wwt
    if sum_wwt is not None:
        sum_wwt = sum_wwt + delta.sum_wwt
    return StreamState(state.sum_w + delta.sum_w, sum_wwt, state.count + delta.count)


@functools.partial(jax.jit, static_argnames=("cov_path",))
def stream_update(state: StreamState, batch: SparseRows,
                  cov_path: Literal["dense", "compact"] = "dense") -> StreamState:
    """Fold one sketched batch into the accumulators (pure; jit/scan friendly)."""
    return stream_apply(state, stream_delta(batch, track_cov=state.sum_wwt is not None,
                                            cov_path=cov_path))


def stream_finalize_mean(state: StreamState, m: int) -> jax.Array:
    p = state.sum_w.shape[0]
    # p/m first: keeps the divisor float (m·count could overflow int32)
    return state.sum_w * (p / m / state.count)


def stream_finalize_cov(state: StreamState, m: int) -> jax.Array:
    p = state.sum_w.shape[0]
    c_emp_hat = _cov_scale(p, m) / state.count * state.sum_wwt
    return _debias(c_emp_hat, p, m)


# ------------------------------------------------- reference quantities -----

def empirical_mean(x: jax.Array) -> jax.Array:
    return jnp.mean(x.astype(jnp.float32), axis=0)


def empirical_cov(x: jax.Array) -> jax.Array:
    """(1/n)·XᵀX — the paper's C_emp (uncentered second moment), rows=samples."""
    x = x.astype(jnp.float32)
    return x.T @ x / x.shape[0]
