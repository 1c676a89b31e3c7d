"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret=True on
CPU, compiled on TPU) across shape/dtype sweeps — see tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ros


def ref_hd_precondition(x: jax.Array, signs: jax.Array) -> jax.Array:
    """y = H·(d ⊙ x) along the last axis — oracle for kernels.fwht.

    ``x``: (n, p) with p a power of two; ``signs``: (p,) of ±1.
    """
    return ros.fwht(x * signs[None, :])


def ref_kmeans_assign(values_t, indices_t, centers):
    """Oracle of kernels.sparse_assign.assign: for centers (G, K, p), every
    row's nearest center per group and its distance ‖z_i − R_iᵀμ_k‖²
    (paper Eq. 36), by the plain gather. values_t, indices_t are (m, n)."""

    def one(c):
        d = jnp.sum((values_t[..., None] - c.T[indices_t]) ** 2, axis=0)   # (n, K)
        return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)

    return jax.lax.map(one, centers)


def ref_kmeans_update(values_t, indices_t, labels, k: int, p: int):
    """Oracle of kernels.sparse_assign.update: Eq. 39's per-cluster sums and
    per-coordinate counts by the plain scatter-add; a negative label adds
    nothing."""
    m, n = values_t.shape

    def one(lab):
        rows = jnp.broadcast_to(jnp.where(lab < 0, k, lab)[None, :], (m, n))
        sums = jnp.zeros((k + 1, p), jnp.float32).at[rows, indices_t].add(values_t)
        counts = jnp.zeros((k + 1, p), jnp.float32).at[rows, indices_t].add(1.0)
        return sums[:k], counts[:k]

    return jax.lax.map(one, labels)


def ref_kmeans_dists(values_t, indices_t, centers):
    """Oracle of kernels.sparse_assign.dists: (C, n) distances to ``centers``
    (C, p)."""
    return jnp.sum((values_t[None] - centers[:, indices_t]) ** 2, axis=1)


def _spmm_out_dtype(a, b) -> jnp.dtype:
    """The shared spmm promotion rule: operands promote jointly, accumulation
    and output are at least f32 (kernels.spmm.promoted_dtypes agrees)."""
    return jnp.promote_types(jnp.promote_types(a, b), jnp.float32)


def ref_spmm(values: jax.Array, indices: jax.Array, dense: jax.Array) -> jax.Array:
    """T (n, l) = W @ dense — oracle for kernels.spmm.spmm.

    values/indices (n, m) compact sparse rows over p columns; dense (p, l).
    """
    out = _spmm_out_dtype(values.dtype, dense.dtype)
    return jnp.einsum("nm,nml->nl", values.astype(out), dense.astype(out)[indices])


def ref_spmm_t(values: jax.Array, indices: jax.Array, t: jax.Array, p: int) -> jax.Array:
    """Y (p, l) = Wᵀ @ t — oracle for kernels.spmm.spmm_t (scatter-add rows)."""
    out = _spmm_out_dtype(values.dtype, t.dtype)
    contrib = values.astype(out)[..., None] * t.astype(out)[:, None, :]
    return jnp.zeros((p, t.shape[1]), out).at[
        indices.reshape(-1)].add(contrib.reshape(-1, t.shape[1]))


def ref_sketch_fused(x: jax.Array, signs: jax.Array, indices: jax.Array) -> jax.Array:
    """values (n, m) = (H·(signs⊙x))[i, indices[i]] — oracle for
    kernels.sketch_fused (the composed precondition → gather it fuses away)."""
    return jnp.take_along_axis(ref_hd_precondition(x, signs), indices, axis=-1)
