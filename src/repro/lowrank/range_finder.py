"""Randomized range-finder / co-occurrence accumulator — the psum-able state.

The Thm-6 covariance needs S = Σ_i w_i w_iᵀ; this state never forms S, only its
action on a fixed (p, l) Gaussian test matrix Omega (:func:`repro.lowrank.model.omega`):

    y    = S · Omega                (p, l)   accumulated EXACTLY (linear in batches)
    diag = diag(S) = Σ_i w_i∘w_i    (p,)     exact, for the Thm-6 debias
    sum_w, count                             the Thm-4 mean accumulator

Each batch's delta is Wᵀ(W·Omega) — two sparse-times-dense products
(``kernels.ops.spmm`` / ``spmm_t``) that never densify the (b, p) batch. The
delta is fixed-size and additive, so it follows the exact ``init / delta /
apply / finalize`` algebra of ``stream.accumulators``: single-device engines
apply it directly, sharded engines psum it (the only cross-shard traffic is
O(p·l) per step), and streaming == batch holds to float-sum reordering.

Finalize (single-pass randomized eigendecomposition, three deliberate choices):

1. **Debias first, then range-find.** Element-wise sampling inflates diag(S)
   by the large (p−m)/(p−1) mask-noise floor that Thm 6 subtracts; a range
   found on raw Y chases those diagonal directions instead of the spectrum.
   Because diag(S) is carried exactly, the debiased operator's sketch is
   available in closed form: Y' = (S − corr·diag(d))·Omega = Y − corr·(d ∘ Omega).
2. **Oversampled, truncated basis.** The basis is the top r = l/2 left
   singular vectors of Y', not all l — Omega then oversamples the basis 2×, which
   is what makes step 3 well-posed (a square Gaussian solve is notoriously
   ill-conditioned and produces ghost eigenvalues).
3. **Fat least-squares core.** From S' ≈ Q(QᵀS'Q)Qᵀ follows
   (QᵀY') ≈ core·(QᵀOmega); the r×l system is solved by pseudo-inverse and
   symmetrized — the standard single-pass core estimate (Halko et al. §5.5,
   stabilized by the oversampling of step 2).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.estimators import _cov_scale, stream_finalize_mean
from repro.core.sampling import SparseRows
from repro.kernels import ops
from repro.lowrank.model import LowRankCov, eig_in_basis


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RangeState:
    """Constant-memory low-rank co-occurrence accumulators (all O(p·l)).

    y:     (p, l)  Σ w_i (w_iᵀ Omega) = S·Omega
    diag:  (p,)    Σ w_i ∘ w_i = diag(S)
    sum_w: (p,)    Σ w_i (Thm-4 mean numerator)
    count: ()      rows folded (int32 — exact, same rationale as MomentState)
    """

    y: jax.Array
    diag: jax.Array
    sum_w: jax.Array
    count: jax.Array

    def tree_flatten(self):
        return (self.y, self.diag, self.sum_w, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def nbytes(self) -> int:
        return sum(a.size * a.dtype.itemsize
                   for a in (self.y, self.diag, self.sum_w, self.count))


def range_init(p: int, ell: int) -> RangeState:
    return RangeState(
        y=jnp.zeros((p, ell), jnp.float32),
        diag=jnp.zeros((p,), jnp.float32),
        sum_w=jnp.zeros((p,), jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def range_delta(batch: SparseRows, omega_mat: jax.Array,
                impl: str = "auto") -> RangeState:
    """One batch's contribution — local, additive, psum-able.

    ``impl`` routes the sparse-times-dense products ("auto" = Pallas kernel on
    TPU, jnp oracle elsewhere — the kernels.ops convention).
    """
    values, indices = batch.values, batch.indices
    t = ops.spmm(values, indices, omega_mat, mode=impl)              # (b, l)
    y = ops.spmm_t(values, indices, t, batch.p, mode=impl)           # (p, l)
    flat_idx = indices.reshape(-1)
    v32 = values.astype(jnp.float32)
    diag = jnp.zeros((batch.p,), jnp.float32).at[flat_idx].add(
        (v32 * v32).reshape(-1))
    sum_w = jnp.zeros((batch.p,), jnp.float32).at[flat_idx].add(v32.reshape(-1))
    return RangeState(y, diag, sum_w, jnp.int32(values.shape[0]))


def range_apply(state: RangeState, delta: RangeState) -> RangeState:
    """Fold a (possibly psum'd) delta into the accumulator."""
    return RangeState(state.y + delta.y, state.diag + delta.diag,
                      state.sum_w + delta.sum_w, state.count + delta.count)


def range_update(state: RangeState, batch: SparseRows, omega_mat: jax.Array,
                 impl: str = "auto") -> RangeState:
    return range_apply(state, range_delta(batch, omega_mat, impl))


# THE Thm-4 mean formula lives in core.estimators; RangeState duck-types the
# (sum_w, count) fields it reads, so a fix there fixes every backend at once.
range_finalize_mean = stream_finalize_mean


def range_finalize(state: RangeState, m: int, omega_mat: jax.Array,
                   rank: int | None = None) -> LowRankCov:
    """Rank-r eigenmodel of Ĉ_n from (Y, diag, count) alone — O(p·l²) flops.

    Returns ``rank`` (default l/2 — Omega must oversample the basis, see module
    docstring) eigenpairs of the debiased estimator; consumers slice ``top(k)``
    with k ≤ rank.
    """
    p, ell = state.y.shape
    if m < 2:
        raise ValueError("covariance estimator needs m >= 2 (Thm B4, Eq. 50)")
    r = max(1, ell // 2) if rank is None else int(rank)
    if not 0 < r <= ell:
        raise ValueError(f"rank must be in [1, l={ell}], got {r}")
    corr = (p - m) / (p - 1)
    # Full f32 products: a TPU's default runs an f32 matmul as one bf16 pass,
    # which left the eigenmodel ~3e-4 (eigenvalues) and ~2e-3 (subspace) off
    # between two states that agreed to 6e-8. One finalize per fit, O(p·l²).
    with jax.default_matmul_precision("highest"):
        # the debiased operator's sketch, exactly: (S − corr·diag(d))·Omega,
        # scaled by 1/count so the solve below is conditioned like Ĉ_n
        yp = (state.y - corr * state.diag[:, None] * omega_mat) / state.count
        u, _, _ = jnp.linalg.svd(yp, full_matrices=False)
        q = u[:, :r]                                         # (p, r) basis
        core = (q.T @ yp) @ jnp.linalg.pinv(q.T @ omega_mat)  # r×l fat solve
        return eig_in_basis(q, _cov_scale(p, m) * core)
