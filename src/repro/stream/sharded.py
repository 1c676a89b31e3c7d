"""shard_map one-shot reductions over row-sharded sketches.

These are the batch (non-streaming) entry points of the same delta/psum algebra
the StreamEngine loops: each shard computes its local accumulator delta from its
rows, and the only collective is one psum of the fixed-size delta — (p,) for the
mean, (p, p) for the covariance — regardless of how many rows each shard holds.
(These absorbed the former ``repro.core.distributed`` shims: this module is
the one home of the distributed one-pass setting, ``repro.api`` the front
door over it.)

The ``repro.api`` sharded backend also streams THROUGH :func:`sharded_moments`:
its moment reducer buffers one step's shard sketches, reduces them with a
single call (one psum of the fixed-size delta), folds the result via
``moment_apply``, and drops the sketches — per-step streaming reduction, so
host memory stays constant in the stream length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.sampling import SparseRows
from repro.lowrank import range_finder as lr_range
from repro.stream import accumulators as acc


@functools.lru_cache(maxsize=None)
def _moments_fn(mesh, axes, track_cov, cov_path, p):
    """The compiled psum reduction, cached per (mesh, axes, flags, p) so the
    per-step streaming callers (repro.api sharded backend) pay tracing and
    compilation once per stream, not once per step."""

    def local(values, indices):
        delta = acc.moment_delta(SparseRows(values, indices, p), track_cov=track_cov,
                                 cov_path=cov_path)
        for a in axes:
            delta = jax.lax.psum(delta, a)
        return delta

    row_spec = P(axes if len(axes) > 1 else axes[0], None)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(row_spec, row_spec),
                             out_specs=P()))


def sharded_moments(s: SparseRows, mesh, axes=("data",), track_cov: bool = True,
                    cov_path: str = "dense") -> acc.MomentState:
    """psum-reduced MomentState for a row-sharded sketch (replicated output).

    ``cov_path="compact"`` uses the n·m² outer-product delta (no dense (n, p)
    intermediate per shard) — the γ ≪ 1 choice.
    """
    p = s.p
    n = s.values.shape[0]
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    # shard_map needs the row axis evenly divisible; zero-value pad rows add
    # nothing to sum_w / sum_wwt, and the true n overrides the count below.
    pad = -n % n_shards
    values, indices = s.values, s.indices
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        indices = jnp.pad(indices, ((0, pad), (0, 0)))

    fn = _moments_fn(mesh, tuple(axes), bool(track_cov), cov_path, p)
    st = fn(values, indices)
    return acc.MomentState(st.sum_w, st.sum_wwt, jnp.int32(n))


@functools.lru_cache(maxsize=None)
def _lowrank_fn(mesh, axes, p, ell, impl):
    """Compiled psum reduction of the low-rank range-finder delta — the
    cross-shard traffic is the fixed (p, l) + 2·(p,) state, never (p, p)."""

    def local(values, indices, omega_mat):
        delta = lr_range.range_delta(SparseRows(values, indices, p), omega_mat,
                                     impl=impl)
        for a in axes:
            delta = jax.lax.psum(delta, a)
        return delta

    row_spec = P(axes if len(axes) > 1 else axes[0], None)
    # check_vma=False: the spmm kernels inside declare no per-axis varying
    # type for their outputs
    return jax.jit(shard_map(local, mesh=mesh,
                             in_specs=(row_spec, row_spec, P()),
                             out_specs=P(), check_vma=False))


def sharded_lowrank(s: SparseRows, omega_mat: jax.Array, mesh, axes=("data",),
                    impl: str = "auto") -> lr_range.RangeState:
    """psum-reduced RangeState delta for a row-sharded sketch (replicated out).

    The streaming low-rank analogue of :func:`sharded_moments`: same zero-pad
    handling (pad rows contribute nothing; the true n overrides the count).
    """
    p = s.p
    n = s.values.shape[0]
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    pad = -n % n_shards
    values, indices = s.values, s.indices
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        indices = jnp.pad(indices, ((0, pad), (0, 0)))

    fn = _lowrank_fn(mesh, tuple(axes), p, omega_mat.shape[1], impl)
    st = fn(values, indices, omega_mat)
    return lr_range.RangeState(st.y, st.diag, st.sum_w, jnp.int32(n))


def sharded_mean(s: SparseRows, mesh, axes=("data",)) -> jax.Array:
    """Thm-4 estimator with explicit psum accumulation (cross-shard traffic: (p,))."""
    st = sharded_moments(s, mesh, axes, track_cov=False)
    return acc.moment_finalize_mean(st, s.m)


def sharded_cov(s: SparseRows, mesh, axes=("data",)) -> jax.Array:
    """Thm-6 estimator with explicit psum accumulation (cross-shard traffic: (p,p))."""
    st = sharded_moments(s, mesh, axes, track_cov=True)
    return acc.moment_finalize_cov(st, s.m)


@functools.lru_cache(maxsize=None)
def _kmeans_step_fn(mesh, axis, p, decay, track):
    """Compiled mini-batch K-means step: local masked delta per shard, ONE psum
    of the fixed-size (sums, cnts, obj, n) delta, apply on replicated state.
    Cached per (mesh, axis, p, decay, track) so streaming callers compile once."""
    from repro.core.kmeans import sparse_sq_dists

    def local(state, values, indices, mask):
        k = state.centers.shape[1]
        maskf = mask.astype(jnp.float32)
        maski = jnp.broadcast_to(mask.astype(jnp.int32)[:, None], indices.shape)

        def one(centers):
            d = sparse_sq_dists(values, indices, centers)        # (n, K)
            a = jnp.argmin(d, axis=1)
            rows = jnp.broadcast_to(a[:, None], indices.shape)
            # Zero-pad rows are REAL points at the origin to the scatter adds
            # (unlike the linear moment deltas) — the mask zeroes their
            # values, counts, and objective contributions explicitly.
            sums = jnp.zeros((k, p), jnp.float32).at[rows, indices].add(
                values.astype(jnp.float32) * maskf[:, None])
            cnts = jnp.zeros((k, p), jnp.int32).at[rows, indices].add(maski)
            obj = jnp.sum(jnp.min(d, axis=1) * maskf).astype(jnp.float32)
            return sums, cnts, obj, a.astype(jnp.int32)

        sums, cnts, obj, assign = jax.vmap(one)(state.centers)
        delta = jax.lax.psum(
            (sums, cnts, obj, jnp.sum(mask).astype(jnp.int32)), axis)
        new = acc.kmeans_apply(state, delta, decay)
        if not track:
            return new

        def reassigned(c_new, a_prev):
            a1 = jnp.argmin(sparse_sq_dists(values, indices, c_new), axis=1)
            return jnp.sum((a1.astype(jnp.int32) != a_prev)
                           * mask.astype(jnp.int32)).astype(jnp.int32)

        cnt = jax.lax.psum(jax.vmap(reassigned)(new.centers, assign), axis)
        return new, cnt

    row_spec = P(axis, None)
    out = (P(), P()) if track else P()
    return jax.jit(shard_map(local, mesh=mesh,
                             in_specs=(P(), row_spec, row_spec, P(axis)),
                             out_specs=out))


def sharded_kmeans_step(state: acc.KMeansState, s: SparseRows, mesh,
                        axis: str = "data", *, decay: float = 1.0,
                        track_reassignments: bool = False, mask=None):
    """One streaming mini-batch K-means step over a row-sharded step sketch.

    The mesh-resident analogue of ``kmeans_delta`` + ``kmeans_apply``:
    assignment stays local to each shard, the only collective is one psum of
    the fixed-size delta, and the Eq.-39 apply (with ``decay``) runs once on
    the replicated state — so sharded streaming matches the host loop to
    float-summation reordering. Returns ``(new_state, reassigned)`` where
    ``reassigned`` is the psum'd (r,) int32 reassignment count when
    ``track_reassignments`` (one extra assignment pass under the NEW centers),
    else ``None``.

    Rows are zero-padded to divide the mesh's shard count; because padded rows
    would be real origin points to the scatter adds, an explicit row ``mask``
    zeroes their contribution (multiprocess callers pass pre-assembled global
    arrays plus their own mask; single-host callers may leave ``mask=None``).
    """
    n = s.values.shape[0]
    n_shards = mesh.shape[axis]
    values, indices = s.values, s.indices
    if mask is None:
        pad = -n % n_shards
        mask = jnp.ones((n,), jnp.int32)
        if pad:
            values = jnp.pad(values, ((0, pad), (0, 0)))
            indices = jnp.pad(indices, ((0, pad), (0, 0)))
            mask = jnp.pad(mask, (0, pad))
    fn = _kmeans_step_fn(mesh, axis, s.p, float(decay),
                         bool(track_reassignments))
    out = fn(state, values, indices, mask)
    return out if track_reassignments else (out, None)


# --------------------------------------------- distributed-data entry points --
# Absorbed from the retired repro.core.distributed module (paper §I's
# distributed setting): place rows on the mesh, sketch them in place, and run
# the sparse Lloyd solver with each device's rows in place, its update's
# sums reduced by one psum a pass.


def shard_rows(x: jax.Array, mesh, axes=("data",)) -> jax.Array:
    """Place (n, …) data row-sharded over the mesh's data axes."""
    from jax.sharding import NamedSharding

    spec = P(axes if len(axes) > 1 else axes[0], *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def sketch_sharded(x: jax.Array, spec, mesh, axes=("data",)) -> SparseRows:
    """One-pass compress of row-sharded data; output stays row-sharded."""
    from repro.core import sketch

    xs = shard_rows(x, mesh, axes)
    with jax.set_mesh(mesh):
        return sketch.sketch(xs, spec)


def sharded_lloyd(s: SparseRows, k: int, key, mesh, axes=None, **kw):
    """Alg. 1's Lloyd (``core.kmeans.lloyd``, its keyword options ``kw``)
    with the sketch's rows spread over ``axes`` (the mesh's data axes
    unless given): each device assigns its own rows, and the update's sums
    and counts psum over the axes. Returns the ``LloydFit``."""
    from repro.core import kmeans
    from repro.launch.mesh import dp_axes_of

    axes = tuple(axes or dp_axes_of(mesh) or mesh.axis_names)
    # the solver's gathers leave their sharding to the compiler: Auto axes
    mesh = jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
    vt, it = kmeans.rows_transposed(s.values, s.indices, mesh, axes)
    with jax.set_mesh(mesh):
        return kmeans.lloyd(vt, it, s.n, s.p, k, key, mesh=mesh, axes=axes, **kw)


def sharded_kmeans(s: SparseRows, k: int, key, mesh, n_init: int = 3,
                   max_iter: int = 50, tol: float = 1e-6, impl: str = "auto"):
    """:func:`sharded_lloyd`'s (centers, labels, objective, n_iter)."""
    fit = sharded_lloyd(s, k, key, mesh, n_init=n_init, max_iter=max_iter, tol=tol,
                        impl=impl)
    return fit.centers, fit.labels, fit.objective, fit.n_iter
