"""Sparsified K-means assignment kernel (paper Eq. 36) on compact sparse rows.

Computes, for every sample i with kept coordinates (values V_i, indices I_i):

    d[i, k] = ‖z_i − R_iᵀ μ_k‖² = Σ_j V_ij² − 2⟨W_i, μ_k⟩ + ⟨S_i, μ_k²⟩

where W_i is the densified sparse row and S_i its 0/1 support mask.

TPU adaptation (DESIGN.md §3.2): the irregular gather μ_k[I_ij] has no fast MXU
form, so we *densify inside VMEM* (never materializing W, S in HBM) and realize
both inner products as dense (block_rows × p) @ (p × K) MXU matmuls. HBM traffic
stays compact — 8·n·m bytes in, 4·n·(K+1) out — so the paper's γ saving survives
as a *bandwidth* saving while the arithmetic runs at MXU rate. Densification is
the SMEM-scalar walk of kernels.walk.scatter (indices are distinct per row, so
each element is stored once); its trip count is block_rows·m, amortized across
the two matmuls that follow. Σ_j V_ij² is read back off the densified tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import walk
from repro.kernels.fwht import MXU_PRECISION, VMEM_BUDGET


def _kernel(vals_ref, idx_ref, ctr_t_ref, ctr2_t_ref, dist_ref, amin_ref,
            w_ref, s_ref, *, bn: int, m: int):
    walk.scatter(w_ref, vals_ref, idx_ref, bn=bn, m=m, s_ref=s_ref)
    w = w_ref[...]
    v2 = jnp.sum(w * w, axis=1, keepdims=True)                # (bn, 1)
    f32 = jnp.float32
    cross = jax.lax.dot(w.astype(ctr_t_ref.dtype), ctr_t_ref[...],
                        precision=MXU_PRECISION, preferred_element_type=f32)
    mask2 = jax.lax.dot(s_ref[...].astype(ctr2_t_ref.dtype), ctr2_t_ref[...],
                        precision=MXU_PRECISION, preferred_element_type=f32)
    d = v2 - 2.0 * cross + mask2
    dist_ref[...] = d.astype(dist_ref.dtype)
    amin_ref[...] = jnp.argmin(d, axis=1).astype(jnp.int32)[:, None]


def default_block_rows(p: int, m: int, k: int, dtype=jnp.float32) -> int:
    """Rows per tile, from what Mosaic allocates per grid step, against
    fwht.VMEM_BUDGET.

    Fixed: the two (p, K) center operands, double-buffered, K padded to 128
    lanes. Per row: the f32 w and s scratch tiles and their casts to the
    operand dtype, and the double-buffered (rows, K) and (rows, 1) outputs
    (lane-padded). Capped by the SMEM windows of values and indices.
    """
    pp, kp = walk.pad_lanes(p), walk.pad_lanes(k)
    isz = jnp.dtype(dtype).itemsize
    fixed = 2 * 2 * pp * kp * isz
    per_row = 2 * pp * (4 + isz) + 2 * (kp + walk.LANES) * 4 + 2 * kp * 4
    br = max(8, (VMEM_BUDGET - fixed) // per_row)
    br = int(min(128, 1 << int(np.floor(np.log2(br)))))
    return min(br, walk.smem_block_rows(m, n_windows=2))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sparse_assign(values: jax.Array, indices: jax.Array, centers: jax.Array,
                  block_rows: int | None = None, interpret: bool = False):
    """(dists (n, K) f32, argmin (n,) int32) for compact sparse rows vs centers (K, p)."""
    n, m = values.shape
    k, p = centers.shape
    br = block_rows or default_block_rows(p, m, k, values.dtype)
    n_pad = -n % br
    if n_pad:
        values = jnp.pad(values, ((0, n_pad), (0, 0)))
        indices = jnp.pad(indices, ((0, n_pad), (0, 0)))
    # the scratch tiles are lane-aligned: pad p with zero-center columns
    # that no index reaches
    pp = walk.pad_lanes(p)
    ctr_t = jnp.pad(centers.astype(values.dtype).T, ((0, pp - p), (0, 0)))
    ctr2_t = jnp.pad((centers.astype(jnp.float32) ** 2).astype(values.dtype).T,
                     ((0, pp - p), (0, 0)))

    dists, amin = pl.pallas_call(
        functools.partial(_kernel, bn=br, m=m),
        grid=((n + n_pad) // br,),
        in_specs=[
            walk.smem_spec(br, m, lambda i: (i, 0)),
            walk.smem_spec(br, m, lambda i: (i, 0)),
            pl.BlockSpec((pp, k), lambda i: (0, 0)),
            pl.BlockSpec((pp, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + n_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((n + n_pad, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, pp), jnp.float32),
            pltpu.VMEM((br, pp), jnp.float32),
        ],
        interpret=interpret,
    )(values.astype(jnp.float32), indices.astype(jnp.int32), ctr_t, ctr2_t)
    dists = dists[:n] if n_pad else dists
    amin = (amin[:n] if n_pad else amin)[:, 0]
    return dists, amin
