"""Sparsified K-means passes over a retained sketch (paper Eqs. 35, 36, 39).

The retained sketch is held transposed, one row per lane: ``values_t`` and
``indices_t`` are (m, n), column i holding row i's kept values and their
coordinates. For centers μ_k (K, p) and a row's densified tile W_i and 0/1
support S_i,

    d[i, k] = ‖z_i − R_iᵀ μ_k‖² = Σ_j V_ij² − 2⟨W_i, μ_k⟩ + ⟨S_i, μ_k²⟩.

Each kernel densifies a tile of rows inside VMEM, transposed to (p, rows),
by an ``iota == index`` compare per kept slot (vector work, one select per
element and slot, no per-coordinate walk), and contracts it on the MXU:

- :func:`assign`: the argmin over K of every row and its minimum, for G
  groups of K centers at once (the n_init hypotheses); K is tiled, and
  only (G, n) labels and minima reach HBM;
- :func:`update`: the Eq. 39 sums Σ_{a_i = k} W_i and per-coordinate counts
  Σ_{a_i = k} S_i as one-hot (K-tile × rows) products against the tile;
  a row whose label is negative adds nothing;
- :func:`dists`: every row's distance to a few candidate centers (the
  K-means++ step), (C, n).

Rows are tiled by ``ROW_TILE`` (the caller pads n to it: a padded column's
distances are meaningless and its label must be masked); K by ``k_tile``.
Full f32 precision throughout: the centers' dot with the values runs at
``MXU_PRECISION``; where one operand is 0/1 (the support, the one-hot
labels), which bfloat16 holds exactly, the other is split into three
bfloat16 parts, so three exact bf16 passes give the f32 result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fwht import MXU_PRECISION

ROW_TILE = 512            # rows per grid step (lanes of the densified tile)
LANES = 128
_SLAB = 64                # sublanes of the densify's register-resident slab
# the kernels hold the (p, rows) tile and its support, and per K tile the
# centers (assign) or the sums and counts (update) of every group,
# double-buffered; 48 MiB of the v5e core's 128 MiB of VMEM
VMEM_LIMIT = 48 << 20
_NT = (((1,), (1,)), ((), ()))   # contract the row axis of both operands


def k_tile(k: int, groups: int, p: int) -> int:
    """Centers per K tile: as many as fit, a multiple of 8, at most 256.

    Per center of every group the assign kernel holds μ and μ² and the
    update kernel the sums and counts, each double-buffered: 16·p bytes."""
    fit = (VMEM_LIMIT // 2) // (16 * p * groups)
    tk = min(256, -(-k // 8) * 8, fit // 8 * 8)
    if tk < 8:
        raise ValueError(f"{groups} groups of centers of width p={p} do not fit "
                         f"a K tile of 8 in {VMEM_LIMIT} bytes of VMEM")
    return tk


def supported(p: int, k: int, groups: int) -> bool:
    """Whether the kernels can tile this shape (else the caller uses the
    jnp reference)."""
    try:
        k_tile(k, groups, p)
    except ValueError:
        return False
    return 4 * p * ROW_TILE * 4 <= VMEM_LIMIT // 2


def _densify(vals_ref, idx_ref, w_ref, s_ref):
    """w_ref (p, rows) ← the tile's rows densified, transposed; s_ref ← their
    0/1 supports. A (slab, 128) block is built in registers across the m
    kept slots (a NaN marks a coordinate no slot set), then stored once."""
    m = vals_ref.shape[0]
    p, rows = w_ref.shape
    slab = min(_SLAB, p)
    n_lane = rows // LANES

    def block(b, carry):
        c0 = pl.multiple_of((b // n_lane) * slab, slab)
        l0 = pl.multiple_of((b % n_lane) * LANES, LANES)
        coord = jax.lax.broadcasted_iota(jnp.int32, (slab, LANES), 0) + c0
        idx = idx_ref[:, pl.ds(l0, LANES)]
        val = vals_ref[:, pl.ds(l0, LANES)]
        w = jnp.full((slab, LANES), jnp.nan, jnp.float32)
        for j in range(m):
            w = jnp.where(coord == idx[j:j + 1, :], val[j:j + 1, :], w)
        hit = w == w
        w_ref[pl.ds(c0, slab), pl.ds(l0, LANES)] = jnp.where(hit, w, 0.0)
        s_ref[pl.ds(c0, slab), pl.ds(l0, LANES)] = hit.astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, (p // slab) * n_lane, block, 0)


def _split(x):
    """x (f32) as three bfloat16 parts whose sum is x to f32 rounding."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _exact_dot(parts, b):
    """Σ part @ b over bf16 parts against an operand b that bf16 holds
    exactly (0/1): every product exact, f32 sums."""
    return sum(jnp.dot(a, b, preferred_element_type=jnp.float32) for a in parts)


def _sq_dists(ctr, ctr2, w, s, z2):
    """(C, rows) distances of the tile's rows to the C centers given; ``s``
    is the tile's support in bfloat16."""
    cross = jax.lax.dot(ctr, w, precision=MXU_PRECISION,
                        preferred_element_type=jnp.float32)
    return z2 - 2.0 * cross + _exact_dot(_split(ctr2), s)


def _row_norms(vals_ref, z2_ref):
    v = vals_ref[...]
    z2_ref[...] = jnp.sum(v * v, axis=0, keepdims=True)


def _assign_kernel(vals_ref, idx_ref, ctr_ref, ctr2_ref, lab_ref, min_ref,
                   w_ref, s_ref, z2_ref, *, k: int, tk: int):
    kt = pl.program_id(1)

    @pl.when(kt == 0)
    def _():
        _densify(vals_ref, idx_ref, w_ref, s_ref)
        _row_norms(vals_ref, z2_ref)

    w, s, z2 = w_ref[...], s_ref[...].astype(jnp.bfloat16), z2_ref[...]
    rows = w.shape[1]
    kid = jax.lax.broadcasted_iota(jnp.int32, (tk, rows), 0) + kt * tk
    for g in range(ctr_ref.shape[0]):
        d = _sq_dists(ctr_ref[g], ctr2_ref[g], w, s, z2)
        d = jnp.where(kid < k, d, jnp.inf)
        dmin = jnp.min(d, axis=0, keepdims=True)
        arg = jnp.min(jnp.where(d == dmin, kid, k), axis=0, keepdims=True)
        # the first K tile writes; a later one replaces only a strictly
        # smaller minimum, so ties keep the lowest index (jnp.argmin's rule)
        prev = jnp.where(kt == 0, jnp.inf, min_ref[g:g + 1, :])
        better = dmin < prev
        min_ref[g:g + 1, :] = jnp.where(better, dmin, prev)
        lab_ref[g:g + 1, :] = jnp.where(better, arg, lab_ref[g:g + 1, :])


def _update_kernel(vals_ref, idx_ref, lab_ref, sums_ref, cnt_ref, w_ref, s_ref,
                   *, tk: int):
    kt, i = pl.program_id(0), pl.program_id(1)
    _densify(vals_ref, idx_ref, w_ref, s_ref)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    w, s = _split(w_ref[...]), s_ref[...].astype(jnp.bfloat16)
    rows = s.shape[1]
    kid = jax.lax.broadcasted_iota(jnp.int32, (tk, rows), 0) + kt * tk
    for g in range(lab_ref.shape[0]):
        hot = (kid == lab_ref[g:g + 1, :]).astype(jnp.bfloat16)
        sums_ref[g] += sum(jax.lax.dot_general(hot, part, _NT,
                                               preferred_element_type=jnp.float32)
                           for part in w)
        cnt_ref[g] += jax.lax.dot_general(hot, s, _NT, preferred_element_type=jnp.float32)


def _dists_kernel(vals_ref, idx_ref, ctr_ref, ctr2_ref, out_ref, w_ref, s_ref, z2_ref):
    _densify(vals_ref, idx_ref, w_ref, s_ref)
    _row_norms(vals_ref, z2_ref)
    out_ref[...] = _sq_dists(ctr_ref[...], ctr2_ref[...], w_ref[...],
                             s_ref[...].astype(jnp.bfloat16), z2_ref[...])


def _row_specs(m: int, index_map):
    return [pl.BlockSpec((m, ROW_TILE), index_map), pl.BlockSpec((m, ROW_TILE), index_map)]


def _scratch(p: int, norms: bool):
    out = [pltpu.VMEM((p, ROW_TILE), jnp.float32), pltpu.VMEM((p, ROW_TILE), jnp.float32)]
    return out + [pltpu.VMEM((1, ROW_TILE), jnp.float32)] if norms else out


def _params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _pad_k(centers, tk: int):
    k = centers.shape[1]
    pad = -k % tk
    return jnp.pad(centers, ((0, 0), (0, pad), (0, 0))) if pad else centers


@functools.partial(jax.jit, static_argnames=("interpret",))
def assign(values_t, indices_t, centers, interpret: bool = False):
    """(labels (G, n) int32, minima (G, n) f32): each row's nearest center of
    every group of ``centers`` (G, K, p) and its distance. n must be a
    multiple of ``ROW_TILE``."""
    m, n = values_t.shape
    g, k, p = centers.shape
    tk = k_tile(k, g, p)
    ctr = _pad_k(centers.astype(jnp.float32), tk)
    ctr_spec = pl.BlockSpec((g, tk, p), lambda i, kt: (0, kt, 0))
    out_spec = pl.BlockSpec((g, ROW_TILE), lambda i, kt: (0, i))
    return pl.pallas_call(
        functools.partial(_assign_kernel, k=k, tk=tk),
        grid=(n // ROW_TILE, ctr.shape[1] // tk),
        in_specs=_row_specs(m, lambda i, kt: (0, i)) + [ctr_spec, ctr_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((g, n), jnp.int32),
                   jax.ShapeDtypeStruct((g, n), jnp.float32)],
        scratch_shapes=_scratch(p, norms=True),
        compiler_params=_params(),
        interpret=interpret,
    )(values_t, indices_t, ctr, ctr * ctr)


@functools.partial(jax.jit, static_argnames=("k", "p", "interpret"))
def update(values_t, indices_t, labels, k: int, p: int, interpret: bool = False):
    """(sums (G, K, p), counts (G, K, p)) f32 of the rows under ``labels``
    (G, n): per group and center, the sum of its rows' densified values and
    how many of them kept each coordinate. A negative label adds nothing."""
    m, n = values_t.shape
    g = labels.shape[0]
    tk = k_tile(k, g, p)
    kp = -(-k // tk) * tk
    out_spec = pl.BlockSpec((g, tk, p), lambda kt, i: (0, kt, 0))
    sums, counts = pl.pallas_call(
        functools.partial(_update_kernel, tk=tk),
        grid=(kp // tk, n // ROW_TILE),
        in_specs=_row_specs(m, lambda kt, i: (0, i))
        + [pl.BlockSpec((g, ROW_TILE), lambda kt, i: (0, i))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((g, kp, p), jnp.float32)] * 2,
        scratch_shapes=_scratch(p, norms=False),
        compiler_params=_params(),
        interpret=interpret,
    )(values_t, indices_t, labels.astype(jnp.int32))
    return sums[:, :k], counts[:, :k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dists(values_t, indices_t, centers, interpret: bool = False):
    """(C, n) f32 distances of every row to each of ``centers`` (C, p)."""
    m, n = values_t.shape
    c, p = centers.shape
    ctr = centers.astype(jnp.float32)
    cp = -c % 8
    if cp:
        ctr = jnp.pad(ctr, ((0, cp), (0, 0)))
    ctr_spec = pl.BlockSpec((c + cp, p), lambda i: (0, 0))
    out = pl.pallas_call(
        _dists_kernel,
        grid=(n // ROW_TILE,),
        in_specs=_row_specs(m, lambda i: (0, i)) + [ctr_spec, ctr_spec],
        out_specs=pl.BlockSpec((c + cp, ROW_TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c + cp, n), jnp.float32),
        scratch_shapes=_scratch(p, norms=True),
        compiler_params=_params(),
        interpret=interpret,
    )(values_t, indices_t, ctr, ctr * ctr)
    return out[:c]
