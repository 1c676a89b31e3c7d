"""Sparse-times-dense kernels for the low-rank spectral accumulators.

Two primitives over compact sparse rows (values (n, m), indices (n, m)) and a
narrow dense matrix of ``l`` columns (the sketch dimension, l ≪ p):

    spmm:    T = W @ Omega          (n, l)   — project each sparse row
    spmm_t:  Y = Wᵀ @ T             (p, l)   — scatter rows into the l-dim sketch

Together they realize the low-rank co-occurrence delta Wᵀ(W·Omega) = S·Omega
(repro.lowrank) without ever materializing the dense (n, p) batch or the (p, p)
co-occurrence matrix S — the only dense objects are (n, l) and (p, l).

TPU adaptation: like sparse_assign, the irregular gather Omega[indices] has no
fast MXU form, so each row block is densified into a VMEM scratch tile
(kernels.walk.scatter: indices and values read as SMEM scalars, each value
stored through its aligned (8, 128) tile) and both products become dense MXU
matmuls against the narrow operand.

The p axis is TILED: the grid carries a second dimension over column blocks of
``block_cols`` columns, the densify scratch is (block_rows, block_cols), and
each step sees only a (block_cols, l) slice of the dense operand — so the VMEM
footprint is bounded by :func:`plan_tiles` against :data:`SPMM_VMEM_BUDGET`
regardless of p. A block's walk skips indices outside [col0, col0+block_cols).
For ``spmm`` the column blocks are the inner (fastest) grid axis, so each
(block_rows, l) output block stays resident while its partial products
accumulate; for ``spmm_t`` the ROW blocks are the inner axis and the (block_cols,
l) output block is the one revisited — zero-initialized at the first reduction
index, accumulated thereafter (the standard reduction-grid pattern), so HBM
writes stay O(p·l) regardless of n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import walk
from repro.kernels.fwht import MXU_PRECISION, VMEM_BUDGET

# THE spmm VMEM model: tiles are planned against this budget (plan_tiles), and
# kernels/ops.py imports it for its dispatch gate — one number, one model.
SPMM_VMEM_BUDGET = VMEM_BUDGET


def promoted_dtypes(value_dtype, dense_dtype) -> tuple[jnp.dtype, jnp.dtype]:
    """(operand, accumulator/output) dtypes — the kernels' promotion rule.

    Operands promote jointly (bf16·bf16 stays bf16 into the MXU, mixed
    bf16/f32 runs in f32, f64 stays f64); accumulation and the output are at
    least f32 — the same promote_types ladder the ref.py oracles follow, so
    kernel and oracle agree on output dtype for every input combination.
    """
    op = jnp.promote_types(value_dtype, dense_dtype)
    return op, jnp.promote_types(op, jnp.float32)


def tile_vmem_bytes(p: int, ell: int, value_dtype=jnp.float32,
                    dense_dtype=jnp.float32, block_rows: int = 128,
                    block_cols: int | None = None) -> int:
    """Per-grid-step VMEM footprint of the tiled schedule: the larger of the
    two kernels, each counted as Mosaic lays it out (l padded to 128 lanes,
    at the ACTUAL promoted dtypes).

    Both hold the f32 densify scratch (block_rows, block_cols) and its cast
    to the operand dtype. ``spmm`` adds its double-buffered (block_cols, l)
    operand tile and, on the row side, the double-buffered (block_rows, l)
    output plus the dot's result. ``spmm_t`` adds a double-buffered
    (block_rows, l) operand and, per column of its (block_cols, l) output,
    12 bytes per lane-padded and 11 per logical element (f32): the output
    block with the transposed full-precision (MXU_PRECISION) dot's parts, a
    fit to the smallest ``vmem_limit_bytes`` each tile compiles under for a
    described v5e ((8…128) × (2048…8192) tiles, l = 32…256). The model is at
    or above Mosaic's figure at every tile measured.
    """
    op, out = promoted_dtypes(value_dtype, dense_dtype)
    osz, outsz = jnp.dtype(op).itemsize, jnp.dtype(out).itemsize
    pb = min(block_cols, p) if block_cols else p
    lanes = walk.pad_lanes(ell)
    scratch = block_rows * pb * (4 + osz)
    fwd = 2 * pb * lanes * osz + 3 * block_rows * lanes * outsz
    bwd = 2 * block_rows * lanes * osz + pb * (12 * lanes + 11 * ell) * outsz // 4
    return scratch + max(fwd, bwd)


def plan_tiles(p: int, ell: int, value_dtype=jnp.float32,
               dense_dtype=jnp.float32,
               m: int | None = None) -> tuple[int, int]:
    """(block_rows, block_cols) so the tiled schedule fits SPMM_VMEM_BUDGET.

    Prefers wide column blocks (fewer densify passes over the sparse rows,
    fewer operand re-reads) and tall row blocks, shrinking column blocks
    first, then rows, both by powers of two down to (8, 256). With ``m``
    given, rows are also capped so the two SMEM windows of compact rows
    (values, indices) fit (kernels.walk).
    """
    pow2_p = 1 << max(0, (p - 1).bit_length())
    br = 128 if m is None else min(128, walk.smem_block_rows(m, n_windows=2))
    pb = max(walk.LANES, min(pow2_p, 1 << 15))   # the walk needs whole vregs

    def fits(br_, pb_):
        return tile_vmem_bytes(p, ell, value_dtype, dense_dtype, br_, pb_) <= SPMM_VMEM_BUDGET

    while pb > 256 and not fits(br, pb):
        pb //= 2
    while br > 8 and not fits(br, pb):
        br //= 2
    return br, pb


def _spmm_kernel(vals_ref, idx_ref, dense_ref, out_ref, w_ref, *,
                 bn: int, m: int, acc_dtype):
    j = pl.program_id(1)  # column-block (reduction) axis — innermost

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    walk.scatter(w_ref, vals_ref, idx_ref, bn=bn, m=m, col0=j * w_ref.shape[1])
    out_ref[...] += jax.lax.dot(
        w_ref[...].astype(dense_ref.dtype), dense_ref[...], precision=MXU_PRECISION,
        preferred_element_type=acc_dtype).astype(out_ref.dtype)


def _spmm_t_kernel(vals_ref, idx_ref, t_ref, out_ref, w_ref, *,
                   bn: int, m: int, acc_dtype):
    i = pl.program_id(1)  # row-block (reduction) axis — innermost

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    walk.scatter(w_ref, vals_ref, idx_ref, bn=bn, m=m,
                 col0=pl.program_id(0) * w_ref.shape[1])
    # Wᵀ @ T as a dot_general contracting the row axis — no explicit transpose
    acc = jax.lax.dot_general(
        w_ref[...].astype(t_ref.dtype), t_ref[...], (((0,), (0,)), ((), ())),
        precision=MXU_PRECISION, preferred_element_type=acc_dtype)
    out_ref[...] += acc.astype(out_ref.dtype)


def _pad_rows(values, indices, extra, br):
    n = values.shape[0]
    n_pad = -n % br
    if n_pad:
        values = jnp.pad(values, ((0, n_pad), (0, 0)))
        indices = jnp.pad(indices, ((0, n_pad), (0, 0)))
        if extra is not None:
            extra = jnp.pad(extra, ((0, n_pad), (0, 0)))
    return values, indices, extra, n_pad


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def spmm(values: jax.Array, indices: jax.Array, dense: jax.Array,
         block_rows: int | None = None, block_cols: int | None = None,
         interpret: bool = False) -> jax.Array:
    """T (n, l) = W @ dense for compact sparse rows W and dense (p, l).

    Padded rows (zero values, index 0) only ever write zeros into column
    block 0, so ragged row blocks are exact; zero-padded dense rows past p
    are never gathered (indices < p).
    """
    n, m = values.shape
    p, ell = dense.shape
    op_dt, out_dt = promoted_dtypes(values.dtype, dense.dtype)
    br0, pb0 = plan_tiles(p, ell, values.dtype, dense.dtype, m=m)
    br = block_rows or br0
    pb = block_cols or pb0
    values, indices, _, n_pad = _pad_rows(values, indices, None, br)
    pc = -p % pb
    dense = dense.astype(op_dt)
    if pc:
        dense = jnp.pad(dense, ((0, pc), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_spmm_kernel, bn=br, m=m, acc_dtype=out_dt),
        grid=((n + n_pad) // br, (p + pc) // pb),
        in_specs=[
            walk.smem_spec(br, m, lambda i, j: (i, 0)),
            walk.smem_spec(br, m, lambda i, j: (i, 0)),
            pl.BlockSpec((pb, ell), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, ell), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, ell), out_dt),
        scratch_shapes=[pltpu.VMEM((br, pb), jnp.float32)],
        interpret=interpret,
    )(values.astype(jnp.float32), indices.astype(jnp.int32), dense)
    return out[:n] if n_pad else out


@functools.partial(jax.jit, static_argnames=("p", "block_rows", "block_cols", "interpret"))
def spmm_t(values: jax.Array, indices: jax.Array, t: jax.Array, p: int,
           block_rows: int | None = None, block_cols: int | None = None,
           interpret: bool = False) -> jax.Array:
    """Y (p, l) = Wᵀ @ t for compact sparse rows W (n over p columns), t (n, l).

    Zero-padded rows contribute nothing, so ragged blocks are exact. Column
    blocks are the OUTER grid axis here (the output is indexed by them), so
    the compact rows are re-read once per column block — n·m·(p/block_cols)
    sparse traffic against O(p·l) output writes.
    """
    n, m = values.shape
    ell = t.shape[1]
    op_dt, out_dt = promoted_dtypes(values.dtype, t.dtype)
    br0, pb0 = plan_tiles(p, ell, values.dtype, t.dtype, m=m)
    br = block_rows or br0
    pb = block_cols or pb0
    values, indices, t, n_pad = _pad_rows(values, indices, t, br)
    pc = -p % pb

    out = pl.pallas_call(
        functools.partial(_spmm_t_kernel, bn=br, m=m, acc_dtype=out_dt),
        grid=((p + pc) // pb, (n + n_pad) // br),
        in_specs=[
            walk.smem_spec(br, m, lambda j, i: (i, 0)),
            walk.smem_spec(br, m, lambda j, i: (i, 0)),
            pl.BlockSpec((br, ell), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((pb, ell), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((p + pc, ell), out_dt),
        scratch_shapes=[pltpu.VMEM((br, pb), jnp.float32)],
        interpret=interpret,
    )(values.astype(jnp.float32), indices.astype(jnp.int32), t.astype(op_dt))
    return out[:p] if pc else out
