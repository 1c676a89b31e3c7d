"""Per-element index walks that Mosaic lowers: SMEM scalars, aligned VMEM tiles.

``spmm``/``spmm_t`` densify compact rows (values (n, m), indices (n, m)) into
(rows, p) VMEM tiles one kept coordinate at a time. Two constraints of the TPU
compiler shape how:

- scalars live in SMEM. An index read out of a VMEM block is a vector, so the
  compact rows are handed to the kernel as SMEM blocks (``smem_spec``), where
  ``idx_ref[i, j]`` is a scalar load;
- VMEM is read and written at dynamic offsets only in whole (8, 128) tiles
  (a (1, 1) slice at a dynamic row and lane is refused as an unaligned load).
  So element (i, col) is reached through its aligned tile and a one-hot mask
  (``element_tile``): a scatter is a masked read-modify-write of one tile.

The other direction, reading kept values out of a dense tile, needs no walk:
``sketch_fused`` gathers whole 128-lane tiles at once (``tpu.dynamic_gather``).

Every kernel that walks this way keeps its dense tiles in float32, whose
(8, 128) tiling this assumes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 128

# SMEM is 1 MiB per core on v5e. The compact-row windows are planned against
# half of it; each window is double-buffered by the pipeline.
SMEM_BUDGET = 512 << 10


def pad_lanes(k: int) -> int:
    """k rounded up to whole 128-lane vregs (how Mosaic lays out a minor dim)."""
    return -(-k // LANES) * LANES


def smem_bytes(block_rows: int, m: int, n_windows: int) -> int:
    """SMEM taken by ``n_windows`` double-buffered (block_rows, m) 32-bit
    windows; SMEM pads the minor dim to 128 like VMEM does."""
    return n_windows * 2 * block_rows * pad_lanes(m) * 4


def smem_block_rows(m: int, n_windows: int) -> int:
    """Largest power-of-two row block (≥ 8, ≤ 256) whose windows fit
    :data:`SMEM_BUDGET`; raises when even 8 rows of width m do not."""
    if smem_bytes(SUBLANES, m, n_windows) > SMEM_BUDGET:
        raise ValueError(
            f"m={m} kept coordinates per row exceed the SMEM index window "
            f"({smem_bytes(SUBLANES, m, n_windows)} > {SMEM_BUDGET} bytes at 8 rows)")
    br = SUBLANES
    while br * 2 <= 256 and smem_bytes(br * 2, m, n_windows) <= SMEM_BUDGET:
        br *= 2
    return br


def smem_spec(block_rows: int, m: int, index_map) -> pl.BlockSpec:
    """BlockSpec of a (block_rows, m) window of compact rows, placed in SMEM."""
    return pl.BlockSpec((block_rows, m), index_map, memory_space=pltpu.SMEM)


def element_tile(i, col):
    """(slices of the aligned (8, 128) tile holding element (i, col), mask of
    the element inside that tile)."""
    r0 = pl.multiple_of((i // SUBLANES) * SUBLANES, SUBLANES)
    c0 = pl.multiple_of((col // LANES) * LANES, LANES)
    shape = (SUBLANES, LANES)
    hit = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == i - r0)
           & (jax.lax.broadcasted_iota(jnp.int32, shape, 1) == col - c0))
    return (pl.ds(r0, SUBLANES), pl.ds(c0, LANES)), hit


def _for_each_entry(bn: int, m: int, fn) -> None:
    """fn(i, j) for every row i < bn and kept slot j < m (nested loops: no
    scalar division in the walk)."""

    def row(i, carry):
        def slot(j, carry):
            fn(i, j)
            return carry

        return jax.lax.fori_loop(0, m, slot, carry)

    jax.lax.fori_loop(0, bn, row, 0)


def scatter(w_ref, vals_ref, idx_ref, *, bn: int, m: int, col0=0, s_ref=None):
    """Densify the block's compact rows into the zeroed (bn, pb) f32 tile
    ``w_ref``: w[i, idx[i, j] - col0] = vals[i, j] for indices inside
    [col0, col0 + pb). ``s_ref``, when given, receives the 0/1 support.

    Indices are distinct within a row, so each element is written at most
    once; entries outside the column block are skipped.
    """
    pb = w_ref.shape[1]
    w_ref[...] = jnp.zeros_like(w_ref)
    if s_ref is not None:
        s_ref[...] = jnp.zeros_like(s_ref)

    def entry(i, j):
        col = idx_ref[i, j] - col0

        @pl.when((col >= 0) & (col < pb))
        def _():
            tile, hit = element_tile(i, col)
            w_ref[tile] = jnp.where(hit, vals_ref[i, j], w_ref[tile])
            if s_ref is not None:
                s_ref[tile] = jnp.where(hit, 1.0, s_ref[tile])

    _for_each_entry(bn, m, entry)

