"""Fused ROS preconditioning kernel: y = H·(d ⊙ x) as Kronecker-factored MXU matmuls.

TPU adaptation (DESIGN.md §3.1): GPU FWHTs use warp-shuffle butterflies; the TPU
equivalent is the Kronecker identity

    H_p = H_a ⊗ H_b   (p = a·b, Sylvester ordering)
    H_p x = vec( H_a · mat_{a×b}(x) · H_bᵀ )      (row-major reshape)

so the whole transform becomes two dense matmuls on the systolic array, with the
sign flip (D) fused into the same VMEM round-trip. Cost p·(a+b) MACs/row instead
of the butterfly's p·log₂p VPU ops — fewer passes over VMEM and ~all of it on
the MXU. For p ≤ 256 a single dense H_p matmul is used (a = 1).

The kernel tiles rows; each grid step owns a (block_rows, p) tile resident in
VMEM. H_a, H_b (and the sign vector) are small and replicated to every step.
Its row block (:func:`default_block_rows`) counts what Mosaic allocates,
the lane-padded Kronecker intermediates included, so every power of two p
from 2^8 to 2^15 compiles for v5e under the default scoped-VMEM limit; the
chunked schedule below compiles at 2^16 and 2^17.

**Large p (the streaming regime, p > 2^15):** a (block_rows, p) tile no longer
fits VMEM, so the transform is *chunked* with the three-factor identity

    H_p = H_a ⊗ H_b ⊗ H_c   (a·b·c = p, each factor ≤ 2^9)

and realized as three passes over the data, each pass a tiled (rows, f) @ H_f
matmul whose (block, f) chunks fit VMEM independent of p. The sign flip is
fused into the first pass; the reorderings between passes are XLA transposes.
This lifts the previous MAX_P = 2^15 ceiling to 2^27 — see
:func:`hd_precondition_chunked` and tests/test_stream.py for the p = 2^17
interpret-mode equivalence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.ros import hadamard_matrix

# largest p the single-tile kernel supports: (block_rows × p) must fit VMEM.
# Every power of two from 2^8 to 2^15 compiles for v5e at default_block_rows.
MAX_P_SINGLE = 1 << 15
# largest p overall — the chunked three-pass schedule with factors ≤ 2^9.
MAX_P = 1 << 27


def factor_p(p: int) -> tuple[int, int]:
    """Split p = a·b with b the MXU-friendly inner factor (b ≥ 128 when possible)."""
    if p & (p - 1):
        raise ValueError(f"p must be a power of two, got {p}")
    if p <= 256:
        return 1, p
    k = p.bit_length() - 1
    b = 1 << max(7, (k + 1) // 2)    # inner factor ≥ 128
    return p // b, b


def factor_p3(p: int) -> tuple[int, int, int]:
    """Split p = a·b·c (Sylvester order) with every factor ≤ 2^9.

    The trailing factors are filled greedily to 2^9 so the two hot passes
    contract MXU-friendly 512-lane dimensions; the outer factor a absorbs the
    remainder (a = 1 for p ≤ 2^18).
    """
    if p & (p - 1):
        raise ValueError(f"p must be a power of two, got {p}")
    k = p.bit_length() - 1
    kc = min(9, k)
    kb = min(9, k - kc)
    ka = k - kc - kb
    if ka > 9:
        raise ValueError(f"p={p} exceeds chunked-kernel limit {MAX_P}")
    return 1 << ka, 1 << kb, 1 << kc


# Mosaic's default scoped-VMEM limit on v5e is 16 MiB; every kernel in this
# package plans its tiles against 12 MiB of it and raises no limit.
VMEM_BUDGET = 12 << 20

# Every kernel dot in this package contracts at full f32 precision. Mosaic's
# default runs an f32 × f32 dot as one bf16 pass, which put the kernels 2e-3
# to 3e-3 (max-abs relative) off their f32 oracles on a v5e; the Hadamard
# factors' ±1/√b entries are not bf16 numbers.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def tile_bytes_per_row(p: int, dtype=jnp.float32) -> int:
    """VMEM one row of the single-tile kernel's (block_rows, p) tile costs.

    - the input and output blocks, each double-buffered by the pipeline:
      4·p·itemsize;
    - the Kronecker intermediates, ``width`` elements a row: p for a = 1,
      else b·pad128(a), because the outer-factor contraction's a-wide minor
      axis is padded to 128 lanes (16× at p = 1024). At full f32
      contraction precision (MXU_PRECISION) Mosaic
      holds several bf16 parts of each; the smallest ``vmem_limit_bytes``
      each tile compiles under for a described v5e (p = 256…32768, 8…64
      rows) comes to at most 21.4 bytes per intermediate element, modelled
      as 22.
    """
    a, b = factor_p(p)
    width = p if a == 1 else b * max(a, 128)
    return 4 * p * jnp.dtype(dtype).itemsize + 22 * width


def default_block_rows(p: int, dtype=jnp.float32, extra_bytes_per_row: int = 0) -> int:
    """Rows per tile (a power of two in [8, 256]) so the tile fits VMEM_BUDGET.

    ``extra_bytes_per_row`` is what a kernel sharing this body adds per row
    (kernels.sketch_fused).
    """
    per_row = tile_bytes_per_row(p, dtype) + extra_bytes_per_row
    br = max(8, VMEM_BUDGET // per_row)
    return int(min(256, 1 << int(np.floor(np.log2(br)))))


def hd_tile(x, d_ref, ha_ref, hb_ref, *, a: int, b: int):
    """H·(d ⊙ x) of a (rows, a·b) tile as Kronecker-factored MXU dots (f32)."""
    x = x * d_ref[...]                                       # sign flip (D), fused
    bn = x.shape[0]
    f32 = jnp.float32
    if a == 1:
        return jax.lax.dot(x, hb_ref[...], precision=MXU_PRECISION,
                           preferred_element_type=f32)
    # inner factor: contract the trailing b axis with H_b
    y = jax.lax.dot(x.reshape(bn * a, b), hb_ref[...], precision=MXU_PRECISION,
                    preferred_element_type=f32)
    # outer factor: contract the a axis with H_a
    y = y.reshape(bn, a, b).transpose(0, 2, 1).reshape(bn * b, a)
    y = jax.lax.dot(y, ha_ref[...], precision=MXU_PRECISION,
                    preferred_element_type=f32)
    return y.reshape(bn, b, a).transpose(0, 2, 1).reshape(bn, a * b)


def _kernel(x_ref, d_ref, ha_ref, hb_ref, o_ref, *, a: int, b: int):
    o_ref[...] = hd_tile(x_ref[...], d_ref, ha_ref, hb_ref, a=a, b=b).astype(o_ref.dtype)


# ---------------------------------------------------- chunked three-pass ----

def _pass_kernel(x_ref, h_ref, o_ref):
    o_ref[...] = jax.lax.dot(
        x_ref[...], h_ref[...], precision=MXU_PRECISION,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pass_signs_kernel(x_ref, s_ref, h_ref, o_ref):
    o_ref[...] = jax.lax.dot(
        x_ref[...] * s_ref[...], h_ref[...], precision=MXU_PRECISION,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _factor_pass(z: jax.Array, h: jax.Array, block_rows: int, interpret: bool,
                 signs2d: jax.Array | None = None) -> jax.Array:
    """One Kronecker-factor contraction: (R, f) @ H_f in (block_rows, f) chunks.

    ``signs2d`` (rows_per_cycle, f), when given, is the D diagonal reshaped so
    that the sign row for global row r is r mod rows_per_cycle; block_rows must
    divide rows_per_cycle for the modular BlockSpec below to tile it exactly
    (guaranteed by the power-of-two choices in :func:`hd_precondition_chunked`).
    """
    rows, f = z.shape
    if rows % block_rows:
        raise ValueError(f"block_rows={block_rows} must divide the pass row count {rows}")
    if signs2d is not None and signs2d.shape[0] % block_rows:
        raise ValueError(
            f"block_rows={block_rows} must divide the sign cycle {signs2d.shape[0]}")
    grid = (rows // block_rows,)
    out_shape = jax.ShapeDtypeStruct(z.shape, z.dtype)
    io_spec = pl.BlockSpec((block_rows, f), lambda i: (i, 0))
    h_spec = pl.BlockSpec((f, f), lambda i: (0, 0))
    if signs2d is None:
        return pl.pallas_call(
            _pass_kernel, grid=grid, in_specs=[io_spec, h_spec],
            out_specs=io_spec, out_shape=out_shape, interpret=interpret,
        )(z, h)
    n_sign_blocks = signs2d.shape[0] // block_rows
    sign_spec = pl.BlockSpec((block_rows, f), lambda i: (i % n_sign_blocks, 0))
    return pl.pallas_call(
        _pass_signs_kernel, grid=grid, in_specs=[io_spec, sign_spec, h_spec],
        out_specs=io_spec, out_shape=out_shape, interpret=interpret,
    )(z, signs2d, h)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def hd_precondition_chunked(x: jax.Array, signs: jax.Array,
                            block_rows: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """y = H·(signs ⊙ x) for p > 2^15 via the chunked H_a ⊗ H_b ⊗ H_c schedule.

    Three passes over the data, each a tiled small-f matmul whose working set is
    (block_rows, f) ≤ (256, 512) regardless of p; the D sign flip rides the
    first pass. Exact (up to f32 rounding) for any power of two p ≤ 2^27.
    ``block_rows``, when given, caps the per-pass tile height and must be a
    power of two (each pass validates divisibility against its row count).
    """
    n, p = x.shape
    a, b, c = factor_p3(p)
    dt = x.dtype
    ab = a * b
    cap = block_rows or 256

    # pass 1 — contract c, signs fused. Rows of the (n·a·b, c) view cycle
    # through sign rows with period a·b, so br | a·b keeps sign blocks exact.
    br1 = min(cap, ab)
    z = _factor_pass(x.reshape(n * ab, c), hadamard_matrix(c, dt), br1,
                     interpret, signs2d=signs.astype(dt).reshape(ab, c))

    # pass 2 — contract b (bring it to the lane axis, contract, restore).
    if b > 1:
        z = z.reshape(n, a, b, c).transpose(0, 1, 3, 2).reshape(n * a * c, b)
        z = _factor_pass(z, hadamard_matrix(b, dt), min(cap, a * c), interpret)
        z = z.reshape(n, a, c, b).transpose(0, 1, 3, 2)

    # pass 3 — contract the outer factor a (identity when a == 1).
    if a > 1:
        z = z.reshape(n, a, b * c).transpose(0, 2, 1).reshape(n * b * c, a)
        z = _factor_pass(z, hadamard_matrix(a, dt), min(cap, b * c), interpret)
        z = z.reshape(n, b * c, a).transpose(0, 2, 1)

    return z.reshape(n, p)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def hd_precondition(x: jax.Array, signs: jax.Array, block_rows: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """y = H·(signs ⊙ x) along the last axis. x: (n, p), p a power of two ≤ 2^27.

    Dispatches to the single-tile two-factor kernel for p ≤ 2^15 and to the
    chunked three-pass schedule above it.
    """
    n, p = x.shape
    if p > MAX_P:
        raise ValueError(f"p={p} exceeds chunked kernel limit {MAX_P}")
    if p > MAX_P_SINGLE:
        return hd_precondition_chunked(x, signs, block_rows=block_rows, interpret=interpret)
    a, b = factor_p(p)
    br = block_rows or default_block_rows(p, x.dtype)
    n_pad = -n % br
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    ha = hadamard_matrix(a, x.dtype) if a > 1 else jnp.zeros((1, 1), x.dtype)
    hb = hadamard_matrix(b, x.dtype)
    d2 = signs.astype(x.dtype)[None, :]

    out = pl.pallas_call(
        functools.partial(_kernel, a=a, b=b),
        grid=((n + n_pad) // br,),
        in_specs=[
            pl.BlockSpec((br, p), lambda i: (i, 0)),
            pl.BlockSpec((1, p), lambda i: (0, 0)),
            pl.BlockSpec((max(a, 1), max(a, 1)), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((n + n_pad), p), x.dtype),
        interpret=interpret,
    )(x, d2, ha, hb)
    return out[:n] if n_pad else out
