"""Fused one-pass sketch kernel: y = gather_m( H·(d ⊙ x) ) — the paper's full
compression operator in a single VMEM round trip.

Composition of the two stages (fwht kernel then an XLA gather) writes the dense
preconditioned tile back to HBM only to re-read γ of it. Fusing keeps the
dense intermediate in VMEM and writes ONLY the m kept values per row — HBM
traffic drops from (2 + γ)·n·p·4 bytes to (1 + 2γ)·n·p·4, i.e. ~2.5× for
γ = 0.05 on the streaming-ingest path (the paper's Tables III/IV setting).

The FWHT stays on the MXU via the Kronecker form (kernels.fwht.hd_tile); the
per-row gather walks the indices as SMEM scalars and reads each kept value
out of its aligned VMEM tile (kernels.walk). Every power of two p from 2^8 to
2^15 compiles for v5e; above that kernels.ops composes the chunked FWHT with
an XLA gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ros import hadamard_matrix
from repro.kernels import walk
from repro.kernels.fwht import MAX_P_SINGLE, default_block_rows, factor_p, hd_tile

# The fused kernel holds a whole (block_rows, p) preconditioned tile in VMEM,
# so it shares the single-tile FWHT ceiling; above it, kernels.ops composes
# the chunked FWHT with an XLA gather instead.
MAX_P_FUSED = MAX_P_SINGLE


def _kernel(x_ref, d_ref, ha_ref, hb_ref, idx_ref, out_ref, y_ref, *,
            a: int, b: int, m: int):
    bn, p = x_ref.shape
    y_ref[:, :p] = hd_tile(x_ref[...], d_ref, ha_ref, hb_ref, a=a, b=b)
    walk.gather(out_ref, y_ref, idx_ref, bn=bn, m=m)


def fused_block_rows(p: int, m: int, dtype=jnp.float32) -> int:
    """Rows per tile: the FWHT tile model, minus its (rows, p) output block,
    plus the f32 copy the gather reads and the double-buffered (rows, m)
    f32 output block; capped by the SMEM index window."""
    extra = (walk.pad_lanes(p) * 4 + 2 * walk.pad_lanes(m) * 4
             - 2 * p * jnp.dtype(dtype).itemsize)
    return min(default_block_rows(p, dtype, extra_bytes_per_row=extra),
               walk.smem_block_rows(m, n_windows=1))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sketch_fused(x: jax.Array, signs: jax.Array, indices: jax.Array,
                 block_rows: int | None = None, interpret: bool = False) -> jax.Array:
    """values (n, m) = (H·(signs⊙x))[i, indices[i]] — fused precondition+sample.

    x (n, p) with p a power of two ≤ MAX_P_FUSED; indices (n, m) int32
    (sorted, distinct). Dispatch through kernels.ops.sketch_fused to get the
    composed chunked-FWHT + gather path above the ceiling.
    """
    n, p = x.shape
    m = indices.shape[1]
    if p > MAX_P_FUSED:
        raise ValueError(
            f"p={p} exceeds the fused kernel's single-tile ceiling "
            f"{MAX_P_FUSED}; use kernels.ops.sketch_fused (composed path)")
    a, b = factor_p(p)
    br = block_rows or fused_block_rows(p, m, x.dtype)
    n_pad = -n % br
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
        indices = jnp.pad(indices, ((0, n_pad), (0, 0)))
    ha = hadamard_matrix(a, x.dtype) if a > 1 else jnp.zeros((1, 1), x.dtype)
    hb = hadamard_matrix(b, x.dtype)
    d2 = signs.astype(x.dtype)[None, :]
    mp = walk.pad_lanes(m)

    out = pl.pallas_call(
        functools.partial(_kernel, a=a, b=b, m=m),
        grid=((n + n_pad) // br,),
        in_specs=[
            pl.BlockSpec((br, p), lambda i: (i, 0)),
            pl.BlockSpec((1, p), lambda i: (0, 0)),
            pl.BlockSpec((max(a, 1), max(a, 1)), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
            walk.smem_spec(br, m, lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, mp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, mp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, walk.pad_lanes(p)), jnp.float32)],
        interpret=interpret,
    )(x, d2, ha, hb, indices.astype(jnp.int32))
    return out[:n, :m].astype(x.dtype)
