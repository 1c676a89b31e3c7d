"""Public jit'd wrappers for the Pallas kernels, with backend auto-selection.

Modes: ``kernel`` (compiled Pallas, TPU only), ``interpret`` (the Pallas
interpreter, any backend) and ``ref`` (the jnp oracles of kernels.ref).
``auto`` resolves to ``kernel`` on a TPU and to ``ref`` elsewhere. Call sites
in core/ go through these wrappers so the backend choice is one switch, and
every resolved choice is counted (``kernels.dispatch{op=,path=}``): a run on
the chip that shows any ``ref`` or ``interpret`` dispatch did not run the
kernels. An explicit ``kernel`` request that the kernel cannot serve raises;
nothing is demoted silently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import fwht as _fwht
from repro.kernels import ref as _ref
from repro.kernels import sketch_fused as _sf
from repro.kernels import sparse_assign as _sa
from repro.kernels import spmm as _spmm


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _count_dispatch(op: str, path: str) -> None:
    """Tally a resolved backend choice as ``kernels.dispatch{op=,path=}``.

    The wrappers below run at JAX trace time, not per device step, so this is
    a handful of counter bumps per compilation — cheap enough to be always-on.
    Watch the ``path="ref"`` series to catch silent demotions to the jnp
    fallback (e.g. a VMEM-gate regression) that would otherwise only show up
    as a perf cliff.
    """
    obs.default_registry().counter("kernels.dispatch", op=op, path=path).inc()


def hd_precondition(x: jax.Array, signs: jax.Array, mode: str = "auto") -> jax.Array:
    """Fused y = H(d⊙x). mode ∈ {auto, kernel, interpret, ref}."""
    if mode == "auto":
        mode = "kernel" if _on_tpu() else "ref"
    _count_dispatch("hd_precondition", mode)
    if mode == "ref":
        return _ref.ref_hd_precondition(x, signs)
    return _fwht.hd_precondition(x, signs, interpret=(mode == "interpret"))


def sparse_assign(values: jax.Array, indices: jax.Array, centers: jax.Array, mode: str = "auto"):
    """(dists, argmin) for sparsified K-means assignment."""
    if mode == "auto":
        mode = "kernel" if _on_tpu() else "ref"
    _count_dispatch("sparse_assign", mode)
    if mode == "ref":
        return _ref.ref_sparse_assign(values, indices, centers)
    return _sa.sparse_assign(values, indices, centers, interpret=(mode == "interpret"))


# The spmm kernels tile BOTH grid axes (row blocks × column blocks —
# kernels/spmm.py), so their VMEM footprint is bounded by plan_tiles against
# this budget at ANY p. The budget is the tile planner's own input
# (spmm.SPMM_VMEM_BUDGET, the package's one VMEM budget) so the dispatch
# gate and the planner can never disagree. The corners the kernels cannot serve — even the
# minimum (8, 256) tile over the budget (an extremely wide l), or m too wide
# for an 8-row SMEM index window — raise under an explicit "kernel" and fall
# to "ref" under "auto", where the dispatch counter shows it.
_SPMM_VMEM_BUDGET = _spmm.SPMM_VMEM_BUDGET


def _sparse_mode(mode: str, p: int, ell: int,
                 value_dtype=jnp.float32, dense_dtype=jnp.float32,
                 m: int | None = None) -> str:
    """Normalize a backend name to this module's vocabulary.

    Call sites forward ``Plan.impl`` / ``StreamEngine.impl`` here verbatim, and
    that knob speaks the Hadamard vocabulary where the jnp reference is spelled
    "jnp" — map it (and any other non-kernel spelling) to "ref" rather than
    falling through to a Pallas compile that CPU hosts reject. The VMEM check
    uses the ONE tile model (spmm.plan_tiles / tile_vmem_bytes) at the actual
    operand dtypes — no second, disagreeing footprint estimate lives here.
    """
    explicit = mode == "kernel"
    if mode == "auto":
        mode = "kernel" if _on_tpu() else "ref"
    if mode not in ("kernel", "interpret"):
        return "ref"
    if mode == "interpret":  # host interpreter: no VMEM constraint to respect
        return mode
    try:
        br, pb = _spmm.plan_tiles(p, ell, value_dtype, dense_dtype, m=m)
    except ValueError:
        if explicit:
            raise
        return "ref"
    vmem = _spmm.tile_vmem_bytes(p, ell, value_dtype, dense_dtype, br, pb)
    if vmem <= _SPMM_VMEM_BUDGET:
        return "kernel"
    if explicit:
        raise ValueError(
            f"spmm kernel cannot tile p={p}, l={ell} within the "
            f"{_SPMM_VMEM_BUDGET}-byte VMEM budget ({vmem} bytes at the "
            f"smallest tile); use mode='ref'")
    return "ref"


def spmm(values: jax.Array, indices: jax.Array, dense: jax.Array,
         mode: str = "auto") -> jax.Array:
    """T (n, l) = W @ dense for compact sparse rows (the low-rank projection)."""
    mode = _sparse_mode(mode, *dense.shape, values.dtype, dense.dtype, m=values.shape[1])
    _count_dispatch("spmm", mode)
    if mode == "ref":
        return _ref.ref_spmm(values, indices, dense)
    return _spmm.spmm(values, indices, dense, interpret=(mode == "interpret"))


def spmm_t(values: jax.Array, indices: jax.Array, t: jax.Array, p: int,
           mode: str = "auto") -> jax.Array:
    """Y (p, l) = Wᵀ @ t — scatter sparse rows into the l-dim sketch."""
    mode = _sparse_mode(mode, p, t.shape[1], values.dtype, t.dtype, m=values.shape[1])
    _count_dispatch("spmm_t", mode)
    if mode == "ref":
        return _ref.ref_spmm_t(values, indices, t, p)
    return _spmm.spmm_t(values, indices, t, p, interpret=(mode == "interpret"))


def sketch_fused(x: jax.Array, signs: jax.Array, indices: jax.Array,
                 mode: str = "auto") -> jax.Array:
    """values (n, m) = (H·(signs⊙x))[i, indices[i]] — the full compression
    operator's value pass in one VMEM round trip (kernels.sketch_fused).

    Above the fused kernel's single-tile ceiling (p > 2^15) the kernel modes
    compose the chunked FWHT with an XLA gather — still the kernel FWHT path,
    just not single-pass (counted as path="kernel_chunked").
    """
    if mode == "auto":
        mode = "kernel" if _on_tpu() else "ref"
    if mode in ("kernel", "interpret"):
        if x.shape[-1] <= _sf.MAX_P_FUSED:
            _count_dispatch("sketch_fused", mode)
            return _sf.sketch_fused(x, signs, indices,
                                    interpret=(mode == "interpret"))
        _count_dispatch("sketch_fused", f"{mode}_chunked")
        y = hd_precondition(x, signs, mode=mode)
        return jnp.take_along_axis(y, indices, axis=-1)
    _count_dispatch("sketch_fused", mode)
    return _ref.ref_sketch_fused(x, signs, indices)


def kernel_assign_fn(mode: str = "auto"):
    """Adapter matching core.kmeans assign_fn signature (returns distances only)."""

    def fn(values, indices, centers):
        d, _ = sparse_assign(values, indices, centers, mode=mode)
        return d

    return fn
