"""Randomized orthonormal system (ROS) preconditioning: x -> y = H D x  (paper Eq. 1).

``H`` is a fast orthonormal transform (normalized Walsh-Hadamard or orthonormal
DCT-II) and ``D`` a random ±1 diagonal. ``HD`` is orthonormal, so the adjoint
``D Hᵀ`` exactly unmixes. Applying H costs O(p log p) and is embarrassingly
parallel across samples.

Data convention: **rows are samples** — ``X`` has shape ``(n, p)`` (the paper
uses columns; everything here is the transpose of the paper's notation).

Hadamard requires p a power of two; :func:`pad_len` gives the padded length and
:func:`precondition` zero-pads internally (zero-padding then applying an
orthonormal transform is itself an isometry on the embedded data, so all the
paper's guarantees hold with p replaced by p_pad).

The TPU-optimized path lives in ``repro.kernels.fwht`` (Kronecker-factored MXU
form); this module is the reference implementation used on CPU and as the
kernels' oracle.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.prng import rademacher

Transform = Literal["hadamard", "dct"]

# η in Thm. 1 / Cor. 2-3: Hadamard has the sharper sub-gaussian constant.
ETA = {"hadamard": 1.0, "dct": 0.5}


def pad_len(p: int, transform: Transform = "hadamard") -> int:
    """Length after padding: next power of two for Hadamard, identity for DCT."""
    if transform == "dct":
        return p
    return 1 << max(0, (p - 1).bit_length())


def fwht(x: jax.Array) -> jax.Array:
    """Normalized fast Walsh-Hadamard transform along the last axis.

    Iterative radix-2 butterfly, O(p log p). Requires p a power of two.
    Self-inverse (H = Hᵀ = H⁻¹ after 1/√p normalization).
    """
    p = x.shape[-1]
    if p & (p - 1):
        raise ValueError(f"FWHT needs a power-of-two length, got {p}")
    orig_shape = x.shape
    x = x.reshape(-1, p)
    h = 1
    while h < p:
        x = x.reshape(-1, p // (2 * h), 2, h)
        a = x[:, :, 0, :]
        b = x[:, :, 1, :]
        x = jnp.stack([a + b, a - b], axis=2)
        h *= 2
    x = x.reshape(orig_shape)
    return x * (1.0 / np.sqrt(p)).astype(x.dtype)


def _dct_ii_ortho(x: jax.Array) -> jax.Array:
    """Orthonormal DCT-II along the last axis via a single length-p FFT.

    Uses the even/odd reordering trick (Makhoul): v = [x_0, x_2, ..., x_3, x_1],
    X_k = Re(e^{-iπk/2p} FFT(v)_k), then orthonormal scaling.
    """
    p = x.shape[-1]
    v = jnp.concatenate([x[..., ::2], x[..., 1::2][..., ::-1]], axis=-1)
    V = jnp.fft.fft(v.astype(jnp.float32), axis=-1)
    k = jnp.arange(p)
    phase = jnp.exp(-1j * jnp.pi * k / (2 * p))
    y = 2.0 * jnp.real(phase * V)
    scale = jnp.full((p,), np.sqrt(1.0 / (2 * p)), dtype=jnp.float32).at[0].set(np.sqrt(1.0 / (4 * p)))
    return (y * scale).astype(x.dtype)


def _dct_iii_ortho(x: jax.Array) -> jax.Array:
    """Orthonormal DCT-III (inverse of orthonormal DCT-II) along the last axis.

    Reconstructs the length-p FFT of the reordered sequence from the DCT
    coefficients using Hermitian symmetry (W_{p−k} = −i·conj(W_k)), then inverts.
    """
    p = x.shape[-1]
    k = jnp.arange(p)
    scale = jnp.full((p,), np.sqrt(1.0 / (2 * p)), dtype=jnp.float32).at[0].set(np.sqrt(1.0 / (4 * p)))
    Y = x.astype(jnp.float32) / (2.0 * scale)                 # Re(e^{-iπk/2p} V_k)
    im = -jnp.concatenate([jnp.zeros_like(Y[..., :1]), Y[..., :0:-1]], axis=-1)
    V = jnp.exp(1j * jnp.pi * k / (2 * p)) * (Y + 1j * im)
    v = jnp.real(jnp.fft.ifft(V, axis=-1))
    # undo the even/odd reordering
    out = jnp.zeros_like(v)
    half = (p + 1) // 2
    out = out.at[..., ::2].set(v[..., :half])
    out = out.at[..., 1::2].set(v[..., half:][..., ::-1])
    return out.astype(x.dtype)


def apply_h(x: jax.Array, transform: Transform = "hadamard", adjoint: bool = False) -> jax.Array:
    """Apply the deterministic orthonormal H (or Hᵀ) along the last axis."""
    if transform == "hadamard":
        return fwht(x)  # symmetric & self-inverse
    if adjoint:
        return _dct_iii_ortho(x)
    return _dct_ii_ortho(x)


def signs_for(key: jax.Array, p_padded: int, dtype=jnp.float32) -> jax.Array:
    """The diagonal of D — derived deterministically from ``key``."""
    return rademacher(key, (p_padded,), dtype=dtype)


def resolve_impl(impl: str) -> str:
    """Resolve the "auto" Hadamard backend: Pallas kernel on TPU, jnp elsewhere.

    The single policy point shared by :func:`precondition` and sketch.sketch.
    """
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "jnp"
    return impl


@functools.partial(jax.jit, static_argnames=("transform", "p_orig", "impl"))
def precondition(x: jax.Array, key: jax.Array, transform: Transform = "hadamard",
                 p_orig: int | None = None, impl: str = "jnp") -> jax.Array:
    """y = H D x along the last axis, zero-padding to the transform length.

    ``x``: (..., p). Returns (..., p_pad).

    ``impl`` selects the Hadamard backend: ``"jnp"`` (butterfly reference),
    ``"kernel"`` / ``"interpret"`` (the Pallas MXU kernel, chunked three-pass
    above p = 2^15 — see repro.kernels.fwht), or ``"auto"`` (kernel on TPU,
    jnp elsewhere). Non-Hadamard transforms always use the jnp path.
    """
    p = p_orig if p_orig is not None else x.shape[-1]
    pp = pad_len(p, transform)
    if x.shape[-1] < pp:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, pp - x.shape[-1])]
        x = jnp.pad(x, pad)
    d = signs_for(key, pp, dtype=x.dtype)
    impl = resolve_impl(impl)
    if impl != "jnp" and transform == "hadamard":
        from repro.kernels import ops as kops  # deferred: kernels import this module

        lead = x.shape[:-1]
        y = kops.hd_precondition(x.reshape(-1, pp), d, mode=impl)
        return y.reshape(*lead, pp)
    return apply_h(x * d, transform)


@functools.partial(jax.jit, static_argnames=("transform", "p_orig"))
def unmix(y: jax.Array, key: jax.Array, transform: Transform = "hadamard", p_orig: int | None = None) -> jax.Array:
    """x = D Hᵀ y — exact inverse of :func:`precondition` (drops any padding)."""
    pp = y.shape[-1]
    d = signs_for(key, pp, dtype=y.dtype)
    x = apply_h(y, transform, adjoint=True) * d
    if p_orig is not None and p_orig < pp:
        x = x[..., :p_orig]
    return x


def hadamard_matrix(p: int, dtype=jnp.float32) -> jax.Array:
    """Dense normalized Hadamard matrix (tests / small-p fallback only)."""
    if p & (p - 1):
        raise ValueError(f"p must be a power of two, got {p}")
    h = np.array([[1.0]])
    while h.shape[0] < p:
        h = np.block([[h, h], [h, -h]])
    return jnp.asarray(h / np.sqrt(p), dtype=dtype)
