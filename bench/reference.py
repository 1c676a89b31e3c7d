"""The plain reference of the sketch-ingest job, and its lower-precision control.

It follows the paper's operator and the program's documented randomness,
and imports nothing of the program:

- preconditioning y = H·D·x / sqrt(p_pad), with D = Rademacher signs under
  ``fold_in_str(key, "ros-signs")`` and H applied as two dense ±1 Hadamard
  factors (H_a ⊗ H_b), i.e. as matrix products;
- per chunk (step, shard), m of p_pad coordinates kept uniformly without
  replacement: the top-m of uniforms drawn under
  ``fold_in(fold_in(fold_in_str(key, "sample-mask"), step), shard)``;
- the folds written out from their definitions on the densified chunk W:
  the range state Y += Wᵀ(W·Ω) with Ω ~ N(0, 1) under
  ``fold_in_str(key, "lowrank-omega")``, diag += Σ w∘w, sum_w += Σ w;
  the second moment S += WᵀW; minibatch K-means (K-means++ seeding on the
  first chunk, per-coordinate running means, best of n_init hypotheses);
- finalizes in float64 on the host with numpy.

A precision says how matrix products run: ``highest`` (full f32),
``default`` (the device's default for f32, one bf16 pass on a TPU),
``high`` (three bf16 passes) and ``bf16`` (bf16 operands and a bf16
result, and the K-means distances rounded to bf16 as well); the last two
are written out with explicit roundings, so that they mean the same on any
device. A configuration states one for the sketch's transform and one for
the folds, as its program runs them; the control runs each one step below.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BELOW = {"highest": "high", "default": "bf16"}   # stated precision → control


def fold_in_str(key, tag: str):
    h = int.from_bytes(hashlib.sha256(tag.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


def round_bf16(x):
    """x rounded to the nearest bfloat16 (ties to even), kept in float32.

    Written with integer operations on the bits, so that no compiler may
    drop the rounding as excess precision (XLA on a TPU folds a
    float32 → bfloat16 → float32 round trip away)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(b & jnp.uint32(0xFFFF0000), F32)


def _exact(a, b):
    """a @ b of bfloat16-valued float32 operands: exact products, f32 sums."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def dot(a, b, precision: str):
    """a @ b at one of the four precisions (see the module docstring)."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "default":
        return jnp.matmul(a, b)
    if precision == "bf16":
        return round_bf16(_exact(round_bf16(a), round_bf16(b)))
    if precision == "high":
        a1, b1 = round_bf16(a), round_bf16(b)
        a2, b2 = round_bf16(a - a1), round_bf16(b - b1)
        return _exact(a1, b1) + (_exact(a1, b2) + _exact(a2, b1))
    raise ValueError(f"unknown precision {precision!r}")


def pad_len(p: int) -> int:
    return 1 << max(0, (p - 1).bit_length())


def hadamard_pm1(n: int) -> np.ndarray:
    """Sylvester's ±1 Hadamard matrix of order n (a power of two)."""
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


def hadamard(x, precision: str):
    """Normalized Walsh-Hadamard transform of the rows of x (n, 2^k) as the
    two matrix products H_a·X·H_b of its Kronecker factors."""
    n, p = x.shape
    k = p.bit_length() - 1
    a, b = 1 << ((k + 1) // 2), 1 << (k // 2)
    ha, hb = jnp.asarray(hadamard_pm1(a)), jnp.asarray(hadamard_pm1(b))
    z = dot(x.reshape(n * a, b), hb, precision).reshape(n, a, b)
    z = dot(z.transpose(0, 2, 1).reshape(n * b, a), ha, precision)
    z = z.reshape(n, b, a).transpose(0, 2, 1).reshape(n, p)
    return z * np.float32(1.0 / np.sqrt(p))


# ------------------------------------------------------------------ keys --


@dataclasses.dataclass(frozen=True)
class Keys:
    """The job's randomness, derived from the estimators' shared key."""

    root: jax.Array

    def signs(self, p_pad: int):
        return jax.random.rademacher(fold_in_str(self.root, "ros-signs"), (p_pad,),
                                     dtype=F32)

    def mask(self, step: int, shard: int):
        mk = fold_in_str(self.root, "sample-mask")
        return jax.random.fold_in(jax.random.fold_in(mk, step), shard)

    def omega(self, p_pad: int, ell: int):
        return jax.random.normal(fold_in_str(self.root, "lowrank-omega"), (p_pad, ell), F32)

    def kmeans(self):
        return fold_in_str(self.root, "api-kmeans")


# ---------------------------------------------------------------- sketch --


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def sketch(x, signs, mask_key, m: int, precision: str):
    """(values (n, m) f32, indices (n, m) int32) of one chunk of rows."""
    n, p = x.shape
    pp = signs.shape[0]
    x = jnp.pad(x.astype(F32), ((0, 0), (0, pp - p)))
    y = hadamard(x * signs, precision)
    u = jax.random.uniform(mask_key, (n, pp))
    _, idx = jax.lax.top_k(u, m)
    idx = jnp.sort(idx.astype(jnp.int32), axis=-1)
    return jnp.take_along_axis(y, idx, axis=-1), idx


def densify(vals, idx, p_pad: int):
    n = vals.shape[0]
    return jnp.zeros((n, p_pad), F32).at[jnp.arange(n)[:, None], idx].set(vals)


# ----------------------------------------------------------------- folds --


@functools.partial(jax.jit, static_argnames=("precision",))
def range_fold(y, diag, sum_w, vals, idx, omega, precision: str):
    w = densify(vals, idx, omega.shape[0])
    t = dot(w, omega, precision)
    y = y + dot(w.T, t, precision)
    flat = idx.reshape(-1)
    diag = diag.at[flat].add((vals * vals).reshape(-1))
    sum_w = sum_w.at[flat].add(vals.reshape(-1))
    return y, diag, sum_w


@functools.partial(jax.jit, static_argnames=("p_pad", "precision"))
def moment_fold(s, sum_w, vals, idx, p_pad: int, precision: str):
    w = densify(vals, idx, p_pad)
    return s + dot(w.T, w, precision), sum_w.at[idx.reshape(-1)].add(vals.reshape(-1))


def _sq_dists(vals, idx, centers, precision: str):
    """(n, K) distances over each row's kept coordinates, ‖z − Rᵀμ‖²."""
    g = centers.T[idx]                                     # (n, m, K)
    v = vals[..., None]
    if precision == "bf16":
        return round_bf16(jnp.sum((round_bf16(v) - round_bf16(g)) ** 2, axis=1))
    return jnp.sum((v - g) ** 2, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "n_init", "p_pad"))
def kmeans_seed(key, vals, idx, k: int, n_init: int, p_pad: int):
    """K-means++ D² seeding with 2 + ceil(ln k) candidates a step, from the
    first chunk, once per hypothesis."""
    n = vals.shape[0]
    n_cand = 2 + int(np.ceil(np.log(max(k, 2))))

    def row(i):
        return jnp.zeros((p_pad,), F32).at[idx[i]].set(vals[i])

    def dist(c):
        return jnp.sum((vals - c[idx]) ** 2, axis=1)

    def one(key):
        k0, key = jax.random.split(key)
        first = row(jax.random.randint(k0, (), 0, n))
        centers = jnp.zeros((k, p_pad), F32).at[0].set(first)
        min_d = dist(first)
        for j in range(1, k):
            key, kc = jax.random.split(key)
            logits = jnp.log(jnp.maximum(min_d, 1e-30))
            cand = jax.random.categorical(kc, logits, shape=(n_cand,))
            rows = jax.vmap(row)(cand)
            new_d = jax.vmap(dist)(rows)
            best = jnp.argmin(jnp.sum(jnp.minimum(min_d[None], new_d), axis=1))
            centers = centers.at[j].set(rows[best])
            min_d = jnp.minimum(min_d, new_d[best])
        return centers

    return jax.vmap(one)(jax.random.split(key, n_init))


@functools.partial(jax.jit, static_argnames=("precision",))
def kmeans_fold(centers, counts, obj, vals, idx, precision: str):
    """One step of every hypothesis: assign under the step-start centers,
    then move each touched coordinate to its running mean."""
    k, p_pad = centers.shape[1:]

    def one(c, cnt, o):
        d = _sq_dists(vals, idx, c, precision)
        a = jnp.argmin(d, axis=1)
        rows = jnp.broadcast_to(a[:, None], idx.shape)
        sums = jnp.zeros((k, p_pad), F32).at[rows, idx].add(vals)
        hits = jnp.zeros((k, p_pad), jnp.int32).at[rows, idx].add(1)
        new_cnt = cnt + hits
        moved = c + (sums - hits.astype(F32) * c) / jnp.maximum(new_cnt, 1).astype(F32)
        return jnp.where(hits > 0, moved, c), new_cnt, o + jnp.sum(jnp.min(d, axis=1))

    return jax.vmap(one)(centers, counts, obj)


# -------------------------------------------------------------- finalize --


def unmix(rows_pre: np.ndarray, signs: np.ndarray, p: int) -> np.ndarray:
    """D·Hᵀ applied to preconditioned-domain rows, cropped to p (float64)."""
    z = np.asarray(rows_pre, np.float64).copy()
    n, pp = z.shape
    h = 1
    while h < pp:
        z = z.reshape(n, pp // (2 * h), 2, h)
        z = np.stack([z[:, :, 0] + z[:, :, 1], z[:, :, 0] - z[:, :, 1]], axis=2)
        h *= 2
    z = z.reshape(n, pp) / np.sqrt(pp) * np.asarray(signs, np.float64)
    return z[:, :p]


def cov_scale(p_pad: int, m: int) -> float:
    return p_pad * (p_pad - 1) / (m * (m - 1))


def range_finalize(y, diag, count, omega, m: int, k: int, precision: str | None = None):
    """Top-k (eigenvalues, components_pre) of the debiased range state:
    basis = top l/2 left singular vectors, pseudo-inverse core, symmetrized.

    In float64 on the host; with ``precision``, in float32 on the device with
    every matrix product at that precision (the control's finalize)."""
    if precision is not None:
        ev, comps = _range_finalize_f32(jnp.asarray(y), jnp.asarray(diag), jnp.float32(count),
                                        jnp.asarray(omega), m, k, precision)
        return np.asarray(ev, np.float64), np.asarray(comps, np.float64)
    y, diag, omega = (np.asarray(a, np.float64) for a in (y, diag, omega))
    p, ell = y.shape
    corr = (p - m) / (p - 1)
    yp = (y - corr * diag[:, None] * omega) / float(count)
    u, _, _ = np.linalg.svd(yp, full_matrices=False)
    q = u[:, :max(1, ell // 2)]
    core = cov_scale(p, m) * (q.T @ yp) @ np.linalg.pinv(q.T @ omega)
    ev, vec = np.linalg.eigh(0.5 * (core + core.T))
    order = np.argsort(ev)[::-1][:k]
    return ev[order], (q @ vec[:, order]).T


@functools.partial(jax.jit, static_argnames=("m", "k", "precision"))
def _range_finalize_f32(y, diag, count, omega, m, k, precision):
    p, ell = y.shape
    corr = (p - m) / (p - 1)
    yp = (y - corr * diag[:, None] * omega) / count
    u, _, _ = jnp.linalg.svd(yp, full_matrices=False)
    q = u[:, :max(1, ell // 2)]
    core = cov_scale(p, m) * dot(dot(q.T, yp, precision),
                                 jnp.linalg.pinv(dot(q.T, omega, precision)), precision)
    ev, vec = jnp.linalg.eigh(0.5 * (core + core.T))
    order = jnp.argsort(ev)[::-1][:k]
    return ev[order], dot(q, vec[:, order], precision).T


def moment_finalize(s, count, m: int, k: int):
    """Top-k of the Thm-6 estimate scale/n·S − corr·diag(·) (float64)."""
    s = np.asarray(s, np.float64)
    p = s.shape[0]
    c = cov_scale(p, m) / float(count) * s
    c = c - (p - m) / (p - 1) * np.diag(np.diag(c))
    ev, vec = np.linalg.eigh(c)
    order = np.argsort(ev)[::-1][:k]
    return ev[order], vec[:, order].T
