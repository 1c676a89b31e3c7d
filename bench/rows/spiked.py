"""Rows of kind ``spiked``: Johnstone's spiked covariance model.

``n_spikes`` orthonormal directions (drawn once from the data key, so every
block shares one spectrum) with variances evenly spaced from ``lam_hi``
down to ``lam_lo``, over isotropic Gaussian noise of scale ``noise``, in
``p`` columns.
"""
import functools

import jax
import jax.numpy as jnp


def width(d: dict) -> int:
    return int(d["p"])


def block(keys, n: int, d: dict):
    return _rows(keys, n, int(d["p"]), int(d["n_spikes"]), float(d["lam_hi"]),
                 float(d["lam_lo"]), float(d["noise"]))


@functools.partial(jax.jit, static_argnames=("n", "p", "n_spikes"))
def _rows(keys, n, p, n_spikes, lam_hi, lam_lo, noise):
    data_key, block_key = keys[0], keys[1]
    u, _ = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(data_key, 0), (p, n_spikes)))
    lam = jnp.linspace(lam_hi, lam_lo, n_spikes)
    z = jax.random.normal(jax.random.fold_in(block_key, 1), (n, n_spikes)) * jnp.sqrt(lam)
    eps = jax.random.normal(jax.random.fold_in(block_key, 2), (n, p))
    return jnp.dot(z, u.T, precision="highest") + noise * eps
