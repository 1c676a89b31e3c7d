"""Rows of kind ``pixel_templates``: pixel-like rows on a side×side grid.

``n_classes`` stroke templates, each the union of ``blobs`` discs of
``radius`` pixels at random places, with intensities in [lo, 1] falling off
from the disc centres (about 150 of 784 pixels on at the MNIST shape). A
row is its class's template times a per-row gain in [0.7, 1], plus
Gaussian noise of scale ``noise`` on the template's own pixels, clipped to
[0, 1]: off pixels stay exactly 0, as in scanned digits, which is the
coherence preconditioning smooths.
"""
import functools

import jax
import jax.numpy as jnp


def width(d: dict) -> int:
    return int(d["side"]) ** 2


def block(keys, n: int, d: dict):
    return _rows(keys, n, int(d["side"]), int(d["n_classes"]), int(d["blobs"]),
                 float(d["radius"]), float(d["noise"]), float(d["lo"]))


@functools.partial(jax.jit, static_argnames=("n", "side", "n_classes", "blobs"))
def _rows(keys, n, side, n_classes, blobs, radius, noise, lo):
    data_key, block_key = keys[0], keys[1]
    yy, xx = jnp.meshgrid(jnp.arange(side), jnp.arange(side), indexing="ij")
    grid = jnp.stack([yy.reshape(-1), xx.reshape(-1)], -1).astype(jnp.float32)  # (P, 2)
    ctr = jax.random.uniform(jax.random.fold_in(data_key, 0), (n_classes, blobs, 2),
                             minval=radius, maxval=side - radius)
    d2 = ((grid[None, None] - ctr[:, :, None]) ** 2).sum(-1)                # (C, B, P)
    near = jnp.min(d2, axis=1)                                              # (C, P)
    on = near <= radius * radius
    tmpl = jnp.where(on, 1.0 - (1.0 - lo) * jnp.sqrt(near) / radius, 0.0)  # (C, P)
    labels = jax.random.randint(jax.random.fold_in(block_key, 1), (n,), 0, n_classes)
    gain = jax.random.uniform(jax.random.fold_in(block_key, 2), (n, 1), minval=0.7,
                              maxval=1.0)
    eps = noise * jax.random.normal(jax.random.fold_in(block_key, 3), (n, side * side))
    t = tmpl[labels]
    return jnp.clip(jnp.where(t > 0, t * gain + eps, 0.0), 0.0, 1.0)
