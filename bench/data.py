"""The host pool of a cell's rows, drawn on the device from the seed.

A configuration's ``data`` object names a ``kind``, a file
``bench/rows/<kind>.py`` whose ``block(keys, n, d)`` is one jitted call
returning ``(n, width(d))`` float32 rows. The pool is drawn one block at a
time and copied into one host array, which the feed cycles through.
Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed up to 64 bits (seeds may pass 2**32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _kind(d: dict):
    from bench import harness as H

    return H.rows(d["kind"])


def width(d: dict) -> int:
    """Columns of the rows a ``data`` object describes."""
    return _kind(d).width(d)


def pool(seed: int, d: dict, n_blocks: int, block_rows: int) -> np.ndarray:
    """(n_blocks·block_rows, p) float32 host rows, one device call per block.

    Each block's keys are (the data key, its own key), so a kind may draw
    what every block shares from the first and the rows from the second."""
    gen = _kind(d)
    data_key = jax.random.fold_in(root_key(seed), 7)
    out = np.empty((n_blocks * block_rows, width(d)), np.float32)
    for b in range(n_blocks):
        keys = jnp.stack([data_key, jax.random.fold_in(data_key, 1000 + b)])
        rows = gen.block(keys, block_rows, d)
        out[b * block_rows:(b + 1) * block_rows] = np.asarray(rows)
        del rows
    return out
