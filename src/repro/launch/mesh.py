"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes: the compiler propagates shardings.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every reshape
    of a sharded array must name its output sharding; the code here is written
    for propagated shardings, so every mesh in the repo is built through this.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips across two pods.

    Uses the first prod(shape) devices so a 512-device dry-run process can
    build both meshes.
    """
    import numpy as np

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    return auto_mesh(shape, axes, devices=jax.devices()[:n])


def make_host_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh over forced host devices — used by multi-device CPU tests."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis_of(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None
