"""The execution :class:`Plan` — one config object selecting how a sketch job runs.

A Plan captures everything about *how* an estimator executes — backend
(in-memory batch, constant-memory streaming accumulators, or shard_map
collectives), kernel choice, batch geometry, mesh — and nothing about *what*
is estimated (that's the estimator class) or the randomness (that's the key
handed to ``fit``). Flipping ``backend`` re-runs the same job on a different
execution engine with tolerance-identical results, because every backend folds
the same per-(step, shard) sketches under the shared
:func:`repro.core.sketch.batch_key` discipline.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Literal

import jax

from repro.core import ros, sketch
from repro.launch.mesh import auto_mesh

Backend = Literal["batch", "stream", "sharded"]

BACKENDS: tuple[str, ...] = ("batch", "stream", "sharded")


@dataclasses.dataclass(frozen=True)
class Plan:
    """How a sketched-estimation job executes.

    backend:    "batch"   — sketch everything, then one-shot ``repro.core``
                            estimators on the concatenated sketch;
                "stream"  — fold per-batch accumulator deltas
                            (``repro.stream.accumulators``), constant memory
                            for the moment estimators;
                "sharded" — reduce via the ``repro.stream.sharded`` shard_map
                            collectives over ``mesh`` (one psum of the
                            fixed-size accumulator per reduction).
    gamma / m:  sketch size — fraction kept (validated to (0, 1]) or absolute
                coordinate count; exactly one is required.
    transform:  ROS preconditioner ("hadamard" or "dct").
    impl:       Hadamard kernel choice forwarded to ``ros.precondition``
                ("auto" = Pallas kernel on TPU, jnp butterfly elsewhere).
    batch_size: rows per (step, shard) batch. fit/partial_fit consume their
                input in consecutive chunks of this size; chunk j is keyed
                (step = j // n_shards, shard = j % n_shards), so every backend
                sees identical per-batch masks.
    n_shards:   logical shards per step (the shard axis of the key discipline).
    axis:       mesh axis name for the sharded backend.
    mesh:       jax Mesh for the sharded backend; None auto-builds a
                (n_shards,)-device mesh at first use.
    cov_path:   covariance delta path — "dense" (scatter to (b, p), one MXU
                matmul), "compact" (scatter b·m² outer products; the γ ≪ 1
                memory fix — no dense (b, p) intermediate), or "lowrank"
                (repro.lowrank: O(rank·p) spectral accumulators for PCA-only
                consumers — the (p, p) accumulator itself disappears).
    rank:       sketch width l of the low-rank path (required when
                cov_path="lowrank"; the finalized eigenmodel holds l/2
                eigenpairs under the default "range" method, all l under "fd").
    lowrank_method: "range" — randomized range-finder / co-occurrence state,
                linear so its (p, l) delta psums across shards (the default);
                "fd" — Frequent Directions, deterministic guarantee but a
                sequential (order-dependent) fold.
    refine_passes: default number of second-pass replay refinements for
                ``fit_refine`` / ``fit_many(refine=True)`` (repro.refine: PCA
                power iteration on the lowrank-range path, two-pass Alg.-2
                K-means for the minibatch fold). 0 = plain one-pass fits;
                ``fit_refine`` with no explicit ``passes`` then runs 1.
    dtype:      input rows are cast to this before sketching.
    """

    backend: Backend = "batch"
    gamma: float | None = None
    m: int | None = None
    transform: ros.Transform = "hadamard"
    impl: str = "auto"
    batch_size: int = 4096
    n_shards: int = 1
    axis: str = "data"
    mesh: Any | None = None
    cov_path: Literal["dense", "compact", "lowrank"] = "dense"
    rank: int | None = None
    lowrank_method: Literal["range", "fd"] = "range"
    refine_passes: int = 0
    dtype: Any = "float32"

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {self.backend!r}")
        if self.cov_path not in ("dense", "compact", "lowrank"):
            raise ValueError(
                f"cov_path must be 'dense', 'compact' or 'lowrank', got {self.cov_path!r}")
        if self.lowrank_method not in ("range", "fd"):
            raise ValueError(
                f"lowrank_method must be 'range' or 'fd', got {self.lowrank_method!r}")
        if self.cov_path == "lowrank":
            if self.rank is None or self.rank < 2:
                raise ValueError(
                    f"cov_path='lowrank' needs rank >= 2 (the l of the (l, p) "
                    f"sketch), got rank={self.rank}")
        elif self.rank is not None:
            raise ValueError("rank= only applies to cov_path='lowrank'")
        if self.refine_passes < 0:
            raise ValueError(f"refine_passes must be >= 0, got {self.refine_passes}")
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.mesh is not None and self.mesh.shape[self.axis] != self.n_shards:
            raise ValueError(
                f"mesh axis {self.axis!r} has size {self.mesh.shape[self.axis]}, "
                f"need n_shards={self.n_shards}")

    # ------------------------------------------------------------- helpers --

    def replace(self, **kw) -> "Plan":
        """A copy with fields overridden — e.g. ``plan.replace(backend="sharded")``."""
        return dataclasses.replace(self, **kw)

    def spec(self, p: int, key: jax.Array) -> sketch.SketchSpec:
        """The SketchSpec this plan induces at dimensionality ``p``."""
        return sketch.make_spec(p, key, gamma=self.gamma, m=self.m,
                                transform=self.transform)

    def resolve_mesh(self):
        """The mesh for the sharded backend (auto-built over n_shards devices).

        Auto-built meshes are cached per (n_shards, axis): repeated fits (and
        the per-step streaming reducer) then reuse one mesh object, so the
        compiled shard_map reductions keyed on it stay cached too.
        """
        if self.mesh is not None:
            return self.mesh
        if len(jax.devices()) < self.n_shards:
            raise ValueError(
                f"sharded backend needs {self.n_shards} devices for axis "
                f"{self.axis!r}, have {len(jax.devices())}; pass mesh= or lower n_shards")
        return _auto_mesh(self.n_shards, self.axis)

    def step_shard(self, chunk: int) -> tuple[int, int]:
        """Map a linear chunk index to its (step, shard) key coordinates."""
        return divmod(chunk, self.n_shards)


@functools.lru_cache(maxsize=None)
def _auto_mesh(n_shards: int, axis: str):
    return auto_mesh((n_shards,), (axis,))


# ----------------------------------------------------------- mesh (de)spec --
# A Mesh object is process-local (it holds live Device handles), but its
# GEOMETRY is not: (axis names, axis sizes) fully determine an equivalent
# mesh on any host with enough devices. Snapshots (repro.sketchserve) and
# checkpoints serialize the spec and rebuild the mesh on restore.


def mesh_spec(mesh) -> dict | None:
    """The JSON-safe geometry of a mesh: ``{"axis_names", "shape"}``.
    None stays None (auto-built meshes need no spec)."""
    if mesh is None:
        return None
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names]}


def mesh_from_spec(spec: dict | None):
    """Rebuild a mesh with the same geometry on THIS host's devices (raises
    if the host has too few)."""
    if spec is None:
        return None
    return auto_mesh(spec["shape"], spec["axis_names"])
