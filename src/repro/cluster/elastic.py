"""Elastic re-sharding: survive worker-count changes by remapping the grid.

The logical (step, shard) grid is FIXED by the sketch-mask key discipline —
shard s of step t always folds the same rows under the same mask, no matter
which physical worker computes it. A worker-count change is therefore a pure
remap: :func:`worker_shards` assigns each of the ``n_workers`` a contiguous
block of the ``n_shards`` logical shards, and each worker replays ONLY the
shards its new block owns (the regenerable source makes a "lost" shard a
replayable PRNG key, not lost data — the property none of the related systems
have).

Per step, every worker's :func:`partial_step_delta` is taken against the SAME
replicated step-start state; the fixed-size deltas are :func:`merge_deltas`'d
(element-wise add) and applied once by :func:`apply_step` — the step rule of
:mod:`repro.stream.step`, split at its reduce. Because the per-shard deltas
are identical to the original layout's and the apply happens once per step
either way, a 4-worker run, its 2-worker continuation, and the single-host
run agree to float-summation reordering (tests/test_cluster.py asserts the
4→2 remap parity).

:func:`continue_elastic` is the single-host driver of that protocol (the test
and bench harness; on a real cluster each worker runs its own
``partial_step_delta`` and ships the delta, e.g. through a psum).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.stream import step as step_mod
from repro.stream.engine import EngineState, StreamEngine


def worker_shards(n_shards: int, n_workers: int, worker: int) -> list[int]:
    """The contiguous block of logical shards worker ``worker`` owns under an
    ``n_workers``-worker layout (earlier workers take the remainder)."""
    if not 0 <= worker < n_workers:
        raise ValueError(f"worker must be in [0, {n_workers}), got {worker}")
    if n_workers > n_shards:
        raise ValueError(f"{n_workers} workers over {n_shards} logical shards "
                         "leaves workers idle — lower n_workers")
    base, rem = divmod(n_shards, n_workers)
    sizes = [base + (1 if w < rem else 0) for w in range(n_workers)]
    start = sum(sizes[:worker])
    return list(range(start, start + sizes[worker]))


def partial_step_delta(engine: StreamEngine, state: EngineState, step: int,
                       shards: list[int], seed: int | None = None) -> dict:
    """One worker's summed delta for ``step``, per accumulating state field:
    fold ONLY ``shards``' batches — regenerated from the (seed, step, shard)
    contract and sketched under their grid-fixed mask keys — against the
    step-start ``state``."""
    if not shards:
        raise ValueError("partial_step_delta needs at least one shard")
    sketches = [engine.sketch_shard(engine.source(seed, step, sh), jnp.int32(step), sh)
                for sh in shards]
    return {field: step_mod.step_delta(desc, getattr(state, field), aux, sketches)[0]
            for field, desc, aux in engine.folds()}


def merge_deltas(a, b):
    """Combine two workers' partial deltas — element-wise add, the same sum
    the engine takes within a step."""
    return jax.tree.map(jnp.add, a, b)


def apply_step(engine: StreamEngine, state: EngineState, delta: dict) -> EngineState:
    """Apply one step's merged delta ONCE (K-means decay and the Eq.-39 mean
    update happen here, exactly once)."""
    return dataclasses.replace(state, **{
        field: step_mod.step_apply(desc, getattr(state, field), delta[field])[0]
        for field, desc, _ in engine.folds()})


def continue_elastic(engine: StreamEngine, steps: int, *, state: EngineState,
                     start_step: int, n_workers: int,
                     seed: int | None = None) -> EngineState:
    """Continue a (restored) run to ``steps`` under a NEW worker count.

    Single-host driver of the elastic protocol: per remaining step, each of
    the ``n_workers`` simulated workers folds its :func:`worker_shards`
    block's deltas against the shared step-start state; the deltas merge and
    apply once. Engine-level reassignment counters (if tracked) are frozen —
    they need the per-shard sketches the distributed protocol does not ship.
    """
    for step in range(start_step, steps):
        deltas = [partial_step_delta(engine, state, step,
                                     worker_shards(engine.n_shards, n_workers, w),
                                     seed)
                  for w in range(n_workers)]
        state = apply_step(engine, state, functools.reduce(merge_deltas, deltas))
    engine.state = state
    return state
