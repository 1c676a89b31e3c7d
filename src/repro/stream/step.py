"""The per-step fold: the one definition of the paper's one-pass step rule.

Every shard's delta is taken against the step-start state, the deltas are
summed (under a mesh, one psum of the fixed-size sum), and the sum is applied
once. Every ingest driver folds through here: the StreamEngine's update
(host loop, scan and ``shard_map``), the api cursor's minibatch K-means step
and its ``lax.scan``, the api's sharded K-means step, and elastic
re-sharding.

A fold is named by a hashable descriptor:

- ``("moment", cov_path)``: the Thm-4/Thm-6 moment accumulators
  (``track_cov`` is read from the state, as ``stream_update`` does);
- ``("range", impl)``: the low-rank range finder; ``aux`` is Omega;
- ``("fd",)``: Frequent Directions. Its shrink is order-dependent, so it
  folds its sketches in order, has no delta, and takes no ``reduce``;
- ``("kmeans", track, decay)``: minibatch K-means (the Eq.-39 update); with
  ``track`` the step also counts the rows whose nearest center moved.

The algebra is looked up on its modules (``est``, ``lowrank``, ``acc``) when
a fold is traced, never bound at import, so a function replaced on its module
at run time reaches every driver. Drivers that cache a compiled fold key the
cache on :func:`algebra`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import lowrank
from repro.core import estimators as est
from repro.stream import accumulators as acc


def algebra() -> tuple:
    """The accumulator functions a fold traces, as their modules now hold them."""
    return (est.stream_delta, est.stream_apply, lowrank.range_delta,
            lowrank.range_apply, lowrank.fd_update, acc.kmeans_delta_with_assign,
            acc.kmeans_apply, acc.kmeans_reassigned)


def _sum(parts):
    out = parts[0]
    for d in parts[1:]:
        out = jax.tree.map(jnp.add, out, d)
    return out


def step_delta(desc, carry, aux, sketches, mask=None):
    """(delta, labels): the sum of every sketch's delta against the step-start
    ``carry``. ``labels`` are the per-sketch (r, n) step-start K-means labels
    when ``desc`` tracks reassignments, else None. ``mask`` (n,) drops the pad
    rows of a lone K-means sketch."""
    kind = desc[0]
    if kind == "moment":
        track_cov = carry.sum_wwt is not None
        return _sum([est.stream_delta(s, track_cov=track_cov, cov_path=desc[1])
                     for s in sketches]), None
    if kind == "range":
        return _sum([lowrank.range_delta(s, aux, impl=desc[1]) for s in sketches]), None
    if kind == "kmeans":
        pairs = [acc.kmeans_delta_with_assign(carry, s, mask) for s in sketches]
        return _sum([d for d, _ in pairs]), ([a for _, a in pairs] if desc[1] else None)
    raise ValueError(f"fold {desc!r} has no delta")


def step_apply(desc, carry, delta, sketches=(), labels=None, mask=None):
    """(carry, counts): ``delta`` applied once. Given the step's ``labels``,
    ``counts`` are the (r,) K-means rows reassigned under the new centers,
    summed over ``sketches``; otherwise a zero."""
    kind = desc[0]
    if kind == "moment":
        new = est.stream_apply(carry, delta)
    elif kind == "range":
        new = lowrank.range_apply(carry, delta)
    elif kind == "kmeans":
        new = acc.kmeans_apply(carry, delta, decay=desc[2])
        if labels is not None:
            return new, _sum([acc.kmeans_reassigned(new, s, a0, mask)
                              for s, a0 in zip(sketches, labels)])
    else:
        raise ValueError(f"fold {desc!r} has no apply")
    return new, jnp.zeros((), jnp.int32)


def fold_step(desc, carry, aux, sketches, reduce=None, mask=None):
    """(carry, counts) after one step: ``step_apply(reduce(step_delta(...)))``.
    ``reduce`` is the identity on one device and ``lax.psum`` over the data
    axis inside a ``shard_map``, where the reassignment counts take it too."""
    if desc[0] == "fd":
        if reduce is not None:
            raise ValueError("the fd fold is order-dependent: it cannot be "
                             "reduced across shards")
        for s in sketches:
            carry = lowrank.fd_update(carry, s)
        return carry, jnp.zeros((), jnp.int32)
    delta, labels = step_delta(desc, carry, aux, sketches, mask)
    if reduce is not None:
        delta = reduce(delta)
    new, counts = step_apply(desc, carry, delta, sketches, labels, mask)
    if reduce is not None and labels is not None:
        counts = reduce(counts)
    return new, counts
