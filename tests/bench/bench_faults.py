"""Faults planted under the benchmark's timed path, for the tests of ``correct``.

Each fault is a context manager that breaks the program underneath an
otherwise unchanged rehearsal run (``bench/run.py`` without its look for a
chip):

- ``unchanged``: every fold step returns its state as it got it (only the
  row count moves on);
- ``half_batch``: every chunk loses its second half, and the first half
  stands in for it, so the fold still counts every row;
- ``altered``: one kept value of every chunk's sketch is altered where the
  sketch is produced;
- ``no_exchange``: the sharded reduction leaves out its psum, so each step
  keeps one shard's delta.

    python3 tests/bench/bench_faults.py <cell> <fault>[,<fault>...]

runs one rehearsal per fault with it in place (a four-chip cell on four host
devices) and prints ``{"fault": ..., "correct": ..., "checks": ...}`` for
each; the fault ``sound`` is a run with none, whose control is judged too
(``control_correct``, ``control_checks``).
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def unchanged():
    from repro import lowrank
    from repro.stream import accumulators as acc

    def range_update(state, batch, *a, **k):
        return type(state)(state.y, state.diag, state.sum_w, state.count + batch.n)

    def range_apply(state, delta):
        return type(state)(state.y, state.diag, state.sum_w, state.count + delta.count)

    def kmeans_apply(state, delta, decay=1.0):
        return type(state)(state.centers, state.counts, state.obj, state.count + delta[3])

    with patched(lowrank, "range_update", range_update), \
            patched(lowrank, "range_apply", range_apply), \
            patched(acc, "kmeans_apply", kmeans_apply):
        yield


@contextlib.contextmanager
def half_batch():
    import jax.numpy as jnp

    from repro.core import sketch as sketch_mod

    real = sketch_mod.sketch

    def sketch(x, spec, batch_key=None, impl="auto"):
        h = x.shape[0] // 2
        return real(jnp.concatenate([x[:h], x[:x.shape[0] - h]]), spec, batch_key, impl)

    with patched(sketch_mod, "sketch", sketch):
        yield


@contextlib.contextmanager
def altered():
    import jax.numpy as jnp

    from repro.core import sketch as sketch_mod

    real = sketch_mod.sketch

    def sketch(x, spec, batch_key=None, impl="auto"):
        s = real(x, spec, batch_key, impl)
        v = s.values.at[0, 0].add(0.01 * jnp.max(jnp.abs(s.values)))
        return type(s)(v, s.indices, s.p)

    with patched(sketch_mod, "sketch", sketch):
        yield


@contextlib.contextmanager
def no_exchange():
    import functools

    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.sampling import SparseRows
    from repro.lowrank import range_finder
    from repro.stream import sharded

    @functools.lru_cache(maxsize=None)
    def lowrank_fn(mesh, axes, p, ell, impl):
        def local(values, indices, omega_mat):
            return range_finder.range_delta(SparseRows(values, indices, p), omega_mat,
                                            impl=impl)

        spec = P(axes if len(axes) > 1 else axes[0], None)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=(spec, spec, P()),
                                 out_specs=P(), check_vma=False))

    with patched(sharded, "_lowrank_fn", lowrank_fn):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "altered": altered,
          "no_exchange": no_exchange}


def rehearse(cell: str, seed: int = 7, fault: str | None = None, control: bool = False):
    """One rehearsal run of ``cell`` in this process, with ``fault`` in place."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import jax

    from bench import harness as H
    from bench import run as RUN

    api = H.import_program()
    wl = H.workload(cell)
    ctx = FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        return RUN.run_cell(H, api, jax, wl, seed, 0.0, rehearse=True, control=control)


if __name__ == "__main__":
    cell, faults = sys.argv[1], sys.argv[2].split(",")
    sys.path.insert(0, str(ROOT))
    from bench import harness as H
    from bench import run as RUN

    from bench import compare as C

    RUN.prepare_env(True, int(H.workload(cell)["chips"]))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for fault in faults:
        sound = fault == "sound"
        res = rehearse(cell, fault=None if sound else fault, control=sound)
        line = {"fault": fault, "correct": res["result"]["correct"],
                "checks": res["result"]["checks"]}
        if sound:
            limits = H.config(H.workload(cell)["config"])["limits"]
            line["control_correct"], line["control_checks"] = C.judge(res["diag"]["control"],
                                                                      limits)
        print(json.dumps(line), flush=True)
