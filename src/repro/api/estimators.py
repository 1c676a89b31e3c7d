"""Estimator classes: fit / partial_fit / finalize over any :class:`Plan` backend.

One compression operator feeding many consumers (the paper's pitch) as one
class family: a :class:`SketchCursor` owns the ``source → sketch`` pass —
it consumes input in consecutive ``plan.batch_size`` chunks, keys chunk j's
mask with ``sketch.batch_key(spec, step=j // n_shards, shard=j % n_shards)``,
sketches each chunk EXACTLY ONCE, and fans the sketch out to every registered
consumer. Estimators are pure folders: ``_fold_sketch(s, step, shard)`` is
their only ingest point, so a lone ``fit()`` is just the one-consumer special
case of :func:`repro.api.fit_many`'s shared pass. Each consumer's reducer then
hands the folds to its plan's backend —

- ``batch``:   keep the (γ·dense) sketch, one-shot ``repro.core`` estimators;
- ``stream``:  fold constant-memory accumulator deltas
               (``repro.stream.accumulators``) batch by batch;
- ``sharded``: reduce with the ``repro.stream.sharded`` shard_map collectives
               (one psum of the fixed-size accumulator over the mesh).

Because all three fold the *same* per-(step, shard) sketches, results agree to
float-summation reordering (tests/test_api.py asserts 1e-5) — the backend is a
pure execution choice.

Fitted attributes follow the sklearn trailing-underscore convention; estimates
come back in the ORIGINAL domain (eigenvectors / means / centers unmixed by
(HD)ᵀ) unless noted.
"""
from __future__ import annotations

import functools
import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.plan import BACKENDS, Plan
from repro.core import estimators as est
from repro.core import ros
from repro.core import kmeans as km
from repro.core import pca as pca_mod
from repro.core import sketch as sketch_mod
from repro.core.grad_compress import CompressConfig, compress_grads, mask_spec
from repro.core.sampling import SparseRows
from repro.core.sketch import batch_key
from repro import lowrank as lowrank_mod
from repro import refine as refine_mod
from repro.stream import accumulators as acc
from repro.stream import sharded as sharded_mod
from repro.stream import state as state_mod
from repro.stream import step as step_mod
from repro.train import checkpoint as checkpoint_mod
from repro.utils.prng import fold_in_str


def as_key(key: jax.Array | int) -> jax.Array:
    """Accept an int seed or a PRNGKey — the one key-normalization point."""
    if isinstance(key, (int,)):
        return jax.random.PRNGKey(key)
    return key


# ------------------------------------------------------------ moment core ---
# The backend registry: one reduce function per Plan.backend, each mapping a
# reducer's folded state to (mean_pre, cov_pre | None, count) through the
# pre-existing implementation it wraps — core one-shot estimators,
# stream accumulators, or the stream.sharded shard_map collectives.

MOMENT_BACKENDS: dict[str, "callable"] = {}


def _is_multiprocess() -> bool:
    """True under a live multi-process jax.distributed runtime (lazy import —
    repro.cluster is only touched when a cluster actually exists)."""
    if jax.process_count() <= 1:
        return False
    return True


def _sharded_mesh(plan: Plan):
    """The mesh the sharded backend reduces over: plan.resolve_mesh() on a
    single host; under a multi-process runtime the process-contiguous
    repro.cluster mesh (each process's devices own a contiguous block of
    shard positions — what per-host global-array assembly requires)."""
    if _is_multiprocess():
        from repro import cluster

        return cluster.process_mesh(plan.n_shards, plan.axis)
    return plan.resolve_mesh()


def _moment_backend(name: str):
    def register(fn):
        MOMENT_BACKENDS[name] = fn
        return fn
    return register


@_moment_backend("batch")
def _reduce_batch(r: "_MomentReducer"):
    s_all = r.concat()
    mean = est.mean_estimator(s_all)
    cov = (est.cov_estimator(s_all, path=r.plan.cov_path) if r.track_cov else None)
    return mean, cov, jnp.int32(s_all.n)


@_moment_backend("stream")
def _reduce_stream(r: "_MomentReducer"):
    st = r.state
    if _read_count(st) == 0:
        raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
    cov = acc.moment_finalize_cov(st, r.spec.m) if r.track_cov else None
    return acc.moment_finalize_mean(st, r.spec.m), cov, st.count


@_moment_backend("sharded")
def _reduce_sharded(r: "_MomentReducer"):
    r.flush_step()  # a trailing partial step still needs its psum
    st = r.state
    if _read_count(st) == 0:
        raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
    cov = acc.moment_finalize_cov(st, r.spec.m) if r.track_cov else None
    return acc.moment_finalize_mean(st, r.spec.m), cov, st.count


assert set(MOMENT_BACKENDS) == set(BACKENDS), "registry out of sync with Plan.BACKENDS"


def _read_count(state) -> int:
    """The folded row count, read back to the host (waits for the device)."""
    with obs.span("readback", site="count"):
        return int(state.count)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _retain(values_t, indices_t, values, indices, at):
    """Write one chunk's rows into the retained buffers at column ``at``."""
    return (jax.lax.dynamic_update_slice(values_t, values.astype(jnp.float32).T, (0, at)),
            jax.lax.dynamic_update_slice(indices_t, indices.astype(jnp.int32).T, (0, at)))


@functools.partial(jax.jit, static_argnames=("cap",))
def _regrow(values_t, indices_t, cap: int):
    pad = ((0, 0), (0, cap - values_t.shape[1]))
    return jnp.pad(values_t, pad), jnp.pad(indices_t, pad)


@jax.tree_util.register_pytree_node_class
class RetainedSketch:
    """Every retained row of the sketch, held once on the device.

    ``values_t`` and ``indices_t`` are (m, capacity) buffers, row i in
    column i (the layout of ``core.kmeans.lloyd``); each chunk is written
    in place at the next free column, and a full buffer doubles (the
    capacity stays a whole number of the kernels' row tiles). ``n`` rows
    are filled; the columns past them are zero."""

    def __init__(self, p: int, values_t=None, indices_t=None, n: int = 0):
        self.p, self.values_t, self.indices_t, self.n = p, values_t, indices_t, n

    def tree_flatten(self):
        return (self.values_t, self.indices_t), (self.p, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], *children, aux[1])

    @property
    def capacity(self) -> int:
        return 0 if self.values_t is None else self.values_t.shape[1]

    @property
    def nbytes(self) -> int:
        return 0 if self.values_t is None else self.values_t.nbytes + self.indices_t.nbytes

    def append(self, s: SparseRows) -> None:
        need = self.n + s.n
        if need > self.capacity:
            tile = km.row_tile()
            cap = max(2 * self.capacity, -(-need // tile) * tile)
            if self.values_t is None:
                self.values_t = jnp.zeros((s.m, cap), jnp.float32)
                self.indices_t = jnp.zeros((s.m, cap), jnp.int32)
            else:
                self.values_t, self.indices_t = _regrow(self.values_t, self.indices_t, cap)
        self.values_t, self.indices_t = _retain(self.values_t, self.indices_t, s.values,
                                                s.indices, jnp.int32(self.n))
        self.n = need

    def rows(self) -> SparseRows:
        """The retained rows as one row-major (n, m) SparseRows (a copy)."""
        if not self.n:
            raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
        return SparseRows(self.values_t[:, :self.n].T, self.indices_t[:, :self.n].T, self.p)


class _MomentReducer:
    """Backend-dispatched reduction of sketched batches to (mean, cov, count).

    ``fold`` ingests one per-(step, shard) sketch; ``reduce`` dispatches
    through :data:`MOMENT_BACKENDS` for the Thm-4 / Thm-6 estimates.

    Only the "batch" backend (and Lloyd K-means, which passes
    ``keep_sketch=True`` on every backend because Alg. 1 clusters the retained
    sketch) holds sketches past their step. "stream" folds each sketch into
    the constant-memory accumulator immediately; "sharded" buffers ONE step's
    shard sketches, reduces them with a single psum of the fixed-size delta
    (the step rule of repro.stream.step), and drops them — streaming
    per-step reduction, not concat()-then-reduce, so host memory stays
    constant in the stream length.
    """

    def __init__(self, plan: Plan, spec: sketch_mod.SketchSpec, track_cov: bool,
                 keep_sketch: bool = False, needs_moments: bool = True):
        self.plan, self.spec, self.track_cov = plan, spec, track_cov
        # the low-rank spectral path replaces the (p, p) accumulator with the
        # O(rank·p) repro.lowrank states — on EVERY backend (batch included:
        # sketches fold through the same per-chunk deltas instead of being
        # retained, which is the whole point of the path)
        self.lowrank = (plan.cov_path == "lowrank" and track_cov and needs_moments)
        self.keep_sketch = keep_sketch or (plan.backend == "batch" and needs_moments
                                           and not self.lowrank)
        self.retained = RetainedSketch(spec.p_pad) if self.keep_sketch else None
        self._step_parts: list[SparseRows] = []  # sharded: the in-flight step
        self._mesh = None
        self._omega = None
        if self.lowrank:
            if plan.rank > spec.p_pad:
                raise ValueError(f"rank={plan.rank} exceeds p_pad={spec.p_pad}; "
                                 "a low-rank sketch must be narrower than p")
            if plan.lowrank_method == "range":
                self._omega = lowrank_mod.omega(spec.key, spec.p_pad, plan.rank)
                self.state = lowrank_mod.range_init(spec.p_pad, plan.rank)
            else:
                self.state = lowrank_mod.fd_init(spec.p_pad, plan.rank)
        else:
            # moment state only where reduce() will read it (K-means never does)
            self.state = (acc.moment_init(spec.p_pad, track_cov=track_cov)
                          if plan.backend in ("stream", "sharded") and needs_moments
                          else None)

    @property
    def _moment_cov_path(self) -> str:
        # stream_delta/sharded_moments only understand dense|compact; with the
        # lowrank path they are only ever called track_cov=False (mean-only)
        return "dense" if self.plan.cov_path == "lowrank" else self.plan.cov_path

    def fold(self, s: SparseRows, step: int, shard: int) -> None:
        if self.lowrank:
            if self.plan.lowrank_method == "fd":
                # FD shrink is order-dependent: fold in (step, shard) linear
                # order on every backend — backends agree bit-for-bit
                self.state = lowrank_mod.fd_update(self.state, s)
            elif self.plan.backend == "sharded":
                self._step_parts.append(s)
                if shard == self.plan.n_shards - 1:
                    self.flush_step()
            else:
                self.state = lowrank_mod.range_update(self.state, s, self._omega,
                                                      impl=self.plan.impl)
        elif self.state is not None:
            if self.plan.backend == "sharded":
                self._step_parts.append(s)
                if shard == self.plan.n_shards - 1:
                    self.flush_step()
            else:
                self.state = est.stream_update(self.state, s,
                                               cov_path=self._moment_cov_path)
        if self.keep_sketch:
            self.retained.append(s)

    def flush_step(self) -> None:
        """Sharded: reduce the buffered step with one psum'd delta, then drop it.

        Multi-process: each process buffered only ITS shards' sketches; they
        enter the same shard_map as this process's contiguous block of ONE
        global row-sharded array (repro.cluster.global_rows), and the psum
        reduces across hosts — every process must reach this flush once per
        step, in step order (the multiprocess fold_source loop guarantees it).
        """
        if not self._step_parts:
            return
        if self._mesh is None:
            self._mesh = _sharded_mesh(self.plan)
        step_sketch = self._assemble_step()
        if self.lowrank:
            delta = sharded_mod.sharded_lowrank(step_sketch, self._omega,
                                                self._mesh, (self.plan.axis,),
                                                impl=self.plan.impl)
            self.state = lowrank_mod.range_apply(self.state, delta)
        else:
            delta = sharded_mod.sharded_moments(
                step_sketch, self._mesh, (self.plan.axis,),
                track_cov=self.track_cov, cov_path=self._moment_cov_path)
            self.state = acc.moment_apply(self.state, delta)
        self._step_parts = []

    def _assemble_step(self) -> SparseRows:
        """The buffered step as one SparseRows, row-sharded over the mesh so
        each device holds its own shards' rows (a ragged step is left for the
        reduction to pad and place); under multi-process, the local shards'
        rows become this process's addressable block of a global row-sharded
        array."""
        if not _is_multiprocess():
            s = _concat_sparse(self._step_parts, self.spec.p_pad)
            if s.n % self.plan.n_shards:
                return s
            axes = (self.plan.axis,)
            return SparseRows(sharded_mod.shard_rows(s.values, self._mesh, axes),
                              sharded_mod.shard_rows(s.indices, self._mesh, axes),
                              s.p)
        from repro import cluster

        with obs.span("readback", site="assemble"):
            vals = np.concatenate([np.asarray(s.values) for s in self._step_parts])
            idxs = np.concatenate([np.asarray(s.indices) for s in self._step_parts])
        return SparseRows(cluster.global_rows(vals, self._mesh, self.plan.axis),
                          cluster.global_rows(idxs, self._mesh, self.plan.axis),
                          self.spec.p_pad)

    def concat(self) -> SparseRows:
        if self.retained is None:
            raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
        return self.retained.rows()

    def reduce(self):
        """(mean_pre, cov_pre | LowRankCov | None, count) via the plan's backend."""
        if self.lowrank:
            return self._reduce_lowrank()
        return MOMENT_BACKENDS[self.plan.backend](self)

    def _reduce_lowrank(self):
        """Finalize the O(rank·p) spectral state — shared by all backends (they
        differ only in HOW the same linear deltas were reduced)."""
        self.flush_step()  # a trailing partial step still needs its psum
        st = self.state
        if _read_count(st) == 0:
            raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
        if self.plan.lowrank_method == "range":
            return (lowrank_mod.range_finalize_mean(st, self.spec.m),
                    lowrank_mod.range_finalize(st, self.spec.m, self._omega),
                    st.count)
        return (lowrank_mod.fd_finalize_mean(st, self.spec.m),
                lowrank_mod.fd_finalize(st, self.spec.m), st.count)


def _concat_sparse(parts: list[SparseRows], p: int) -> SparseRows:
    return SparseRows(jnp.concatenate([s.values for s in parts]),
                      jnp.concatenate([s.indices for s in parts]), p)


# --------------------------------------------------------- scanned ingest ---
# The opt-in lax.scan hot loop (cursor.scan = True / fit_many(scan=True)):
# instead of one Python-dispatched sketch + fold round trip per chunk, the
# aligned full-step prefix of each partial_fit array is staged as
# (steps, n_shards, batch_size, p) and driven through ONE jitted scan whose
# body regenerates chunk (step, shard)'s mask key exactly as fold_rows does
# and folds each step through repro.stream.step. Same sketches as the host
# loop, so results match it to float-summation reordering — but not
# bit-identically, which is why the scan stays opt-in.
#
# Consumers name their in-scan fold with a repro.stream.step descriptor
# (_scan_desc), so the compiled scan is shared across estimator instances via
# the lru_cache below; consumers whose fold cannot run inside a scan
# (retained sketches, shard_map reductions) return None and scan=True raises.


@functools.lru_cache(maxsize=None)
def _kmeans_fold_jit(track: bool, decay: float, *algebra):
    """The host loop's minibatch K-means step as ONE compiled program:
    (state, the step's shard sketches) -> (state, (r,) reassignment counts),
    the scan body's K-means fold. Cached on its static description so every
    estimator shares it (a benchmark's warm-up and measured runs, each
    SketchService group); jit compiles once per observed chunk shape and
    number of shard sketches. The cache key also holds ``algebra``
    (``repro.stream.step.algebra()``), the accumulator functions the fold
    traces: jit keeps what it traced, so where one of them is replaced at run
    time (an instrumented or fault-planted build) the step is traced again
    from the module as it now stands."""
    desc = ("kmeans", track, decay)

    def kmeans_step(state, sketches):   # names the program in device traces
        return step_mod.fold_step(desc, state, None, sketches)

    return jax.jit(kmeans_step)


@functools.lru_cache(maxsize=None)
def _build_scan_fn(plan: Plan, p: int, m: int, transform: str, impl: str,
                   descs: tuple, *algebra):
    """The jitted scan over full (step × n_shards) blocks, cached on the
    static description so repeated fit_many calls (and benchmark loops) reuse
    one compilation per shape; ``algebra`` is keyed as in _kmeans_fold_jit."""
    n_shards = plan.n_shards

    @jax.jit
    def scan_all(carries, auxes, xs, step0, signs_key, mask_key):
        def body(carry, inp):
            t, x_step = inp
            step = step0 + t
            sketches = [
                sketch_mod._sketch_impl(
                    x_step[sh], signs_key,
                    jax.random.fold_in(jax.random.fold_in(mask_key, step), sh),
                    p, m, transform, impl)
                for sh in range(n_shards)
            ]
            new, ys = [], []
            for c, aux, desc in zip(carry, auxes, descs):
                nc, y = step_mod.fold_step(desc, c, aux, sketches)
                new.append(nc)
                ys.append(y)
            return tuple(new), tuple(ys)

        steps = xs.shape[0]
        return jax.lax.scan(body, carries,
                            (jnp.arange(steps, dtype=jnp.int32), xs))

    return scan_all


# ------------------------------------------------------------ the cursor ----


class SketchCursor:
    """The shared ``source → sketch`` pass: ONE sketch per (step, shard) chunk.

    The cursor owns everything sketching needs — spec derivation from
    (plan, key), the chunk counter mapping consecutive ``plan.batch_size``
    chunks to (step, shard) mask keys, and the ``sketch_mod.sketch`` call —
    and fans each sketch out to every registered consumer's ``_fold_sketch``.
    A lone estimator owns a one-consumer cursor; :func:`repro.api.fit_many`
    registers many consumers on one cursor, so a single compression pass feeds
    them all (the paper's pitch: compress once, answer every question).

    Thread-safety contract: ``partial_fit`` / ``fold_source`` hold an internal
    lock for the WHOLE call, so concurrent producers (e.g. several threads
    feeding one :class:`~repro.api.fused.SharedSketchRun`) serialize — each
    call folds atomically, chunk indices (hence (step, shard) mask keys) are
    assigned in lock-acquisition order, and counts stay exact. Which producer
    gets which chunk index is whatever the lock arbitration yields, so
    multi-producer results are run-to-run ordering-dependent (still valid
    estimates — every chunking is); a single producer (the
    ``repro.sketchserve`` worker loop, which funnels all ingest through one
    thread) stays fully deterministic. ``finalize``/``reduce`` are NOT
    guarded: quiesce producers (or go through the sketchserve queue, which
    orders queries after ingest) before reading fitted state.
    """

    def __init__(self, plan: Plan, key: jax.Array | int):
        self.plan = plan
        self.key = as_key(key)
        self._lock = threading.Lock()
        self.spec: sketch_mod.SketchSpec | None = None
        self.chunk = 0           # linear chunk index → plan.step_shard(chunk)
        self.count = 0           # rows folded through this cursor
        self.chunk_rows: list[int] = []  # rows per chunk — the replay contract
        self.n_sketches = 0      # sketch_mod.sketch invocations (one per chunk)
        self.last_sketch: SparseRows | None = None
        self.consumers: list["SketchedEstimator"] = []
        self.scan = False        # opt-in lax.scan hot loop for partial_fit
        self._scan_out = None    # last scan's carries — the sync() barrier
        self._calls = itertools.count()  # the ``call`` of each ingest call's spans

    def register(self, consumer: "SketchedEstimator") -> None:
        self.consumers.append(consumer)
        if self.spec is not None:
            consumer._bind_spec(self.spec)

    def ensure_spec(self, p: int) -> sketch_mod.SketchSpec:
        if self.spec is None:
            self.spec = self.plan.spec(p, self.key)
            for c in self.consumers:
                c._bind_spec(self.spec)
        elif self.spec.p != p:
            raise ValueError(
                f"batch has p={p}, but this pass was started with "
                f"p={self.spec.p}; start a new fit (estimator.fit/reset, or a "
                "fresh fit_many) to change dimensionality")
        return self.spec

    def fold_rows(self, rows: jax.Array) -> None:
        """Sketch one ≤batch_size chunk under its (step, shard) mask key and
        hand the SAME SparseRows to every consumer."""
        step, shard = self.plan.step_shard(self.chunk)
        n = int(rows.shape[0])
        with obs.span("chunk", chunk=self.chunk, step=step, shard=shard, rows=n):
            with obs.span("sketch"):
                s = sketch_mod.sketch(rows, self.spec,
                                      batch_key=batch_key(self.spec, step, shard),
                                      impl=self.plan.impl)
            self.n_sketches += 1
            self.last_sketch = s
            for i, c in enumerate(self.consumers):
                with obs.span("fold." + c.kind, consumer=i):
                    c._consume(s, step, shard, n)
            self.chunk += 1
            self.count += n
            self.chunk_rows.append(n)

    def partial_fit(self, x) -> None:
        shape = np.shape(x)
        with obs.span("ingest.partial_fit", call=next(self._calls),
                      rows=shape[0] if shape else 0):
            with obs.span("h2d", bytes=getattr(x, "nbytes", 0)):
                x = jnp.asarray(x)
                if x.ndim != 2:
                    raise ValueError(f"expected (rows, p) data, got shape {x.shape}")
                x = x.astype(self.plan.dtype)
            with self._lock:  # concurrent producers serialize whole-call (see class doc)
                self.ensure_spec(x.shape[1])
                start = self._fold_rows_scanned(x) if self.scan else 0
                bs = self.plan.batch_size
                for i in range(start, x.shape[0], bs):
                    self.fold_rows(x[i:i + bs])

    def scan_descs(self) -> tuple | None:
        """The consumers' in-scan fold descriptors, or None if any consumer
        cannot fold inside lax.scan (see SketchedEstimator._scan_desc)."""
        descs = tuple(c._scan_desc() for c in self.consumers)
        if not descs or any(d is None for d in descs):
            return None
        return descs

    def _fold_rows_scanned(self, x) -> int:
        """Fold the step-aligned full-step prefix of ``x`` through ONE jitted
        lax.scan (see _build_scan_fn) and return the rows consumed; the
        ordinary host loop takes the ragged tail. A cursor mid-step
        (chunk % n_shards != 0) folds everything on the host instead — the
        scan only ever starts at a step boundary so mask keys stay aligned."""
        plan, spec = self.plan, self.spec
        ns, bs = plan.n_shards, plan.batch_size
        if self.chunk % ns:
            return 0
        steps = x.shape[0] // (bs * ns)
        if steps == 0:
            return 0
        descs = self.scan_descs()
        if descs is None:
            raise ValueError(
                "scan=True but a registered consumer cannot fold inside "
                "lax.scan: batch-backend moment estimators and Lloyd K-means "
                "retain their sketches, and the sharded backend reduces "
                "through shard_map collectives — use the default host loop "
                "(scan=False) for those, or switch to stream/minibatch/"
                "lowrank folds")
        take = steps * ns * bs
        with obs.span("scan", steps=steps):
            xs = x[:take].reshape(steps, ns, bs, x.shape[1])
            step0 = self.chunk // ns
            for c in self.consumers:
                c._scan_prepare(self, xs, step0)
            scan_fn = _build_scan_fn(plan, spec.p, spec.m, spec.transform,
                                     ros.resolve_impl(plan.impl), descs,
                                     *step_mod.algebra())
            carries = tuple(c._scan_carry() for c in self.consumers)
            auxes = tuple(c._scan_aux() for c in self.consumers)
            new_carries, ys = scan_fn(carries, auxes, xs, jnp.int32(step0),
                                      spec.signs_key(), spec.mask_key())
            for c, nc, y in zip(self.consumers, new_carries, ys):
                c._scan_absorb(nc, y, steps, ns * bs)
        self.chunk += steps * ns
        self.count += take
        self.chunk_rows.extend([bs] * (steps * ns))
        self.n_sketches += steps * ns
        self.last_sketch = None  # the scan never materializes its sketches
        self._scan_out = new_carries
        return take

    def sync(self) -> None:
        """Block until everything folded so far is materialized: the last
        chunk's sketch (after a scanned fold, the scan's output carries) and
        every registered consumer's fold state — the public ingest barrier
        to time a fold pass against."""
        last = self.last_sketch
        jax.block_until_ready((
            None if last is None else (last.values, last.indices),
            self._scan_out, [c._fold_state() for c in self.consumers]))

    def fold_source(self, source, steps: int, seed: int | None = None) -> None:
        """One pass over a normalized ``(seed, step, shard) → (b, p)`` source
        (the StreamEngine contract): each (step, shard) batch is folded under
        exactly that (step, shard) mask key.

        Under a multi-process runtime with the sharded backend, each process
        generates and sketches ONLY the shards it owns (the regenerable-source
        contract makes "distribute the stream" exactly that); the per-step
        shard_map reduction then psums across hosts.
        """
        with (obs.span("ingest.fold_source", call=next(self._calls), steps=steps),
              self._lock):  # concurrent producers serialize whole-call (see class doc)
            if _is_multiprocess() and self.plan.backend == "sharded":
                self._fold_source_multiprocess(source, steps, seed)
                return
            for step in range(steps):
                for shard in range(self.plan.n_shards):
                    rows = self._source_rows(source, seed, step, shard)
                    self.ensure_spec(rows.shape[1])
                    self.fold_rows(rows)

    def _source_rows(self, source, seed, step: int, shard: int) -> jax.Array:
        """Batch (step, shard) of ``source``, on the device in the plan's dtype."""
        rows = source(seed, step, shard)
        with obs.span("h2d", bytes=getattr(rows, "nbytes", 0)):
            return jnp.asarray(rows).astype(self.plan.dtype)

    def _fold_source_multiprocess(self, source, steps: int,
                                  seed: int | None) -> None:
        """The per-host slice of the shared (step, shard) grid: fold the
        shards this process owns, skip the rest (their chunk indices still
        advance — the mask-key discipline is global), and drive every
        consumer's step flush so all processes enter each step's collective
        reduction exactly once, in step order."""
        from repro import cluster

        for i, c in enumerate(self.consumers):
            why = c._multiprocess_unsupported()
            if why:
                raise ValueError(
                    f"consumers[{i}] ({type(c).__name__}) cannot fold under a "
                    f"multi-process runtime: {why}")
        mesh = _sharded_mesh(self.plan)
        mine = set(cluster.local_shards(mesh, self.plan.axis))
        if not mine:
            raise ValueError(f"process {jax.process_index()} owns no shards — "
                             "shrink n_shards or the process count")
        # data-dependent inits (minibatch K-means' K-means++ seeding) must be
        # bit-identical on every process: all of them sketch chunk (0, 0)
        # (replicated host compute) before any per-host folding starts.
        rows0 = None
        for c in self.consumers:
            if c._needs_first_sketch():
                if rows0 is None:
                    rows0 = self._source_rows(source, seed, 0, 0)
                    self.ensure_spec(rows0.shape[1])
                    s0 = sketch_mod.sketch(
                        rows0, self.spec, batch_key=batch_key(self.spec, 0, 0),
                        impl=self.plan.impl)
                c._seed_first_sketch(s0)
        for step in range(steps):
            for shard in range(self.plan.n_shards):
                if shard in mine:
                    rows = self._source_rows(source, seed, step, shard)
                    self.ensure_spec(rows.shape[1])
                    self.fold_rows(rows)
                else:
                    # the chunk happened — on another host. Mask keys are a
                    # pure function of the chunk index, so it must advance;
                    # rows-per-chunk is unknown here (0 = not locally held).
                    self.chunk += 1
                    self.chunk_rows.append(0)
            for i, c in enumerate(self.consumers):
                with obs.span("fold." + c.kind, consumer=i):
                    c._step_flush()


# -------------------------------------------------------------- base class --


class SketchedEstimator:
    """Shared fit / partial_fit / finalize plumbing — a pure sketch FOLDER.

    Sketching itself lives in :class:`SketchCursor`; the estimator's only
    ingest point is ``_fold_sketch(s, step, shard)``, called by whichever
    cursor it is registered on (its own by default, a shared one under
    :func:`repro.api.fit_many`). Subclasses set ``_track_cov`` /
    ``_keep_sketch`` and implement ``_finalize()`` from the reducer.
    ``fit(X)`` = reset → partial_fit(X) → finalize; ``partial_fit`` may be
    called any number of times with (rows, p) arrays (each call consumes its
    input in ``plan.batch_size`` chunks, so a stream fed in batch_size pieces
    reproduces ``fit`` of the concatenation exactly); ``finalize()`` computes
    the fitted attributes and returns self.
    """

    kind = "estimator"     # names the consumer's spans: fold.<kind>, finalize.<kind>
    _track_cov = False
    _keep_sketch = False
    _needs_moments = True  # False when _finalize never calls reducer.reduce()

    def __init__(self, plan: Plan, key: jax.Array | int = 0):
        self.plan = plan
        self.key = as_key(key)
        self.reset()

    # ------------------------------------------------------------ lifecycle --

    def reset(self) -> "SketchedEstimator":
        """Drop all folded state (spec is re-derived at the next first batch).

        Also detaches from any shared cursor — the old cursor stops fanning
        sketches into this estimator and a fresh one-consumer cursor takes
        over, so a still-live SharedSketchRun can't fold into reset state.
        """
        old = getattr(self, "_cursor", None)
        if old is not None and self in old.consumers:
            old.consumers.remove(self)
        self.spec_: sketch_mod.SketchSpec | None = None
        self._reducer: _MomentReducer | None = None
        self.count_ = 0
        self._fitted = False
        self._cursor = SketchCursor(self.plan, self.key)
        self._cursor.register(self)
        return self

    def _bind_spec(self, spec: sketch_mod.SketchSpec) -> None:
        """Cursor callback: the spec exists — allocate the reducer."""
        self.spec_ = spec
        self._reducer = _MomentReducer(self.plan, spec, self._track_cov,
                                       keep_sketch=self._keep_sketch,
                                       needs_moments=self._needs_moments)
        self._on_spec(spec)

    def _on_spec(self, spec: sketch_mod.SketchSpec) -> None:
        """Subclass hook: validate the spec once it exists (e.g. m >= 2)."""

    def partial_fit(self, x) -> "SketchedEstimator":
        """Fold more rows. Under a shared cursor (fit_many) this extends the
        shared pass — every co-registered consumer folds the same sketches."""
        self._cursor.partial_fit(x)
        return self

    def sync(self) -> "SketchedEstimator":
        """Block until this estimator's ingest (its cursor's last sketch and
        its consumers' fold states) is materialized — for wall-clock
        measurements of the fold pass."""
        self._cursor.sync()
        return self

    def _consume(self, s: SparseRows, step: int, shard: int, n_rows: int) -> None:
        self._fold_sketch(s, step, shard)
        self.count_ += n_rows

    def _fold_state(self):
        """The device arrays the folds so far write (what sync() waits on)."""
        r = self._reducer
        return None if r is None else (r.state, r.retained, r._step_parts)

    def _fold_sketch(self, s: SparseRows, step: int, shard: int) -> None:
        self._reducer.fold(s, step, shard)

    # --------------------------------------------------- multi-process fold --
    # Hooks for SketchCursor._fold_source_multiprocess: each process folds
    # only its own shards, so consumers must (a) reduce through per-step
    # collectives (sharded backend), (b) flush when the CURSOR says the step
    # ended (this process's last local shard is usually not shard
    # n_shards-1), and (c) run data-dependent inits from a sketch every
    # process regenerated identically.

    def _multiprocess_unsupported(self) -> str | None:
        """None when this consumer can fold under a multi-process runtime,
        else the reason it cannot."""
        if self.plan.backend != "sharded":
            return (f"backend={self.plan.backend!r} folds on the host — only "
                    "the sharded backend reduces across processes")
        if self._keep_sketch:
            return ("it retains its sketches (batch moments / Lloyd K-means); "
                    "a per-process buffer would hold only this host's shards")
        if (self.plan.cov_path == "lowrank" and self._track_cov
                and self._needs_moments and self.plan.lowrank_method == "fd"):
            return ("Frequent Directions is an order-dependent sequential "
                    "fold — its shrink cannot psum across processes")
        return None

    def _needs_first_sketch(self) -> bool:
        return False

    def _seed_first_sketch(self, s0: SparseRows) -> None:
        """Run a data-dependent init from chunk (0, 0)'s sketch (regenerated
        identically on every process)."""

    def _step_flush(self) -> None:
        """Cursor-driven step boundary: enter this step's collective
        reduction (exactly once per process per step)."""
        if self._reducer is not None:
            self._reducer.flush_step()

    # ------------------------------------------------------- scanned ingest --
    # Hooks for the cursor's opt-in lax.scan hot loop (cursor.scan = True /
    # fit_many(scan=True)). _scan_desc names the in-scan fold (a
    # repro.stream.step descriptor) or returns None when this consumer's fold
    # cannot run inside a scan; carry/aux/absorb move the fold state across
    # the jit boundary.

    def _scan_desc(self) -> tuple | None:
        plan = self.plan
        if self._keep_sketch:
            return None  # retained sketches can't stream through a scan
        if plan.cov_path == "lowrank" and self._track_cov and self._needs_moments:
            if plan.lowrank_method == "fd":
                return ("fd",)
            # range on sharded reduces through shard_map psums — host only
            return None if plan.backend == "sharded" else ("range", plan.impl)
        if not self._needs_moments:
            return None
        if plan.backend != "stream":
            # batch retains the sketch; sharded reduces via shard_map
            return None
        # mean-only folds under cov_path="lowrank" still use the dense delta
        # (mirrors _MomentReducer._moment_cov_path)
        return ("moment", "dense" if plan.cov_path == "lowrank" else plan.cov_path)

    def _scan_prepare(self, cursor: "SketchCursor", xs, step0: int) -> None:
        """Called before the scan launches with the staged (steps, n_shards,
        batch_size, p) block — subclasses that lazily init from a first
        sketch do so here (on the host, outside the scan)."""

    def _scan_carry(self):
        return self._reducer.state

    def _scan_aux(self):
        return self._reducer._omega

    def _scan_absorb(self, carry, ys, steps: int, rows_per_step: int) -> None:
        self._reducer.state = carry
        self.count_ += steps * rows_per_step

    def fit(self, x) -> "SketchedEstimator":
        self.reset()
        self.partial_fit(x)
        return self.finalize()

    def fit_stream(self, source, steps: int, seed: int | None = None) -> "SketchedEstimator":
        """One pass over a ``(seed, step, shard) → (b, p)`` source (the
        repro.data.pipeline / StreamEngine contract)."""
        from repro.stream.engine import normalize_source

        self.reset()
        self._cursor.fold_source(normalize_source(source), steps, seed)
        return self.finalize()

    def finalize(self) -> "SketchedEstimator":
        if self.spec_ is None:
            raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
        with obs.span("finalize." + self.kind):
            self._finalize()
        self._fitted = True
        return self

    def _finalize(self) -> None:
        raise NotImplementedError

    # ---------------------------------------------------------- refinement --
    # Second-pass replay refinement (repro.refine): subclasses that support it
    # override _refine_supported/_refine_check and the _refine_* fold hooks
    # documented in repro.refine.replay; the base class only owns the drivers.

    def _refine_supported(self) -> bool:
        return False

    def _refine_check(self) -> None:
        raise ValueError(
            f"{type(self).__name__} has no second-pass refinement: its "
            "estimator is already exact given the sketch (nothing a replay "
            "could sharpen). fit_refine applies to SparsifiedPCA on the "
            "lowrank 'range' path and to minibatch SparsifiedKMeans")

    def _refine_needs_signal(self) -> bool:
        return False

    def _refine_metric(self) -> float:
        """The latest per-pass convergence measurement (smaller = settled):
        PCA's principal-angle change between consecutive power bases, the
        minibatch K-means rebuild's reassigned-row fraction. Subclasses that
        support refinement implement it; the ``tol=`` loop reads it."""
        raise NotImplementedError

    def _refine_tol_check(self) -> None:
        """Subclass hook: reject ``tol=`` when the convergence signal is off."""

    def _resolve_passes(self, passes: int | None) -> int:
        if passes is None:
            passes = self.plan.refine_passes or 1
        if passes < 1:
            raise ValueError(f"refinement needs passes >= 1, got {passes}")
        return int(passes)

    def refine(self, x=None, passes: int | None = None, *, tol: float | None = None,
               max_passes: int = 16, source=None,
               steps: int | None = None, seed: int | None = None) -> "SketchedEstimator":
        """Replay the FITTED pass more times and sharpen the fit.

        ``x`` must be the same array ``fit`` consumed (re-chunked and re-masked
        identically under the (step, shard) key discipline; the row count is
        checked), or ``source`` / ``steps`` / ``seed`` the same stream
        ``fit_stream`` pulled — the replay regenerates every sketch
        bit-identically, storing nothing. ``passes`` defaults to
        ``plan.refine_passes`` (or 1). Repeat calls RESUME: ``refine(x);
        refine(x)`` continues the iteration where the first call stopped
        (≡ one ``refine(x, passes=2)``), with ``refine_passes_`` accumulating.

        ``tol=`` replaces the fixed pass count with "refine until converged":
        single passes run (resuming, exactly as repeat calls do) until the
        per-pass convergence measurement — ``refine_subspace_change_[-1]`` for
        PCA, ``refine_reassign_fraction_[-1]`` for minibatch K-means (needs
        ``track_reassignments=True``, and prices one trailing measurement
        replay per pass) — drops to ``tol`` or ``max_passes`` is hit;
        ``refine_converged_`` records which. Mutually exclusive with
        ``passes``.
        """
        self._refine_check()
        if not self._fitted:
            raise RuntimeError("refine() replays a fitted estimator — call "
                               "fit()/fit_stream() first, or use fit_refine()")
        if tol is not None:
            if passes is not None:
                raise ValueError("pass a fixed passes= OR an adaptive tol=, not both")
            if tol <= 0:
                raise ValueError(f"tol must be > 0, got {tol}")
            if max_passes < 1:
                raise ValueError(f"max_passes must be >= 1, got {max_passes}")
            self._refine_tol_check()
        chunk_rows = None
        if x is not None:
            n = int(jnp.asarray(x).shape[0])
            if n != self.count_:
                raise ValueError(
                    f"refine(x) got {n} rows but the fitted pass folded "
                    f"{self.count_}; the replay must regenerate the SAME "
                    "chunks — pass the array fit() consumed")
            # an array replay must regenerate the SAME chunk boundaries (hence
            # (step, shard) mask keys) the fitted pass folded — the cursor's
            # recorded chunk_rows, which cover ragged partial_fit histories
            # that uniform batch_size re-chunking could not reproduce
            chunk_rows = list(self._cursor.chunk_rows)
        src = None
        if source is not None:
            from repro.stream.engine import normalize_source

            src = normalize_source(source)
        if tol is None:
            refine_mod.run_refine(self.plan, self.spec_, [self],
                                  self._resolve_passes(passes), data=x, source=src,
                                  steps=steps, seed=seed, chunk_rows=chunk_rows)
            return self
        # adaptive: one resuming pass at a time, watching the estimator's own
        # convergence measurement (pure loop control — the replay math is the
        # fixed-passes path's, so refine(tol=) ≡ refine(passes=q) for the q it
        # settles on)
        self.refine_converged_ = False
        for _ in range(int(max_passes)):
            refine_mod.run_refine(self.plan, self.spec_, [self], 1, data=x,
                                  source=src, steps=steps, seed=seed,
                                  chunk_rows=chunk_rows)
            if self._refine_metric() <= tol:
                self.refine_converged_ = True
                break
        return self

    def fit_refine(self, x=None, passes: int | None = None, *,
                   tol: float | None = None, max_passes: int = 16, source=None,
                   steps: int | None = None, seed: int | None = None) -> "SketchedEstimator":
        """One-pass fit + replay refinement in one call.

        The data argument doubles as the replay source: an in-memory ``x`` is
        fit then re-chunked per pass; a ``(seed, step, shard) → (b, p)``
        ``source`` is streamed once then replayed per pass. ``tol=`` switches
        from the fixed ``passes`` count to adaptive refine-until-converged
        (see :meth:`refine`).
        """
        self._refine_check()
        if (x is None) == (source is None):
            raise ValueError("fit_refine needs exactly one of x or source=")
        if x is not None:
            self.fit(x)
        else:
            if steps is None:
                raise ValueError("fit_refine(source=...) needs steps=")
            self.fit_stream(source, steps=steps, seed=seed)
        return self.refine(x, passes, tol=tol, max_passes=max_passes,
                           source=source, steps=steps, seed=seed)

    # ------------------------------------------------------------- utility --

    def sketch(self, x, mask_key: jax.Array | int | None = None) -> SparseRows:
        """The compression operator applied to new rows.

        On a fitted (or fitting) estimator this uses the fitted spec; on a
        fresh one, a THROWAWAY spec is derived from (plan, key) for this call
        only — reading a sketch never pins ``p`` or allocates fold state.

        ``mask_key=None`` reuses the spec's one-shot mask key, so repeated
        ``sketch()`` / ``predict()`` calls sample the SAME coordinates of
        equal inputs (deterministic, but not independent across calls). Pass
        an int (folded into the spec's mask key) or a PRNGKey for an
        independent mask per call.
        """
        x = jnp.asarray(x).astype(self.plan.dtype)
        spec = self.spec_ if self.spec_ is not None else self.plan.spec(x.shape[-1], self.key)
        if mask_key is None:
            bk = None
        elif isinstance(mask_key, int):
            bk = jax.random.fold_in(spec.mask_key(), mask_key)
        else:
            bk = mask_key
        return sketch_mod.sketch(x, spec, batch_key=bk, impl=self.plan.impl)

    def _unmix_vec(self, v_pre: jax.Array) -> jax.Array:
        return sketch_mod.unmix_dense(v_pre[None, :], self.spec_)[0]

    # ------------------------------------------------------------ snapshot --
    # State export/import for checkpoints and repro.sketchserve snapshots:
    # everything a restarted process needs to continue THIS estimator's ingest
    # bit-identically, as a flat {name: array} dict in the EngineState
    # protocol's wire format (repro.stream.state.to_arrays — the same keys the
    # StreamEngine checkpoints). The spec is NOT exported — it re-derives
    # deterministically from (plan, key, p); derived fitted attributes aren't
    # either — finalize() recomputes them from the fold state. Import targets
    # a freshly constructed estimator whose spec is already bound (the
    # importer calls cursor.ensure_spec first).

    def state_arrays(self) -> dict:
        r = self._reducer
        if r is None:
            raise RuntimeError("nothing folded yet — nothing to export")
        if r._step_parts:
            raise RuntimeError(
                "a sharded reducer is mid-step (buffered shard sketches not "
                "yet psum'd); ingest to a step boundary before snapshotting")
        out: dict = {"count": np.int64(self.count_)}
        if r.state is not None:
            out.update(state_mod.to_arrays(r.state))
        if r.retained is not None and r.retained.n:   # batch moments / Lloyd
            s = r.retained.rows()
            out["parts.values"], out["parts.indices"] = s.values, s.indices
            out["parts.rows"] = np.array([s.n], np.int64)
        return out

    def load_state_arrays(self, arrs: dict) -> None:
        if self.spec_ is None:
            raise RuntimeError("bind the spec (cursor.ensure_spec) before "
                               "importing snapshot state")
        r = self._reducer
        self.count_ = int(arrs["count"])
        # the reducer only ever holds a moment/range/fd state — the km kind
        # belongs to SparsifiedKMeans' own slot (its override loads it)
        st = state_mod.from_arrays(arrs, kinds=("moment", "range", "fd"))
        if st is not None:
            r.state = st
        if "parts.values" in arrs:
            r.retained = RetainedSketch(self.spec_.p_pad)
            r.retained.append(SparseRows(jnp.asarray(arrs["parts.values"]),
                                         jnp.asarray(arrs["parts.indices"]),
                                         self.spec_.p_pad))

    # Estimator-level checkpoint/restore — the fold state plus the cursor
    # counters, through the train.checkpoint atomic-rename protocol. restore()
    # rebinds the spec from (plan, key, p) and resumes the chunk cursor, so
    # partial_fit after restore() continues the interrupted pass
    # bit-identically (tests/test_engine_state.py).

    def checkpoint(self, ckpt_dir: str, *, keep_last: int = 3) -> "SketchedEstimator":
        """Write the fold state + ingest cursor to ``ckpt_dir`` (atomic)."""
        if self.spec_ is None:
            raise RuntimeError("nothing folded yet — nothing to checkpoint")
        cur = self._cursor
        extra = {"p": int(self.spec_.p), "chunk": cur.chunk, "count": cur.count,
                 "n_sketches": cur.n_sketches,
                 "chunk_rows": list(cur.chunk_rows)}
        checkpoint_mod.save_arrays(ckpt_dir, cur.chunk, self.state_arrays(),
                                   extra=extra, keep_last=keep_last)
        return self

    def restore(self, ckpt_dir: str) -> "SketchedEstimator":
        """Reset, rebind the spec, and load the latest checkpoint under
        ``ckpt_dir`` — the estimator continues ingest where it stopped."""
        arrs, extra = checkpoint_mod.load_arrays(ckpt_dir)
        self.reset()
        cur = self._cursor
        cur.ensure_spec(int(extra["p"]))
        self.load_state_arrays(arrs)
        cur.chunk = int(extra["chunk"])
        cur.count = int(extra["count"])
        cur.n_sketches = int(extra["n_sketches"])
        cur.chunk_rows = [int(r) for r in extra["chunk_rows"]]
        return self


# ----------------------------------------------------------- the estimators --


class SparsifiedMean(SketchedEstimator):
    """Thm-4 unbiased mean from the sketch alone.

    Fitted: ``mean_`` (p, original domain), ``mean_pre_`` (p_pad,
    preconditioned domain), ``count_``.
    """

    kind = "mean"
    _track_cov = False

    def _finalize(self) -> None:
        mean_pre, _, n = self._reducer.reduce()
        self.mean_pre_ = mean_pre
        self.mean_ = self._unmix_vec(mean_pre)
        self.count_ = int(n)


class SparsifiedCov(SketchedEstimator):
    """Thm-6 unbiased covariance (uncentered second moment) from the sketch.

    Fitted: ``cov_`` ((p_pad, p_pad), PRECONDITIONED domain — the spectrum
    equals the original's since HD is orthonormal), ``mean_pre_``, ``mean_``,
    ``count_``. Use :meth:`cov_original` for the (p, p) original-domain matrix.
    """

    kind = "cov"
    _track_cov = True

    def _on_spec(self, spec: sketch_mod.SketchSpec) -> None:
        if spec.m < 2:
            raise ValueError(f"covariance needs m >= 2 (Thm B4), got m={spec.m}; "
                             "raise gamma/m")
        if self.plan.cov_path == "lowrank":
            raise ValueError(
                "cov_path='lowrank' is a PCA-only factored path (it never forms "
                "the (p, p) matrix this estimator returns); use SparsifiedPCA, "
                "or cov_path='dense'/'compact' for the full covariance")

    def _finalize(self) -> None:
        mean_pre, cov_pre, n = self._reducer.reduce()
        self.mean_pre_ = mean_pre
        self.mean_ = self._unmix_vec(mean_pre)
        self.cov_ = cov_pre
        self.count_ = int(n)

    def cov_original(self) -> jax.Array:
        """(p, p) covariance in the original domain: (HD)ᵀ Ĉ_pre (HD)."""
        c1 = sketch_mod.unmix_dense(self.cov_, self.spec_)        # rows still pre-domain
        return sketch_mod.unmix_dense(c1.T, self.spec_)


class SparsifiedPCA(SketchedEstimator):
    """Principal components from the sketched covariance (paper §V).

    With ``Plan(cov_path="lowrank", rank=l)`` the (p, p) covariance accumulator
    is replaced by the O(l·p) ``repro.lowrank`` spectral states on every
    backend — same fit/finalize contract, and the factored eigenmodel is kept
    on ``cov_lowrank_``. Pick l ≥ 4·n_components (the "range" method finalizes
    l/2 eigenpairs from the 2×-oversampled sketch; "fd" finalizes all l).

    Fitted: ``components_`` ((n_components, p), original domain, rows are PCs),
    ``explained_variance_`` (eigenvalues, descending), ``mean_``, ``count_``,
    ``cov_lowrank_`` (:class:`repro.lowrank.LowRankCov` | None).
    """

    kind = "pca"
    _track_cov = True

    def __init__(self, n_components: int, plan: Plan, key: jax.Array | int = 0):
        self.n_components = int(n_components)
        super().__init__(plan, key)

    def _on_spec(self, spec: sketch_mod.SketchSpec) -> None:
        if spec.m < 2:
            raise ValueError(f"PCA needs m >= 2 (Thm B4 covariance), got m={spec.m}")
        if self.plan.cov_path == "lowrank":
            model_rank = (self.plan.rank // 2 if self.plan.lowrank_method == "range"
                          else self.plan.rank)
            if self.n_components > model_rank:
                raise ValueError(
                    f"n_components={self.n_components} exceeds the rank-{model_rank} "
                    f"eigenmodel of a rank={self.plan.rank} "
                    f"{self.plan.lowrank_method!r} sketch; raise Plan.rank "
                    f"(l ≥ 4·n_components recommended)")

    def _finalize(self) -> None:
        mean_pre, cov_pre, n = self._reducer.reduce()
        if isinstance(cov_pre, lowrank_mod.LowRankCov):
            self.cov_lowrank_ = cov_pre
            comps_pre, evals = cov_pre.top(self.n_components)
        else:
            self.cov_lowrank_ = None
            comps_pre, evals = pca_mod._top_eig(cov_pre, self.n_components)
        self.components_ = sketch_mod.unmix_dense(comps_pre, self.spec_)
        self.explained_variance_ = evals
        self.mean_ = self._unmix_vec(mean_pre)
        self.count_ = int(n)
        self.refine_passes_ = 0           # refine() overwrites after its replay
        self.refine_subspace_change_ = None

    def transform(self, x) -> jax.Array:
        """Project rows onto the fitted components (original domain, uncentered
        — the paper's convention)."""
        return jnp.asarray(x).astype(self.plan.dtype) @ self.components_.T

    def result(self) -> pca_mod.PCAResult:
        return pca_mod.PCAResult(self.components_, self.explained_variance_, self.mean_)

    # ---------------------------------------------------------- refinement --
    # Power iteration against the regenerable source (repro.refine.power):
    # each pass replays every (step, shard) sketch and accumulates Y = S·Q
    # through the SAME RangeState deltas as the first pass (sharded: one
    # fixed-size psum per step via sharded_lowrank), squaring the one-pass
    # gap ratio. Extra fitted attrs: refine_passes_ (int, 0 = one-pass fit)
    # and refine_subspace_change_ ((passes,) max principal-angle sine between
    # consecutive power bases — the per-pass convergence diagnostic).

    def _refine_supported(self) -> bool:
        return (self.plan.cov_path == "lowrank"
                and self.plan.lowrank_method == "range")

    def _refine_check(self) -> None:
        if self.plan.cov_path != "lowrank":
            raise ValueError(
                "fit_refine sharpens the lowrank range-finder's subspace; "
                f"cov_path={self.plan.cov_path!r} accumulates the full "
                "covariance exactly, so its eigendecomposition has no "
                "refinement gap — use Plan(cov_path='lowrank', rank=l)")
        if self.plan.lowrank_method != "range":
            raise ValueError(
                "lowrank_method='fd' has no replayable linear operator (the "
                "SVD-shrink fold is order-dependent); power-iteration "
                "refinement needs lowrank_method='range'")

    def _refine_pass_begin(self, f: int) -> None:
        if f == 0 and not self.refine_passes_:
            # the first basis is free: orth of the ALREADY-FOLDED first-pass
            # state (debiased against Omega) — no extra replay. A repeat
            # refine() instead RESUMES from self._rq (the basis the previous
            # refinement's last pass produced), continuing the iteration.
            self._rq = refine_mod.power_orth(self._reducer.state,
                                             self._reducer._omega, self.spec_.m)
            self._rchanges: list[float] = []
        self._rstate = lowrank_mod.range_init(self.spec_.p_pad, self.plan.rank)
        self._rstep_parts: list[SparseRows] = []

    def _refine_fold(self, s: SparseRows, step: int, shard: int) -> None:
        if self.plan.backend == "sharded":
            self._rstep_parts.append(s)
            if shard == self.plan.n_shards - 1:
                self._refine_flush()
        else:
            self._rstate = lowrank_mod.range_update(self._rstate, s, self._rq,
                                                    impl=self.plan.impl)

    def _refine_flush(self) -> None:
        if not self._rstep_parts:
            return
        step_sketch = _concat_sparse(self._rstep_parts, self.spec_.p_pad)
        delta = sharded_mod.sharded_lowrank(step_sketch, self._rq,
                                            self.plan.resolve_mesh(),
                                            (self.plan.axis,), impl=self.plan.impl)
        self._rstate = lowrank_mod.range_apply(self._rstate, delta)
        self._rstep_parts = []

    def _refine_pass_end(self, f: int, last: bool, signal: bool) -> None:
        self._refine_flush()
        q_new = refine_mod.power_orth(self._rstate, self._rq, self.spec_.m)
        # convergence is watched on the top-n_components columns — the
        # subspace the consumer keeps; wider slices are dominated by the
        # oversampling columns churning in the (near-degenerate) tail
        r = self.n_components
        self._rchanges.append(
            refine_mod.subspace_change(q_new[:, :r], self._rq[:, :r]))
        self._rq_prev, self._rq = self._rq, q_new

    def _refine_end(self, passes: int) -> None:
        self.cov_lowrank_ = refine_mod.power_finalize(self._rstate, self._rq_prev,
                                                      self.spec_.m)
        comps_pre, evals = self.cov_lowrank_.top(self.n_components)
        self.components_ = sketch_mod.unmix_dense(comps_pre, self.spec_)
        self.explained_variance_ = evals
        self.refine_passes_ += passes    # cumulative across repeat refine()s
        self.refine_subspace_change_ = np.asarray(self._rchanges)

    def _refine_metric(self) -> float:
        return float(self.refine_subspace_change_[-1])


# Minibatch K-means steps whose reassignment counts may wait on the device
# before ingest reads them back (SparsifiedKMeans._reassign_host).
_HISTORY_ON_DEVICE = 1024


class SparsifiedKMeans(SketchedEstimator):
    """Sparsified K-means over any backend.

    algorithm="lloyd" (default, paper Alg. 1): the sketch — the γ-compressed
    dataset, which is the point of the method — is retained, and full Lloyd
    (``sparse_kmeans_core``; under the sharded backend, the same solver inside
    the mesh context via ``stream.sharded.sharded_kmeans``) runs at
    finalize. Fitted ``labels_`` covers every row folded.

    algorithm="minibatch": the constant-memory streaming accumulators of
    ``repro.stream.accumulators`` (online Eq. 39 update, r = n_init parallel
    hypotheses) — nothing is retained but the (r, K, p_pad) centers/counts.
    The fold is the same on every backend (``repro.stream.step``: per-step
    deltas against the step-start state), so backends stay
    tolerance-identical; ``labels_`` is None (use :meth:`predict`).

    Mini-batch extras (ROADMAP streaming-K-means items): ``decay`` < 1 is a
    forgetting factor for non-stationary streams — accumulated per-coordinate
    counts shrink by ``decay`` each step before the new deltas fold in, so the
    centers track drifting clusters with effective memory ≈ 1/(1−decay) steps.
    Unless ``track_reassignments=False``, each step's rows are re-assigned
    under the post-update centers and compared to their pre-update assignment;
    the per-step counts (best hypothesis) land on ``reassign_counts_`` /
    ``reassign_fraction_`` — a convergence signal that decays toward zero as
    the solution settles (costs one extra assignment pass per batch).

    Fitted: ``centers_`` ((k, p), original domain), ``centers_pre_``,
    ``objective_``, ``labels_`` (host array), ``n_iter_`` (lloyd), ``count_``,
    ``reassign_counts_`` / ``reassign_fraction_`` ((steps,) arrays; minibatch);
    lloyd also ``seed_rows_`` ((n_init, k): the retained rows each
    hypothesis's K-means++ chose) and ``prev_centers_pre_`` (the best
    hypothesis's centers entering its last iteration).
    """

    kind = "kmeans"
    _track_cov = False
    _needs_moments = False  # centers come from the solver, not Thm-4/6

    def __init__(self, k: int, plan: Plan, key: jax.Array | int = 0, *,
                 n_init: int = 3, max_iter: int = 100, tol: float = 1e-6,
                 algorithm: str = "lloyd", decay: float = 1.0,
                 track_reassignments: bool = True):
        if algorithm not in ("lloyd", "minibatch"):
            raise ValueError(f"algorithm must be 'lloyd' or 'minibatch', got {algorithm!r}")
        if not 0.0 < decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay}")
        if decay < 1.0 and algorithm != "minibatch":
            raise ValueError("decay (forgetting) only applies to the streaming "
                             "algorithm='minibatch' accumulators")
        self.k = int(k)
        self.n_init = int(n_init)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        self.algorithm = algorithm
        self.decay = float(decay)
        self.track_reassignments = bool(track_reassignments) and algorithm == "minibatch"
        self._keep_sketch = algorithm == "lloyd"  # Alg. 1 clusters the retained sketch
        super().__init__(plan, key)

    def reset(self) -> "SparsifiedKMeans":
        super().reset()
        self._km_state: acc.KMeansState | None = None
        # the in-flight step's shard sketches, folded as one step at its
        # last shard (_flush_step) — a step is one device program
        self._km_step_sketches: list[SparseRows] = []
        # per applied step: ((r,) reassignment counts, rows). The folds
        # leave the counts on the device (_reassign_device, a scan's as one
        # (steps, r) block); _reassign_host() reads them where the history
        # is consumed, so ingest never waits on the device for them.
        self._reassign_history: list[tuple[np.ndarray, int]] = []
        self._reassign_device: list[tuple[jax.Array, int]] = []
        return self

    # --------------------------------------------------------- minibatch ----

    def _fold_sketch(self, s: SparseRows, step: int, shard: int) -> None:
        if self.algorithm == "lloyd":
            self._reducer.fold(s, step, shard)
            return
        if self._km_state is None:
            with obs.span("init"):
                self._km_state = acc.kmeans_init(
                    fold_in_str(self.spec_.key, "api-kmeans"), s, self.k,
                    self.n_init, decay=self.decay)
        self._km_step_sketches.append(s)
        if shard == self.plan.n_shards - 1:
            self._flush_step()

    def _flush_step(self) -> None:
        """Fold the buffered step through ``repro.stream.step``: one jitted
        program on the host backends, the mesh-resident shard_map step (one
        psum of the delta) on ``sharded``."""
        if not self._km_step_sketches:
            return
        parts, self._km_step_sketches = self._km_step_sketches, []
        if self.plan.backend == "sharded":
            mesh = _sharded_mesh(self.plan)
            mask = None
            if _is_multiprocess():
                from repro import cluster

                with obs.span("readback", site="assemble"):
                    vals = np.concatenate([np.asarray(s.values) for s in parts])
                    idxs = np.concatenate([np.asarray(s.indices) for s in parts])
                s_cat = SparseRows(
                    cluster.global_rows(vals, mesh, self.plan.axis),
                    cluster.global_rows(idxs, mesh, self.plan.axis),
                    parts[0].p)
                mask = cluster.global_rows(
                    np.ones(vals.shape[0], np.int32), mesh, self.plan.axis)
            else:
                s_cat = _concat_sparse(parts, parts[0].p)
            self._km_state, counts = sharded_mod.sharded_kmeans_step(
                self._km_state, s_cat, mesh, axis=self.plan.axis,
                decay=self.decay,
                track_reassignments=self.track_reassignments, mask=mask)
            rows = s_cat.n      # the global step's rows, on every process
        else:
            with obs.span("step", shards=len(parts)):
                self._km_state, counts = _kmeans_fold_jit(
                    self.track_reassignments, self.decay,
                    *step_mod.algebra())(self._km_state, parts)
            rows = sum(s.n for s in parts)
        if self.track_reassignments:
            self._reassign_device.append((counts, rows))
            if len(self._reassign_device) >= _HISTORY_ON_DEVICE:
                self._reassign_host()

    def _reassign_host(self) -> list[tuple[np.ndarray, int]]:
        """The reassignment history on the host, one ((r,) counts, rows)
        entry per applied step, reading what the folds left on the device in
        one transfer. Ingest reads it only every _HISTORY_ON_DEVICE steps,
        which bounds the device buffers a long stream holds."""
        if self._reassign_device:
            with obs.span("readback", site="reassign_counts"):
                got = jax.device_get(self._reassign_device)
            self._reassign_device = []
            self._reassign_history.extend(
                (c, rows) for counts, rows in got
                for c in np.asarray(counts).reshape(-1, self.n_init))
        return self._reassign_history

    # --------------------------------------------------- multi-process fold --

    def _needs_first_sketch(self) -> bool:
        return self.algorithm == "minibatch" and self._km_state is None

    def _seed_first_sketch(self, s0: SparseRows) -> None:
        self._km_state = acc.kmeans_init(
            fold_in_str(self.spec_.key, "api-kmeans"), s0, self.k, self.n_init,
            decay=self.decay)

    def _step_flush(self) -> None:
        super()._step_flush()
        self._flush_step()

    def _fold_state(self):
        return (super()._fold_state(), self._km_state, self._km_step_sketches)

    # ------------------------------------------------------- scanned ingest --

    def _scan_desc(self) -> tuple | None:
        if self.algorithm != "minibatch":
            return None  # lloyd retains the sketch — host loop only
        if self.plan.backend == "sharded":
            return None  # mesh-resident shard_map fold — host loop only
        # the host-delta minibatch fold is backend-independent (per-step
        # deltas against the step-start state), so the rest scan
        return ("kmeans", self.track_reassignments, self.decay)

    def _scan_prepare(self, cursor: "SketchCursor", xs, step0: int) -> None:
        if self._km_state is None:
            # host-sketch chunk (step0, shard 0) once for the data-dependent
            # init — the scan re-sketches it identically (same mask key)
            spec = cursor.spec
            s0 = sketch_mod.sketch(xs[0, 0], spec,
                                   batch_key=batch_key(spec, step0, 0),
                                   impl=self.plan.impl)
            self._km_state = acc.kmeans_init(
                fold_in_str(spec.key, "api-kmeans"), s0, self.k, self.n_init,
                decay=self.decay)

    def _scan_carry(self):
        return self._km_state

    def _scan_aux(self):
        return None

    def _scan_absorb(self, carry, ys, steps: int, rows_per_step: int) -> None:
        self._km_state = carry
        self.count_ += steps * rows_per_step
        if self.track_reassignments:
            self._reassign_device.append((ys, rows_per_step))  # (steps, n_init)

    # ----------------------------------------------------------- finalize ---

    def _finalize(self) -> None:
        self.reassign_counts_ = None
        self.reassign_fraction_ = None
        if self.algorithm == "minibatch":
            self._flush_step()
            if self._km_state is None:
                raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
            centers_pre, obj = acc.kmeans_finalize(self._km_state)
            history = self._reassign_host()
            if self.track_reassignments and history:
                with obs.span("readback", site="objective"):
                    best = int(np.argmin(np.asarray(self._km_state.obj)))
                cnt = np.array([c[best] for c, _ in history])
                rows = np.array([max(r, 1) for _, r in history])
                self.reassign_counts_ = cnt
                self.reassign_fraction_ = cnt / rows
            self.labels_ = None
            self.n_iter_ = None
            self.count_ = _read_count(self._km_state)
        else:
            centers_pre, obj = self._finalize_lloyd()
        self.centers_pre_ = centers_pre
        self.centers_ = sketch_mod.unmix_dense(centers_pre, self.spec_)
        self.objective_ = obj
        self.refine_passes_ = 0           # refine() overwrites after its replay
        self.refine_reassign_counts_ = None
        self.refine_reassign_fraction_ = None

    def _finalize_lloyd(self):
        """Alg. 1 over the retained sketch (``core.kmeans.lloyd``): the
        seeding and the iterations are dispatched without a wait, and their
        results come back in one read (``readback`` ``site=labels``)."""
        r = self._reducer.retained
        if r is None or not r.n:
            raise RuntimeError("no batches folded yet — call fit()/partial_fit() first")
        reg = obs.default_registry()
        reg.gauge("kmeans.retained.rows").set(r.n)
        reg.gauge("kmeans.retained.bytes").set(r.nbytes)
        init_key = fold_in_str(self.spec_.key, "api-kmeans")
        kw = dict(n_init=self.n_init, max_iter=self.max_iter, tol=self.tol,
                  impl=self.plan.impl)
        if self.plan.backend == "sharded":
            fit = sharded_mod.sharded_lloyd(r.rows(), self.k, init_key,
                                            self.plan.resolve_mesh(), axes=(self.plan.axis,),
                                            **kw)
        else:
            fit = km.lloyd(r.values_t, r.indices_t, r.n, r.p, self.k, init_key, **kw)
        with obs.span("readback", site="labels"):
            labels, n_iter, seeds, steps = jax.device_get(
                (fit.labels, fit.n_iter, fit.seed_rows, fit.steps))
        reg.counter("kmeans.lloyd.iterations").inc(int(steps))
        self.labels_ = labels
        self.n_iter_ = int(n_iter)
        self.seed_rows_ = seeds
        self.prev_centers_pre_ = fit.prev_centers
        return fit.centers, fit.objective

    def predict(self, x) -> jax.Array:
        """Nearest-center labels for new rows (sketched with a one-shot mask)."""
        s = self.sketch(x)
        return acc.kmeans_assign(self.centers_pre_, s)

    # ------------------------------------------------------------ snapshot --

    def state_arrays(self) -> dict:
        out = super().state_arrays()
        if self.algorithm == "minibatch":
            if self._km_step_sketches:
                raise RuntimeError(
                    "the minibatch fold is mid-step (buffered shard sketches); "
                    "ingest to a step boundary before snapshotting")
            if self._km_state is not None:
                out.update(state_mod.to_arrays(self._km_state))
            history = self._reassign_host()
            if history:
                out["km.reassign_counts"] = np.stack([c for c, _ in history])
                out["km.reassign_rows"] = np.array([r for _, r in history],
                                                   np.int64)
        return out

    def load_state_arrays(self, arrs: dict) -> None:
        super().load_state_arrays(arrs)
        if "km.centers" in arrs:
            self._km_state = state_mod.from_arrays(arrs, kinds=("km",))
        if "km.reassign_counts" in arrs:
            cnts = np.asarray(arrs["km.reassign_counts"])
            rows = np.asarray(arrs["km.reassign_rows"]).tolist()
            self._reassign_history = [(cnts[i], int(rows[i]))
                                      for i in range(len(rows))]
            self._reassign_device = []

    # ---------------------------------------------------------- refinement --
    # Two-pass (Alg. 2) replay refinement (repro.refine.kmeans2): each pass
    # re-assigns every replayed row against FROZEN pass-start centers (the
    # best first-pass hypothesis) and rebuilds centers from those consistent
    # assignments — the unbiased per-coordinate center estimator over ONE
    # assignment, instead of the streaming fold's evolving ones. The per-batch
    # delta depends only on the frozen centers, so folds commute and all three
    # backends produce BIT-IDENTICAL refined centers. Extra fitted attrs:
    # refine_passes_, refine_reassign_counts_ / refine_reassign_fraction_ —
    # rows reassigned by each rebuild, continuing the streaming
    # reassign_counts_ convergence signal across passes. The count for the
    # LAST rebuild is only observable one replay later, so when
    # track_reassignments is on, one trailing measurement-only replay runs
    # (rebuild discarded; it also upgrades objective_ to the true objective
    # of the FINAL centers). With tracking off the counts cover the first
    # passes-1 rebuilds and objective_ is measured under the pre-rebuild
    # centers of the last pass.

    def _refine_supported(self) -> bool:
        return self.algorithm == "minibatch" and self.decay == 1.0

    def _refine_check(self) -> None:
        if self.algorithm != "minibatch":
            raise ValueError(
                "algorithm='lloyd' retains the sketch and already iterates "
                "assignment/update to a fixed point on it — there is no "
                "second-pass gap to close; two-pass refinement applies to "
                "the streaming algorithm='minibatch' fold")
        if self.decay < 1.0:
            raise ValueError(
                "two-pass refinement rebuilds centers as a UNIFORM mean over "
                "the whole replayed history, which would resurrect exactly the "
                "stale rows a decay= fit deliberately forgets (and drag the "
                "centers back toward pre-drift positions); refine the "
                "undecayed fit, or keep the decayed one-pass centers "
                "(decay-weighted rebuilds are a ROADMAP item)")

    def _refine_needs_signal(self) -> bool:
        return self.track_reassignments

    def _refine_pass_begin(self, f: int) -> None:
        if f == 0 and not self.refine_passes_:
            # fresh refinement freezes the best first-pass hypothesis (THE
            # selection rule — kmeans_finalize); a repeat refine() resumes
            # from self._rc, the previous refinement's rebuilt centers
            self._rc, _ = acc.kmeans_finalize(self._km_state)
            self._rc_prev = None
            self._rflips: list[tuple[int, int]] = []
        self._r2 = refine_mod.kmeans2_init(self.k, self.spec_.p_pad)

    def _refine_fold(self, s: SparseRows, step: int, shard: int) -> None:
        self._r2 = refine_mod.kmeans2_apply(
            self._r2, refine_mod.kmeans2_delta(s, self._rc, self._rc_prev))

    def _refine_pass_end(self, f: int, last: bool, signal: bool) -> None:
        if self._rc_prev is not None:
            # flips between c_{f-1} and c_f = rows reassigned by rebuild f
            self._rflips.append((int(self._r2.flips), int(self._r2.count)))
        self._robj = self._r2.obj
        if signal:
            # every rebuild so far is measured — a resumed refine() must not
            # re-count the last one, so drop the pending comparison centers
            self._rc_prev = None
        else:
            self._rc_prev = self._rc
            self._rc = refine_mod.kmeans2_centers(self._r2, self._rc)

    def _refine_end(self, passes: int) -> None:
        self.centers_pre_ = self._rc
        self.centers_ = sketch_mod.unmix_dense(self._rc, self.spec_)
        self.objective_ = self._robj
        self.refine_passes_ += passes    # cumulative across repeat refine()s
        if self._rflips:
            cnt = np.array([c for c, _ in self._rflips])
            rows = np.array([max(r, 1) for _, r in self._rflips])
            self.refine_reassign_counts_ = cnt
            self.refine_reassign_fraction_ = cnt / rows

    def _refine_tol_check(self) -> None:
        if not self.track_reassignments:
            raise ValueError(
                "refine(tol=) watches the reassigned-row fraction of each "
                "rebuild, which track_reassignments=False turned off — "
                "re-construct with track_reassignments=True or use a fixed "
                "passes=")

    def _refine_metric(self) -> float:
        return float(self.refine_reassign_fraction_[-1])


# --------------------------------------------------------- grad compressor --


class GradCompressor:
    """The paper's estimator as a stateful gradient compressor — one front door
    over ``core.grad_compress`` sharing the repo's (seed, step, shard) key
    discipline: masks are ``sketch.batch_key(mask_spec(cfg, key), step, shard)``,
    exactly as a stream shard's data masks are.

    Holds the error-feedback residual and a step cursor; ``transform`` (alias
    ``compress``) is the per-step round trip. For jitted training loops keep
    using the pure ``core.grad_compress.compress_grads`` with the same cfg/key
    — the masks are identical by construction.
    """

    def __init__(self, cfg: CompressConfig = CompressConfig(),
                 key: jax.Array | int = 0, shard: int = 0):
        self.cfg = cfg
        self.key = as_key(key)
        self.shard = int(shard)
        self.spec_ = mask_spec(cfg, self.key)
        self.reset()

    def reset(self) -> "GradCompressor":
        self.residual_ = None
        self.step_ = 0
        self.wire_floats_ = 0
        return self

    def transform(self, grads, step: int | None = None):
        """Compress-decompress one gradient pytree; returns ĝ (same structure).

        ``step`` defaults to the internal cursor (auto-incremented); pass the
        trainer's step to stay aligned with a resumed run.
        """
        s = self.step_ if step is None else int(step)
        g_hat, self.residual_, wire = compress_grads(
            grads, self.key, jnp.int32(s), self.cfg,
            residual=self.residual_, shard=self.shard)
        self.wire_floats_ = wire
        self.step_ = s + 1
        return g_hat

    compress = transform
