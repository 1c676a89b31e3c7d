"""StreamEngine — the paper's one-pass pipeline as a single jitted, shardable loop.

Drives ``source → sketch → accumulate → finalize`` (paper §I's streaming and
distributed settings, §IV–V estimators, §VI K-means):

- **source** is any pure function ``(seed, step, shard) → (b, p) batch`` — the
  (seed, step, shard) contract of repro.data.pipeline, so any worker can
  regenerate any batch (straggler backup dispatch, exactly-once by construction);
- **sketch** applies HD then R_i per sample with an *independent mask per
  (step, shard) batch* (fold of the spec's mask key), preserving the per-sample
  independence the estimators' guarantees hinge on;
- **accumulate** folds each step's sketched batches into donated
  constant-memory accumulators (repro.stream.accumulators) — Thm-4 mean,
  Thm-6 covariance, and mini-batch streaming sparsified K-means — through the
  per-step fold of repro.stream.step;
- **finalize** applies the closed-form debiasing once, after the last batch.

Distribution: with ``mesh=``, the update runs under ``shard_map`` — every shard
sketches and assigns locally, and the **only cross-shard traffic is the psum of
the fixed-size accumulator deltas** ((p,) + (p,p) + (r,K,p)·2 per step,
independent of batch size). Single-device and sharded engines fold identical
per-(step, shard) sketches, so they agree to float-sum reordering
(tests/test_stream.py asserts 1e-5).

The estimator API surfaces this fused pass: ``repro.api.fit_many`` drives any
set of consumers from one shared ``source → sketch`` cursor under the same
(seed, step, shard) contract (:func:`normalize_source` is the shared adapter)
and the same step fold.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import sketch as sketch_mod
from repro.core.sampling import SparseRows
from repro.core.sketch import batch_key  # noqa: F401  (re-exported; the repo-wide discipline)
from repro import lowrank as lowrank_mod
from repro import obs
from repro import refine as refine_mod
from repro.stream import accumulators as acc
from repro.stream import step as step_mod
from repro.utils.prng import fold_in_str

Source = Callable[[int, int, int], Any]  # (seed, step, shard) -> (b, p) array


@dataclasses.dataclass
class EngineTelemetry:
    """Opt-in per-step observability for :meth:`StreamEngine.run`.

    Strictly observe-only: the instrumented loop folds bit-identical state to
    an uninstrumented one (tests assert it) — telemetry reads timings, shapes,
    and already-materialized signals, never the stream. Per step it records
    into ``registry``:

    - counters ``engine.steps`` / ``engine.rows`` / ``engine.checkpoints``
      (+ ``engine.reassigned`` when the K-means config tracks reassignments);
    - histogram ``engine.step_seconds`` — wall time of the whole step;
    - the ``span`` histogram series of paths ``engine.source`` (host-side
      batch generation), ``engine.update`` and ``engine.checkpoint``
      (checkpoint writes).
      ``engine.update`` is dispatch time: the jitted update returns once it
      is enqueued, and nothing waits for the device. Its device time is in
      a profile, where the update's sketch/fold/psum phases are
      jax.named_scope-annotated (see ``_build_update``);
    - gauges ``engine.rows_per_sec`` (cumulative over this run) and
      ``engine.state_bytes`` (accumulator footprint — constant in stream
      length by construction, so a drift here is a leak).

    ``step_logger``/``log_every`` add a structured JSONL record per logged
    step (step, rows, rows/sec, phase seconds — ``update_s`` being the
    update's dispatch time — reassign fraction, state bytes, checkpoint
    timestamps); ``on_step`` receives the same record dict
    (the cluster launcher's heartbeat hook).
    """

    registry: obs.MetricsRegistry | None = None
    step_logger: obs.StepLogger | None = None
    log_every: int = 1
    on_step: Callable[[dict], None] | None = None

    def _reg(self) -> obs.MetricsRegistry:
        return self.registry if self.registry is not None else obs.default_registry()

    def emit(self, record: dict) -> None:
        if self.step_logger is not None and record["step"] % self.log_every == 0:
            self.step_logger.log(**record)
        if self.on_step is not None:
            self.on_step(record)


@dataclasses.dataclass(frozen=True)
class StreamKMeansConfig:
    """Mini-batch streaming sparsified K-means: K clusters, r parallel seeds.

    ``decay`` < 1 is the forgetting factor for non-stationary streams: the
    per-coordinate count accumulators shrink by ``decay`` once per psum'd step
    (inside ``kmeans_apply``, so sharded == single-device holds), giving the
    centers an effective memory of ≈ 1/(1−decay) steps.
    """

    k: int
    n_init: int = 3
    decay: float = 1.0
    track_reassignments: bool = False

    def __post_init__(self):
        if not 0.0 < self.decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {self.decay}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EngineState:
    """Everything the engine carries between batches — a donated pytree.

    Exactly one of ``moments`` / ``lowrank`` accumulates the second moment AND
    the Thm-4 mean (RangeState carries sum_w/count itself, so the lowrank path
    runs no moment accumulator — one (p,) scatter and psum per step, not two).

    ``reassign`` is the engine-level K-means convergence signal (present iff
    ``StreamKMeansConfig.track_reassignments``): a ``(total, last)`` pair of
    (r,) int32 counters — rows whose nearest center changed across an apply,
    cumulative and for the last folded step — computed INSIDE the jitted
    update (one extra assignment pass per shard, psum'd with the deltas'
    step), so the drift signal exists without the estimator layer.

    Serialization/merge go through the :mod:`repro.stream.state` protocol:
    ``state.engine_to_arrays`` / ``engine_from_arrays`` / ``engine_merge``.
    """

    moments: acc.MomentState | None
    kmeans: acc.KMeansState | None
    lowrank: lowrank_mod.RangeState | None = None
    reassign: tuple | None = None  # ((r,) int32 total, (r,) int32 last step)

    def tree_flatten(self):
        return (self.moments, self.kmeans, self.lowrank, self.reassign), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Finalized one-pass estimates (mean/cov in the preconditioned domain when
    the spec preconditions; kmeans centers returned in both domains)."""

    mean: jax.Array | None
    cov: jax.Array | None
    count: jax.Array
    centers: jax.Array | None = None        # original domain, (K, p)
    centers_pre: jax.Array | None = None    # preconditioned domain, (K, p_pad)
    kmeans_obj: jax.Array | None = None
    cov_lowrank: "lowrank_mod.LowRankCov | None" = None  # cov_path="lowrank"
    refine_passes: int = 0                  # replay() passes folded into this
    refine_reassigned: tuple | None = None  # rows reassigned by rebuilds 1..q-1
    # engine-level K-means drift signal (StreamKMeansConfig.track_reassignments):
    reassign_total: np.ndarray | None = None   # (r,) cumulative over the run
    reassign_last: np.ndarray | None = None    # (r,) of the last folded step
    reassign_counts: np.ndarray | None = None  # (steps, r) per-step (run() only)


def _normalize_source(source) -> Source:
    """Adapt a source to (seed, step, shard) → batch. seed=None means "the
    source's own default" (0 for plain callables); an explicit seed must not be
    silently ignored, so batch_at objects that can't take one reject it."""
    if callable(source):
        return lambda seed, step, shard: source(0 if seed is None else seed, step, shard)
    if hasattr(source, "batch_at"):
        accepts_seed = "seed" in inspect.signature(source.batch_at).parameters

        def from_obj(seed, step, shard):
            if seed is None:
                return source.batch_at(step, shard)
            if not accepts_seed:
                raise ValueError(
                    "run(seed=...) given, but this source's batch_at() has no seed "
                    "parameter — it streams its constructed seed; pass seed=None")
            return source.batch_at(step, shard, seed=seed)

        return from_obj
    raise TypeError(f"source must be callable or expose batch_at, got {type(source)}")


# the (seed, step, shard) source contract is repo-wide — the estimator layer's
# fit_stream / fit_many consume it through the same adapter
normalize_source = _normalize_source


class StreamEngine:
    """One-pass sharded estimation over a (seed, step, shard) batch stream.

    Parameters
    ----------
    spec: the sketch (p, m, transform, key) — see repro.core.sketch.
    source: ``(seed, step, shard) → (b, p)`` array, or an object with
        ``batch_at(step, shard)`` (e.g. data.pipeline.VectorStreamSource).
    n_shards: logical shards per step. Without a mesh they are folded
        sequentially on one device; with a mesh they run data-parallel.
    mesh / axis: optional jax Mesh and its data axis name; axis size must
        equal ``n_shards``.
    track_cov: accumulate the (p, p) second moment (Thm-6). Disable for
        mean-only streams at very large p.
    kmeans: optional :class:`StreamKMeansConfig` to run mini-batch streaming
        sparsified K-means alongside the moment estimators.
    impl: preconditioning backend forwarded to sketch ("auto" = Pallas kernel
        on TPU, jnp butterfly elsewhere).
    cov_path: "dense" (scatter batch to (b, p), one matmul), "compact"
        (scatter b·m² outer products directly — pick it when γ ≪ 1 and the
        dense (b, p) intermediate would dominate the step's memory), or
        "lowrank" (the repro.lowrank range-finder state: the second-moment
        accumulator shrinks from (p, p) to the (p, rank) projection S·Omega, and
        the per-step psum shrinks with it; finalize returns the factored
        eigenmodel on ``StreamResult.cov_lowrank`` instead of ``cov``).
    rank: sketch width l of the "lowrank" path (required there). The engine's
        lowrank path is the linear range-finder — the order-dependent FD
        variant lives behind the estimator layer (``Plan(lowrank_method="fd")``),
        where folds are sequential by construction.
    """

    def __init__(self, spec: sketch_mod.SketchSpec, source, *, n_shards: int = 1,
                 mesh=None, axis: str = "data", track_cov: bool = True,
                 kmeans: StreamKMeansConfig | None = None, impl: str = "auto",
                 cov_path: str = "dense", rank: int | None = None):
        self.spec = spec
        self.source = _normalize_source(source)
        self.n_shards = int(n_shards)
        self.mesh = mesh
        self.axis = axis
        self.track_cov = track_cov
        self.kmeans = kmeans
        self.impl = impl
        self.cov_path = cov_path
        if mesh is not None and mesh.shape[axis] != self.n_shards:
            raise ValueError(
                f"mesh axis {axis!r} has size {mesh.shape[axis]}, need n_shards={n_shards}")
        # a mesh spanning >1 process runs true multi-host ingest: each process
        # generates ONLY its own shards' batches (repro.cluster assembles the
        # global array from process-local data); state stays replicated and
        # the per-step psum is unchanged.
        self._multiprocess = (mesh is not None and len(
            {d.process_index for d in mesh.devices.flat}) > 1)
        if track_cov and spec.m < 2:
            # fail before streaming, not at finalize (Thm B4 needs m ≥ 2)
            raise ValueError(f"track_cov needs m >= 2, got m={spec.m}; "
                             "raise gamma/m or pass track_cov=False")
        self.lowrank = cov_path == "lowrank" and track_cov
        self._omega = None
        if self.lowrank:
            if rank is None or not 2 <= rank <= spec.p_pad:
                raise ValueError(f"cov_path='lowrank' needs 2 <= rank <= "
                                 f"p_pad={spec.p_pad}, got rank={rank}")
            self.rank = int(rank)
            self._omega = lowrank_mod.omega(spec.key, spec.p_pad, self.rank)
        self._update = jax.jit(self._build_update(), donate_argnums=0)
        self._scan = None  # compiled-once lax.scan over a whole stream
        self._refine_update = None  # lazily jitted replay() step update
        self._refine_scan = None    # compiled-once lax.scan of one replay pass
        self.state: EngineState | None = None  # set by run()/run_scanned()

    # ------------------------------------------------------------ plumbing --

    def sketch_shard(self, x, step, shard) -> SparseRows:
        """Shard ``shard``'s batch of ``step`` sketched under its grid-fixed
        mask key."""
        return sketch_mod.sketch(jnp.asarray(x), self.spec,
                                 batch_key=batch_key(self.spec, step, shard),
                                 impl=self.impl)

    def folds(self) -> tuple:
        """``(field, descriptor, aux)`` for each :class:`EngineState` field
        this engine accumulates: the :mod:`repro.stream.step` fold of each."""
        out = []
        if self.lowrank:
            out.append(("lowrank", ("range", self.impl), self._omega))
        else:
            out.append(("moments", ("moment", self.cov_path), None))
        if self.kmeans is not None:
            out.append(("kmeans", ("kmeans", self.kmeans.track_reassignments,
                                   self.kmeans.decay), None))
        return tuple(out)

    def _build_update(self):
        """update(state, x (n_shards, b, p), step) → state, single-device or
        shard_map'd; both fold the same per-(step, shard) sketches.

        With ``track_reassignments`` the K-means fold also counts the rows
        whose nearest center moved across the apply; the (r,) counts travel
        in ``state.reassign`` and, under a mesh, take the deltas' psum."""
        mesh, axis, folds = self.mesh, self.axis, self.folds()

        def psum(tree):
            with jax.named_scope("obs.psum"):
                return jax.lax.psum(tree, axis)  # the only cross-shard traffic

        # jax.named_scope annotations: zero-cost trace-time names, so an XLA
        # profile splits the fused device step into sketch / fold / psum —
        # the in-jit counterpart of the host-side obs.span timings.
        def update(state, x, step):
            shards = (range(self.n_shards) if mesh is None
                      else [jax.lax.axis_index(axis)])
            with jax.named_scope("obs.sketch"):
                sketches = [self.sketch_shard(x[i], step, sh)
                            for i, sh in enumerate(shards)]
            new = {}
            with jax.named_scope("obs.fold"):
                for field, desc, aux in folds:
                    new[field], counts = step_mod.fold_step(
                        desc, getattr(state, field), aux, sketches,
                        reduce=None if mesh is None else psum)
                    if field == "kmeans" and state.reassign is not None:
                        new["reassign"] = (state.reassign[0] + counts, counts)
            return dataclasses.replace(state, **new)

        if mesh is None:
            return update
        # check_vma=False: the Pallas kernels inside declare no per-axis
        # varying type for their outputs
        return shard_map(update, mesh=mesh, in_specs=(P(), P(axis), P()),
                         out_specs=P(), check_vma=False)

    # ------------------------------------------------------------- running --

    def init_state(self, seed: int | None = None) -> EngineState:
        """Fresh accumulators; K-means hypotheses seed from the step-0 global
        batch (replicated, so sharded and single-device runs start identically)."""
        km = None
        if self.kmeans is not None:
            x0 = self._host_global_batch(seed, 0, device_put=False)
            # shard id n_shards is never used by the stream — an independent mask
            s0 = self.sketch_shard(x0.reshape(-1, x0.shape[-1]), jnp.int32(0), self.n_shards)
            km = acc.kmeans_init(fold_in_str(self.spec.key, "stream-kmeans"), s0,
                                 self.kmeans.k, self.kmeans.n_init,
                                 decay=self.kmeans.decay)
        return self._fresh_state(km)

    def _fresh_state(self, km) -> EngineState:
        reassign = None
        if self.kmeans is not None and self.kmeans.track_reassignments:
            z = jnp.zeros((self.kmeans.n_init,), jnp.int32)
            reassign = (z, z)
        return EngineState(
            moments=(None if self.lowrank
                     else acc.moment_init(self.spec.p_pad, track_cov=self.track_cov)),
            kmeans=km,
            lowrank=(lowrank_mod.range_init(self.spec.p_pad, self.rank)
                     if self.lowrank else None),
            reassign=reassign,
        )

    def _host_global_batch(self, seed, step, device_put: bool = True):
        if device_put and self._multiprocess:
            # multi-host: each process materializes ONLY its own shards' rows
            # and contributes them as the addressable part of one global array
            from repro import cluster

            return cluster.global_shard_batch(self.source, seed, step,
                                              self.mesh, self.axis)
        x = np.stack([np.asarray(self.source(seed, step, s)) for s in range(self.n_shards)])
        if device_put and self.mesh is not None:
            x = jax.device_put(x, NamedSharding(self.mesh, P(self.axis)))
        return x

    def update(self, state: EngineState, x, step) -> EngineState:
        """Fold one global batch x (n_shards, b, p); x's leading axis is the
        shard axis (row-sharded under a mesh)."""
        return self._update(state, x, jnp.int32(step))

    def run(self, steps: int, seed: int | None = None,
            state: EngineState | None = None, *, start_step: int = 0,
            checkpoint_dir: str | None = None,
            checkpoint_every: int = 0,
            telemetry: EngineTelemetry | None = None) -> StreamResult:
        """Fold global batches ``start_step .. steps-1`` from the source.

        ``seed`` is forwarded to the source (None = the source's own default);
        it only selects the data stream — sketch masks key off the spec.

        The loop is an explicit-state fold, resumable from ANY step: a fresh
        call starts at step 0 from :meth:`init_state`; passing ``state=`` and
        ``start_step=`` (e.g. from :meth:`restore_state`) continues a prior
        run bit-identically — the (seed, step, shard) contract regenerates
        every remaining batch and mask, so nothing about the interrupted run
        needs to have been stored beyond the fixed-size state.

        ``checkpoint_every=t`` writes the EngineState to ``checkpoint_dir``
        every t folded steps via ``train.checkpoint``'s atomic protocol
        (multi-process runs: process 0 writes; the state is replicated).

        ``telemetry=`` opts into per-step observability (see
        :class:`EngineTelemetry`). None — the default — leaves the loop
        untouched; enabled, the fold stays bit-identical (observe-only) and
        overhead is gated ≤3% by ``benchmarks/obs_bench.py``."""
        if checkpoint_every and not checkpoint_dir:
            raise ValueError("checkpoint_every needs checkpoint_dir=")
        if state is None:
            if start_step != 0:
                raise ValueError("start_step > 0 needs the state that was "
                                 "current at that step (restore_state)")
            state = self.init_state(seed)
        if self._multiprocess:
            # host-ify so jit replicates identical per-process copies onto the
            # multi-host mesh (init/restored states live on local devices)
            state = jax.tree.map(np.asarray, state)
        track = self.kmeans is not None and self.kmeans.track_reassignments
        history: list[np.ndarray] = []
        tel = telemetry
        if tel is not None:
            reg = tel._reg()
            c_steps, c_rows = reg.counter("engine.steps"), reg.counter("engine.rows")
            h_step = reg.histogram("engine.step_seconds")
            g_rate = reg.gauge("engine.rows_per_sec")
            g_bytes = reg.gauge("engine.state_bytes")
            rows_run, run_t0 = 0, time.perf_counter()
        for step in range(start_step, steps):
            if tel is None:
                state = self.update(state, self._host_global_batch(seed, step), step)
            else:
                t0 = time.perf_counter()
                with obs.span("engine.source", reg):
                    x = self._host_global_batch(seed, step)
                t1 = time.perf_counter()
                with obs.span("engine.update", reg):
                    state = self.update(state, x, step)
                t2 = time.perf_counter()
            if track:
                # copy NOW — the buffer is donated back at the next update
                history.append(np.asarray(state.reassign[1]))
            ckpt_s = None
            if checkpoint_every and (step + 1 - start_step) % checkpoint_every == 0:
                t3 = time.perf_counter()
                if tel is None:
                    self.save_state(checkpoint_dir, step + 1, state, seed=seed)
                else:
                    with obs.span("engine.checkpoint", reg):
                        self.save_state(checkpoint_dir, step + 1, state, seed=seed)
                    ckpt_s = time.perf_counter() - t3
                    reg.counter("engine.checkpoints").inc()
            if tel is not None:
                rows_step = int(x.shape[0]) * int(x.shape[1])
                rows_run += rows_step
                elapsed = time.perf_counter() - run_t0
                state_bytes = sum(
                    int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(state)
                    if hasattr(leaf, "nbytes"))
                c_steps.inc()
                c_rows.inc(rows_step)
                h_step.observe(t2 - t0)
                g_rate.set(rows_run / max(elapsed, 1e-9))
                g_bytes.set(state_bytes)
                record = {"step": step, "rows": rows_step, "rows_total": rows_run,
                          "rows_per_sec": round(rows_run / max(elapsed, 1e-9), 1),
                          "source_s": round(t1 - t0, 6),
                          # dispatch time: nothing waits for the device
                          "update_s": round(t2 - t1, 6),
                          "state_bytes": state_bytes}
                if ckpt_s is not None:
                    record["checkpoint_s"] = round(ckpt_s, 6)
                    record["checkpoint_step"] = step + 1
                if track and history:
                    re_last = history[-1]
                    reg.counter("engine.reassigned").inc(int(re_last.sum()))
                    record["reassign_frac"] = round(
                        float(re_last.mean()) / max(rows_step, 1), 6)
                tel.emit(record)
        self.state = state
        result = self.finalize(state)
        if track and history:
            result = dataclasses.replace(result,
                                         reassign_counts=np.stack(history))
        return result

    # ---------------------------------------------------- checkpoint/restore --

    def save_state(self, ckpt_dir: str, step: int,
                   state: EngineState | None = None,
                   seed: int | None = None) -> None:
        """Checkpoint ``state`` (default: the engine's current one) as
        step ``step`` — the number of steps already folded, i.e. the step a
        restored run resumes at. One writer per cluster: only process 0
        writes (the state is replicated across processes by construction)."""
        state = state if state is not None else self.state
        if state is None:
            raise RuntimeError("no state to checkpoint — run() first or pass "
                               "state=")
        if jax.process_index() != 0:
            return
        from repro.stream import state as state_mod

        state_mod.save_engine(ckpt_dir, step, state, extra={
            "p_pad": int(self.spec.p_pad), "n_shards": self.n_shards,
            "seed": seed})

    def restore_state(self, ckpt_dir: str) -> tuple[EngineState, int]:
        """(state, next_step) from the latest checkpoint under ``ckpt_dir`` —
        feed straight into ``run(steps, state=state, start_step=next_step)``
        to continue, or into ``replay(state=state)`` to refine the restored
        stream without re-running it."""
        from repro.stream import state as state_mod

        state, next_step, extra = state_mod.load_engine(ckpt_dir)
        p_pad = extra.get("p_pad")
        if p_pad is not None and int(p_pad) != int(self.spec.p_pad):
            raise ValueError(f"checkpoint was written at p_pad={p_pad}, this "
                             f"engine has p_pad={self.spec.p_pad}")
        self.state = state
        return state, next_step

    def run_scanned(self, xs) -> StreamResult:
        """Fold a pre-staged stream ``xs (steps, n_shards, b, p)`` as ONE jitted
        lax.scan — the hardware-rate hot loop used by benchmarks/stream_bench.py."""
        state = self.init_from_array(xs)
        if self._scan is None:
            update = self._build_update()

            @jax.jit
            def scan_all(state, xs):
                def body(st, inp):
                    step, x = inp
                    return update(st, x, step), None
                steps = xs.shape[0]
                st, _ = jax.lax.scan(body, state, (jnp.arange(steps, dtype=jnp.int32), xs))
                return st

            self._scan = scan_all
        self.state = self._scan(state, jnp.asarray(xs))
        return self.finalize(self.state)

    def init_from_array(self, xs) -> EngineState:
        km = None
        if self.kmeans is not None:
            x0 = jnp.asarray(xs[0]).reshape(-1, xs.shape[-1])
            s0 = self.sketch_shard(x0, jnp.int32(0), self.n_shards)
            km = acc.kmeans_init(fold_in_str(self.spec.key, "stream-kmeans"), s0,
                                 self.kmeans.k, self.kmeans.n_init,
                                 decay=self.kmeans.decay)
        return self._fresh_state(km)

    # ------------------------------------------------------------ replaying --
    # Second-pass refinement (repro.refine): the (seed, step, shard) contract
    # regenerates every batch AND its mask, so extra passes store nothing.
    # Each pass folds a fixed-size carry — a RangeState accumulating Y = S·Q
    # (PCA power iteration) and/or a KMeans2State accumulating frozen-center
    # assignment sums (two-pass Alg. 2) — through one jitted update per step;
    # under a mesh the only cross-shard traffic is ONE psum of that fixed-size
    # delta per step, exactly like run(). The carry is scan-safe:
    # replay_scanned() folds a whole pass as one lax.scan.

    def _build_refine_update(self):
        """update(carry, x, step, q_mat, frozen, prev) → carry."""
        has_lr, has_km = self.lowrank, self.kmeans is not None

        def local_deltas(x, step, shard, q_mat, frozen, prev):
            s = self.sketch_shard(x, step, shard)
            ld = (lowrank_mod.range_delta(s, q_mat, impl=self.impl)
                  if has_lr else None)
            kd = refine_mod.kmeans2_delta(s, frozen, prev) if has_km else None
            return ld, kd

        def apply(carry, deltas):
            ld, kd = deltas
            cl, ck = carry
            return (lowrank_mod.range_apply(cl, ld) if ld is not None else cl,
                    refine_mod.kmeans2_apply(ck, kd) if kd is not None else ck)

        if self.mesh is None:
            def update(carry, x, step, q_mat, frozen, prev):
                deltas = local_deltas(x[0], step, 0, q_mat, frozen, prev)
                for shard in range(1, self.n_shards):
                    d = local_deltas(x[shard], step, shard, q_mat, frozen, prev)
                    deltas = jax.tree.map(jnp.add, deltas, d)
                return apply(carry, deltas)
            return update

        axis = self.axis

        def sharded_update(carry, x, step, q_mat, frozen, prev):
            deltas = local_deltas(x[0], step, jax.lax.axis_index(axis),
                                  q_mat, frozen, prev)
            deltas = jax.lax.psum(deltas, axis)  # the only cross-shard traffic
            return apply(carry, deltas)

        return shard_map(
            sharded_update, mesh=self.mesh,
            in_specs=(P(), P(axis), P(), P(), P(), P()), out_specs=P(),
            check_vma=False,   # Pallas kernels inside, as in the update above
        )

    def _init_refine_carry(self):
        return (lowrank_mod.range_init(self.spec.p_pad, self.rank)
                if self.lowrank else None,
                refine_mod.kmeans2_init(self.kmeans.k, self.spec.p_pad)
                if self.kmeans is not None else None)

    def _replay_passes(self, fold_pass, passes: int,
                       state: EngineState | None) -> StreamResult:
        """Shared head/tail of replay()/replay_scanned(): per-pass basis
        orthonormalization / center rebuild around ``fold_pass(carry, q,
        frozen, prev) → carry``, then the refined finalize."""
        state = state if state is not None else self.state
        if state is None:
            raise RuntimeError("no stream folded yet — run()/run_scanned() "
                               "first; replay() refines a finished pass")
        if not (self.lowrank or self.kmeans is not None):
            raise ValueError(
                "replay() refines the low-rank PCA basis and/or streaming "
                "K-means centers; this engine tracks neither (dense moment "
                "accumulators are already exact in one pass)")
        if self.kmeans is not None and self.kmeans.decay < 1.0:
            raise ValueError(
                "replay()'s uniform Alg.-2 rebuild would un-forget the "
                "history a decay= stream deliberately down-weights; refine "
                "an undecayed engine (decay-weighted rebuilds are a ROADMAP "
                "item)")
        if passes < 1:
            raise ValueError(f"replay needs passes >= 1, got {passes}")
        m = self.spec.m
        q = q_prev = None
        if self.lowrank:
            q = refine_mod.power_orth(state.lowrank, self._omega, m)
        frozen = prev = None
        if self.kmeans is not None:
            # the best first-pass hypothesis is the frozen Alg.-2 start; prev
            # mirrors it on pass 0 (flips trivially 0 — dropped below) so the
            # jitted update keeps one signature across passes
            frozen, _ = acc.kmeans_finalize(state.kmeans)
            prev = frozen
        flips: list[int] = []
        obj = None
        lr_state = km_state = None
        for r in range(passes):
            carry = fold_pass(self._init_refine_carry(), q, frozen, prev)
            lr_state, km_state = carry
            if self.lowrank:
                q_prev, q = q, refine_mod.power_orth(lr_state, q, m)
            if self.kmeans is not None:
                if r > 0:
                    flips.append(int(km_state.flips))
                obj = km_state.obj
                prev = frozen
                frozen = refine_mod.kmeans2_centers(km_state, frozen)

        if self.lowrank:
            mean = lowrank_mod.range_finalize_mean(lr_state, m)
            count = lr_state.count
            cov = None
            cov_lowrank = refine_mod.power_finalize(lr_state, q_prev, m)
        else:
            base = self.finalize(state)
            mean, cov, count, cov_lowrank = base.mean, base.cov, base.count, None
        centers = centers_pre = None
        if self.kmeans is not None:
            centers_pre = frozen
            centers = sketch_mod.unmix_dense(centers_pre, self.spec)
        return StreamResult(mean=mean, cov=cov, count=count, centers=centers,
                            centers_pre=centers_pre, kmeans_obj=obj,
                            cov_lowrank=cov_lowrank, refine_passes=passes,
                            refine_reassigned=tuple(flips))

    def replay(self, steps: int, seed: int | None = None, passes: int = 1,
               state: EngineState | None = None) -> StreamResult:
        """Refine a finished run() by ``passes`` replays of the same source.

        PCA (cov_path="lowrank"): each pass is one power iteration — the
        replayed operator action S·Q replaces the Gaussian sketch S·Omega,
        squaring the one-pass gap ratio per pass; finalize goes through the
        same LowRankCov core solve. K-means: each pass re-assigns every row
        against frozen pass-start centers and rebuilds them from those
        consistent assignments (two-pass Alg. 2); ``refine_reassigned[r]`` is
        the rows reassigned by rebuild r+1 (observable one replay later, so
        the last rebuild's count needs a ``passes+1``-th measurement replay if
        wanted — the estimator layer's track_reassignments does exactly that).
        ``kmeans_obj`` is the objective under the LAST pass's frozen centers.
        """
        if self._refine_update is None:
            self._refine_update = jax.jit(self._build_refine_update(),
                                          donate_argnums=0)

        def fold_pass(carry, q, frozen, prev):
            for step in range(steps):
                carry = self._refine_update(carry,
                                            self._host_global_batch(seed, step),
                                            jnp.int32(step), q, frozen, prev)
            return carry

        return self._replay_passes(fold_pass, passes, state)

    def replay_scanned(self, xs, passes: int = 1,
                       state: EngineState | None = None) -> StreamResult:
        """replay() over a pre-staged stream ``xs (steps, n_shards, b, p)``,
        each pass folded as ONE jitted lax.scan (the carry is fixed-size by
        construction — scan-safety is the point of the delta algebra)."""
        if self._refine_scan is None:
            update = self._build_refine_update()

            @jax.jit
            def scan_pass(carry, xs, q, frozen, prev):
                def body(c, inp):
                    step, x = inp
                    return update(c, x, step, q, frozen, prev), None
                steps = xs.shape[0]
                c, _ = jax.lax.scan(
                    body, carry, (jnp.arange(steps, dtype=jnp.int32), xs))
                return c

            self._refine_scan = scan_pass
        xs = jnp.asarray(xs)

        def fold_pass(carry, q, frozen, prev):
            return self._refine_scan(carry, xs, q, frozen, prev)

        return self._replay_passes(fold_pass, passes, state)

    # ---------------------------------------------------------- finalizing --

    def finalize(self, state: EngineState | None = None) -> StreamResult:
        state = state if state is not None else self.state
        if state is None:
            raise RuntimeError("no stream folded yet — call run()/run_scanned(), "
                               "or pass an EngineState explicitly")
        if state.lowrank is not None:
            # RangeState carries the Thm-4 accumulators itself (see EngineState)
            mean = lowrank_mod.range_finalize_mean(state.lowrank, self.spec.m)
            count = state.lowrank.count
            cov = None
            cov_lowrank = lowrank_mod.range_finalize(state.lowrank, self.spec.m,
                                                     self._omega)
        else:
            mean = acc.moment_finalize_mean(state.moments, self.spec.m)
            count = state.moments.count
            cov = (acc.moment_finalize_cov(state.moments, self.spec.m)
                   if self.track_cov else None)
            cov_lowrank = None
        centers = centers_pre = obj = None
        if state.kmeans is not None:
            centers_pre, obj = acc.kmeans_finalize(state.kmeans)
            centers = sketch_mod.unmix_dense(centers_pre, self.spec)
        r_total = r_last = None
        if state.reassign is not None:
            r_total = np.asarray(state.reassign[0])
            r_last = np.asarray(state.reassign[1])
        return StreamResult(mean=mean, cov=cov, count=count,
                            centers=centers, centers_pre=centers_pre, kmeans_obj=obj,
                            cov_lowrank=cov_lowrank,
                            reassign_total=r_total, reassign_last=r_last)

    def assign(self, batch: SparseRows, state: EngineState | None = None) -> jax.Array:
        """Labels for already-sketched rows under the best hypothesis' centers."""
        state = state if state is not None else self.state
        if state is None or state.kmeans is None:
            raise RuntimeError("no K-means state — construct the engine with a "
                               "StreamKMeansConfig and run() a stream first")
        centers_pre, _ = acc.kmeans_finalize(state.kmeans)
        return acc.kmeans_assign(centers_pre, batch)
