"""fit_many — ONE compression pass feeds every consumer (the paper's pitch, §I).

Fitting ``SparsifiedPCA`` and ``SparsifiedKMeans`` separately on the same
:class:`Plan` sketches the data twice; :func:`fit_many` registers every
consumer on one shared :class:`~repro.api.estimators.SketchCursor`, so each
per-(step, shard) sketch is computed exactly once and folded into every
consumer's accumulator. Because the consumers are pure folders and the shared
cursor derives the SAME spec (same key) and the SAME per-chunk mask keys that
each consumer's lone ``fit`` would, ``fit_many`` reproduces the separate fits
exactly — on every backend (tests/test_api.py asserts ≤1e-5) — while doing a
single pass of ``sketch_mod.sketch`` per chunk.

Under ``backend="stream" | "sharded"`` this is the StreamEngine's fused
moment+K-means pass surfaced through the estimator API: moments fold into
constant-memory accumulators (sharded: one psum of the fixed-size per-step
delta — nothing is retained past its step), minibatch K-means folds the
engine's per-step summed deltas, and only Lloyd K-means retains the
γ-compressed sketch it clusters at finalize (Alg. 1's defining feature).

    from repro.api import Plan, SparsifiedKMeans, SparsifiedPCA, fit_many

    plan = Plan(backend="stream", gamma=0.05, batch_size=4096)
    pca = SparsifiedPCA(8, plan, key=0)
    km = SparsifiedKMeans(10, plan, key=0)
    run = fit_many(plan, [pca, km], x)      # one sketch pass, both fitted
    pca.components_; km.centers_            # identical to separate fits
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence

import numpy as np

from repro.api.estimators import SketchCursor, SketchedEstimator, as_key
from repro.api.plan import Plan
from repro.core import sketch as sketch_mod
from repro import refine as refine_mod
from repro.train import checkpoint

# Plan fields that determine WHAT the shared sketch is (spec + chunk→key
# mapping). Consumers must agree with the driving plan on these; the backend —
# and the fold choices cov_path / rank / lowrank_method, so an O(rank·p)
# lowrank PCA and a full dense covariance can ride ONE pass — may differ per
# consumer: they are pure fold/execution choices (tests/test_lowrank.py).
SKETCH_FIELDS = ("gamma", "m", "transform", "impl", "batch_size", "n_shards",
                 "dtype")


@dataclasses.dataclass
class SharedSketchRun:
    """Handle over one shared compression pass and its fitted consumers.

    Iterable/indexable like the consumer sequence passed to :func:`fit_many`.
    ``partial_fit`` + ``finalize`` extend the SAME pass (every consumer folds
    the new chunks' sketches once more), mirroring the estimator contract.
    """

    consumers: tuple[SketchedEstimator, ...]
    cursor: SketchCursor

    @property
    def spec(self) -> sketch_mod.SketchSpec:
        return self.cursor.spec

    @property
    def count(self) -> int:
        """Rows folded through the shared pass."""
        return self.cursor.count

    @property
    def n_sketches(self) -> int:
        """sketch() invocations — one per (step, shard) chunk, NOT per consumer."""
        return self.cursor.n_sketches

    def __iter__(self) -> Iterator[SketchedEstimator]:
        return iter(self.consumers)

    def __getitem__(self, i: int) -> SketchedEstimator:
        return self.consumers[i]

    def __len__(self) -> int:
        return len(self.consumers)

    def partial_fit(self, x) -> "SharedSketchRun":
        self.cursor.partial_fit(x)
        return self

    def sync(self) -> "SharedSketchRun":
        """Block until the shared pass's last sketch and every consumer's
        fold state are materialized (the public ingest barrier — what
        api_bench times)."""
        self.cursor.sync()
        return self

    def finalize(self) -> "SharedSketchRun":
        for c in self.consumers:
            if c in self.cursor.consumers:  # skip consumers detached by reset()
                c.finalize()
        return self

    def checkpoint(self, ckpt_dir: str, *, keep_last: int = 3) -> "SharedSketchRun":
        """Checkpoint the shared pass — every consumer's fold state (the
        EngineState protocol wire format, ``SketchedEstimator.state_arrays``)
        plus the ONE shared cursor, atomically via ``train.checkpoint``.
        :func:`restore_run` resumes the pass bit-identically."""
        cur = self.cursor
        if cur.spec is None:
            raise RuntimeError("nothing folded yet — nothing to checkpoint")
        arrays: dict = {}
        for i, c in enumerate(self.consumers):
            for name, v in c.state_arrays().items():
                arrays[f"c{i}/{name}"] = np.asarray(v)
        extra = {"format": "fused-run-v1", "n_consumers": len(self.consumers),
                 "p": int(cur.spec.p), "chunk": cur.chunk, "count": cur.count,
                 "n_sketches": cur.n_sketches,
                 "chunk_rows": list(cur.chunk_rows)}
        checkpoint.save_arrays(ckpt_dir, cur.chunk, arrays, extra=extra,
                               keep_last=keep_last)
        return self


def restore_run(ckpt_dir: str, plan: Plan,
                consumers: Sequence[SketchedEstimator]) -> SharedSketchRun:
    """Rebuild a :class:`SharedSketchRun` from its latest checkpoint.

    ``consumers`` are freshly constructed estimators in the same order (and
    with the same plans/keys) as the checkpointed run's — the checkpoint holds
    fold STATE, not constructors. The restored run continues the interrupted
    pass bit-identically: the shared cursor resumes at the saved chunk index,
    so the next ``partial_fit`` folds under the very (step, shard) mask keys
    the uninterrupted pass would have used.
    """
    arrays, extra = checkpoint.load_arrays(ckpt_dir)
    if extra.get("format") != "fused-run-v1":
        raise ValueError(f"{ckpt_dir} is not a fused-run checkpoint "
                         f"(format={extra.get('format')!r})")
    consumers = tuple(consumers)
    if len(consumers) != int(extra["n_consumers"]):
        raise ValueError(f"checkpoint holds {extra['n_consumers']} consumers, "
                         f"got {len(consumers)}")
    key0 = as_key(consumers[0].key)
    for i, c in enumerate(consumers):
        _check_consumer(plan, c, i, key0)
    cursor = SketchCursor(plan, key0)
    for c in consumers:
        c.reset()
        c._cursor = cursor
        cursor.register(c)
    cursor.ensure_spec(int(extra["p"]))
    for i, c in enumerate(consumers):
        prefix = f"c{i}/"
        sub = {k[len(prefix):]: v for k, v in arrays.items()
               if k.startswith(prefix)}
        c.load_state_arrays(sub)
    cursor.chunk = int(extra["chunk"])
    cursor.count = int(extra["count"])
    cursor.n_sketches = int(extra["n_sketches"])
    cursor.chunk_rows = [int(r) for r in extra["chunk_rows"]]
    return SharedSketchRun(consumers, cursor)


def _check_consumer(plan: Plan, c: SketchedEstimator, i: int, key0) -> None:
    for f in SKETCH_FIELDS:
        mine, theirs = getattr(plan, f), getattr(c.plan, f)
        if f == "dtype":
            mine, theirs = np.dtype(mine), np.dtype(theirs)  # "float32" == jnp.float32
        if mine != theirs:
            raise ValueError(
                f"consumers[{i}] ({type(c).__name__}) was built with "
                f"plan.{f}={theirs!r}, but the shared pass uses {f}={mine!r}; "
                "a shared sketch requires every consumer to agree on the "
                f"sketch geometry fields {SKETCH_FIELDS}")
    if not np.array_equal(np.asarray(key0), np.asarray(c.key)):
        raise ValueError(
            f"consumers[{i}] ({type(c).__name__}) holds a different key than "
            "consumers[0] — a shared sketch means shared randomness; construct "
            "every consumer with the same key")


def fit_many(plan: Plan, consumers: Sequence[SketchedEstimator], data=None, *,
             source=None, steps: int | None = None, seed: int | None = None,
             finalize: bool = True, refine: bool | int = False,
             scan: bool = False) -> SharedSketchRun:
    """Fit every consumer from ONE ``source → sketch → fan-out`` pass.

    Parameters
    ----------
    plan: the shared execution plan. Every consumer's plan must agree with it
        on the sketch geometry fields (:data:`SKETCH_FIELDS`); backends may
        differ per consumer (each reducer folds its own way — the sketches are
        backend-independent).
    consumers: estimator instances, all constructed with the SAME key (shared
        sketch ⇒ shared randomness). They are reset, registered on one shared
        :class:`SketchCursor`, fed, and finalized in place.
    data: in-memory ``(rows, p)`` array, consumed in ``plan.batch_size``
        chunks — exactly like ``estimator.fit``. Mutually exclusive with
        ``source``.
    source / steps / seed: a ``(seed, step, shard) → (b, p)`` stream source
        (the StreamEngine contract) pulled for ``steps`` steps ×
        ``plan.n_shards`` shards — exactly like ``estimator.fit_stream``.
    finalize: pass False to stop after ingest (e.g. to keep feeding via
        ``run.partial_fit``); call ``run.finalize()`` when done.
    refine: run second-pass replay refinement (``repro.refine``) after
        finalize on every consumer that supports it — PCA power iteration on
        the lowrank-range path, two-pass (Alg. 2) minibatch K-means. ``True``
        uses ``plan.refine_passes`` (or 1); an int overrides the pass count.
        Each replay pass regenerates every (step, shard) sketch ONCE and fans
        it out to all refiners — the shared-cursor discipline applied to
        refinement, so one shared-sketch run feeds both refiners. Requires
        ``finalize=True`` (refinement replays a finalized first pass).
    scan: drive in-memory ingest through ONE jitted ``lax.scan`` over full
        (step × n_shards) blocks instead of the per-chunk host loop (mirrors
        ``StreamEngine.run_scanned``) — same sketches, same fold order, results
        match the host loop to float-summation reordering (which is why it is
        opt-in rather than the default). Requires ``data`` (a source pull is
        host-driven by nature) and consumers whose folds run inside a scan:
        stream-backend moments, lowrank PCA (non-sharded range / any-backend
        fd), and minibatch K-means; batch moments, Lloyd K-means, and sharded
        shard_map reductions raise.

    Returns the :class:`SharedSketchRun`; the fitted attributes live on the
    consumer objects themselves, identical (≤1e-5) to what separate ``fit``
    calls would produce — but the data was compressed once, not once per
    consumer.
    """
    consumers = tuple(consumers)
    if not consumers:
        raise ValueError("fit_many needs at least one consumer")
    if (data is None) == (source is None):
        raise ValueError("provide exactly one of data or source=")
    if source is not None and steps is None:
        raise ValueError("source= needs steps=")
    if refine and not finalize:
        raise ValueError("refine= replays a FINALIZED first pass; drop "
                         "finalize=False (or refine later via estimator.refine)")
    for i, c in enumerate(consumers):
        if not isinstance(c, SketchedEstimator):
            raise TypeError(f"consumers[{i}] is {type(c).__name__}, expected a "
                            "SketchedEstimator (SparsifiedMean/Cov/PCA/KMeans)")
    key0 = as_key(consumers[0].key)
    for i, c in enumerate(consumers):
        _check_consumer(plan, c, i, key0)
    refiners: tuple[SketchedEstimator, ...] = ()
    if refine:
        refiners = tuple(c for c in consumers if c._refine_supported())
        if not refiners:
            raise ValueError(
                "refine= given but no consumer supports second-pass "
                "refinement (SparsifiedPCA with cov_path='lowrank'/"
                "lowrank_method='range', or minibatch SparsifiedKMeans)")

    cursor = SketchCursor(plan, key0)
    for c in consumers:
        c.reset()
        c._cursor = cursor      # adopt the shared pass (reset() detaches again)
        cursor.register(c)
    if scan:
        if data is None:
            raise ValueError("scan=True stages in-memory data for lax.scan; "
                             "source= ingest is host-driven — drop scan=True")
        if cursor.scan_descs() is None:
            raise ValueError(
                "scan=True but a consumer cannot fold inside lax.scan "
                "(batch-backend moments, Lloyd K-means, and sharded shard_map "
                "reductions are host-loop only); drop scan=True or switch "
                "those consumers to stream/minibatch/lowrank folds")
        cursor.scan = True

    src = None
    if data is not None:
        cursor.partial_fit(data)
    else:
        from repro.stream.engine import normalize_source

        src = normalize_source(source)
        cursor.fold_source(src, steps, seed)

    run = SharedSketchRun(consumers, cursor)
    if not finalize:
        return run
    run.finalize()
    if refiners:
        passes = (plan.refine_passes or 1) if refine is True else int(refine)
        refine_mod.run_refine(plan, cursor.spec, refiners, passes, data=data,
                              source=src, steps=steps, seed=seed,
                              chunk_rows=(list(cursor.chunk_rows)
                                          if data is not None else None))
    return run
