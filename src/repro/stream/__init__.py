"""Streaming sketch engine: one-pass, sharded estimation at any p (paper §I, IV–VI).

- engine:       StreamEngine — source → sketch → accumulate → finalize as one
                jitted, optionally shard_map'd loop.
- accumulators: constant-memory delta/apply algebra (Thm-4 mean, Thm-6 cov,
                mini-batch streaming sparsified K-means).
- step:         the per-step fold over that algebra that every driver calls.
- sharded:      one-shot shard_map reductions + the distributed-data entry
                points (shard_rows / sketch_sharded / sharded_kmeans).
- state:        the EngineState lifecycle protocol — merge algebra and
                to_arrays/from_arrays serialization shared by the engine,
                the api estimators, sketchserve snapshots, and elastic
                re-sharding (repro.cluster).
- queued:       QueueSource — live pushed chunks adapted to the
                (seed, step, shard) source contract.
"""
from repro.stream.accumulators import (  # noqa: F401
    KMeansState,
    MomentState,
    kmeans_assign,
    kmeans_finalize,
    kmeans_init,
    moment_finalize_cov,
    moment_finalize_mean,
    moment_init,
)
from repro.stream.engine import (  # noqa: F401
    EngineState,
    EngineTelemetry,
    StreamEngine,
    StreamKMeansConfig,
    StreamResult,
    batch_key,
    normalize_source,
)
from repro.stream.queued import QueueSource  # noqa: F401
from repro.stream.state import (  # noqa: F401
    engine_from_arrays,
    engine_merge,
    engine_to_arrays,
    from_arrays,
    load_engine,
    merge,
    save_engine,
    to_arrays,
)
from repro.stream.sharded import (  # noqa: F401
    shard_rows,
    sharded_cov,
    sharded_kmeans,
    sharded_kmeans_step,
    sharded_mean,
    sharded_moments,
    sketch_sharded,
)
