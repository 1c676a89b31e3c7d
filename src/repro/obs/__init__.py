"""repro.obs — full-stack telemetry: metrics, tracing, structured step logs.

Stdlib and numpy, plus ``jax.profiler`` for the span annotations where jax
is installed. The pieces:

- :class:`MetricsRegistry` — thread-safe counters / gauges / histograms
  (p50/p95/p99 from a bounded reservoir), label-keyed series, an in-process
  ``snapshot()`` API, and a shared no-op mode so disabled telemetry is free.
- :func:`span` — nested host spans named by dotted path, each passed
  through ``jax.profiler.TraceAnnotation`` with its attributes, so a
  profile shows them on the device's clock; a span records its wall time
  into a registry only where its caller passes one.
- :class:`StepLogger` / :func:`read_jsonl` — structured JSONL step records.
- :func:`render_exposition` / :class:`MetricsServer` — Prometheus-style text
  exposition and a stdlib scrape endpoint.
- :func:`quantiles` — THE shared percentile helper (benchmarks and launch
  drivers compute latency percentiles through it).

Wired consumers: the ingest path (``SketchCursor`` spans ``ingest.*`` for
each call, chunk, sketch dispatch, consumer fold, host→device copy and
device→host readback; ``finalize.<kind>``), ``StreamEngine.run(telemetry=)``
(per-step engine metrics),
``SketchService`` (its legacy ``stats`` dict is now a registry snapshot),
``repro.cluster.heartbeat`` (per-host liveness gauges on the EngineState wire
format), and the ``repro.kernels.ops`` dispatch counters
(``kernels.dispatch{op=,path=}`` — watch for silent regressions to the jnp
fallback path).
"""
from repro.obs.registry import (  # noqa: F401
    DEFAULT_QUANTILES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_REGISTRY,
    default_registry,
    quantiles,
    set_default_registry,
)
from repro.obs.sinks import (  # noqa: F401
    MetricsServer,
    render_exposition,
    serve_metrics,
)
from repro.obs.steplog import StepLogger, read_jsonl  # noqa: F401
from repro.obs.tracing import (  # noqa: F401
    current_path,
    span,
    span_totals,
)
