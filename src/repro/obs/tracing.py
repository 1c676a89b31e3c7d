"""span() — nested host spans on the profiler's clock, optionally aggregated.

``with span("chunk", rows=n):`` opens a span whose *path* dot-joins it onto
the innermost open span of this thread (``ingest.partial_fit.chunk``), so a
reader of a trace rebuilds the tree from names alone. Every span passes
through :class:`jax.profiler.TraceAnnotation` under its full path, with its
keyword attributes as the event's stats: while the profiler records, the
span lands on the host plane beside the device's operations, on the same
clock. With no profiler running no annotation is made, since it would
record nothing.

A span records its wall time only into a registry its caller passes, as the
``span`` histogram series of its path (read back by :func:`span_totals`);
with none it times nothing and writes nothing. A span never waits for the
device: it measures what the host spends in the block, which for an
asynchronous dispatch is the enqueue.

The ``call`` attribute is inherited: a span that names none carries its
parent's, so every span of one top-level call shares the caller's
identifier. The nesting stack is thread-local, so worker threads trace
independently.
"""
from __future__ import annotations

import threading
import time

from repro.obs.registry import MetricsRegistry

SPAN_METRIC = "span"

try:  # the obs package imports without jax
    from jax.profiler import TraceAnnotation as _Annotation

    _profiling = _Annotation.is_enabled
except ImportError:  # pragma: no cover - jax is installed wherever spans run
    _Annotation = None

    def _profiling() -> bool:
        return False


class _Local(threading.local):
    def __init__(self):
        self.stack = []     # open spans, innermost last, as (path, call)


_tls = _Local()


def current_path() -> str | None:
    """The innermost active span path on this thread, if any."""
    s = _tls.stack
    return s[-1][0] if s else None


def span(name: str, registry: MetricsRegistry | None = None, **attrs) -> "_Span":
    """A span named ``name`` under the innermost open one; ``with span(...)
    as path`` yields its dotted path. ``registry`` receives the span's
    seconds; ``attrs`` become the trace annotation's stats."""
    return _Span(name, registry, attrs)


class _Span:
    __slots__ = ("_name", "_reg", "_attrs", "_ann", "_t0")

    def __init__(self, name, registry, attrs):
        self._name = name
        self._reg = registry
        self._attrs = attrs

    def __enter__(self) -> str:
        stack = _tls.stack
        attrs = self._attrs
        if stack:
            parent, call = stack[-1]
            path = parent + "." + self._name
            if call is not None and "call" not in attrs:
                attrs["call"] = call
        else:
            path = self._name
        stack.append((path, attrs.get("call")))
        if _profiling():
            self._ann = ann = _Annotation(path, **attrs)
            ann.__enter__()
        else:
            self._ann = None
        if self._reg is not None:
            self._t0 = time.perf_counter()
        return path

    def __exit__(self, exc_type, exc, tb) -> None:
        reg = self._reg
        if reg is not None:
            dt = time.perf_counter() - self._t0
        ann = self._ann
        if ann is not None:
            ann.__exit__(None, None, None)
        path = _tls.stack.pop()[0]
        if reg is not None:
            reg.histogram(SPAN_METRIC, path=path).observe(dt)


def span_totals(registry: MetricsRegistry) -> dict[str, dict]:
    """Aggregated per-path span view: ``{path: {count, total_s, p50, p95,
    p99, max}}`` — the read side of a :func:`span` given ``registry``."""
    out: dict[str, dict] = {}
    for m in registry.metrics():
        if m.name == SPAN_METRIC and m.kind == "histogram":
            s = m.summary()
            out[m.labels.get("path", "")] = {
                "count": s["count"], "total_s": s["sum"], "p50": s["p50"],
                "p95": s["p95"], "p99": s["p99"], "max": s["max"]}
    return out
