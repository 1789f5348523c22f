"""repro.obs — stdlib-only observability: metrics registry + span tracing.

The measurement substrate every service layer reports through
(docs/OBSERVABILITY.md is the catalog):

* :class:`MetricsRegistry` — thread-safe counters, gauges, and log-bucketed
  histograms with p50/p95/p99 export; :func:`merge_snapshots` aggregates
  many snapshots (e.g. the per-shard-server ones fetched over the wire by
  ``ShardedDedupService.metrics()``) into one.
* :func:`span` — causal tracing context manager emitting JSONL records
  (trace/span/parent IDs + wall/CPU time + byte counts) when
  ``REPRO_TRACE`` is set; otherwise only its profiler annotation (below),
  or a shared no-op.  :func:`current_context`
  and :func:`scope` carry the causal chain across thread and process seams
  (writer queue, shard RPC).  :func:`set_annotator` puts every span and
  request phase on the JAX profiler's timeline too, as ``repro.<name>``
  (installed by the layer that imports jax).
* :class:`PhaseClock` — exact wall-time partitioner behind the
  ``req.latency_s{op=,phase=}`` request histograms: phases tile the
  request's wall time by construction, so per-phase sums reconcile with
  the root span.

Deliberately *not* lazy and deliberately dependency-free: the numpy-only
shard server processes import this package, so it must stay importable
without jax, numpy, or anything outside the standard library.
"""
from .metrics import (
    BUCKETS_PER_OCTAVE,
    MetricsRegistry,
    PhaseClock,
    bucket_index,
    bucket_value,
    labeled,
    merge_snapshots,
)
from .trace import (
    TRACE_ENV,
    Span,
    current_context,
    enabled,
    scope,
    set_annotator,
    span,
)

__all__ = [
    "BUCKETS_PER_OCTAVE",
    "MetricsRegistry",
    "PhaseClock",
    "Span",
    "TRACE_ENV",
    "bucket_index",
    "bucket_value",
    "current_context",
    "enabled",
    "labeled",
    "merge_snapshots",
    "scope",
    "set_annotator",
    "span",
]
