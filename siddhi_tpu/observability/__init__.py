"""Observability subsystem: spans, percentile histograms, telemetry, export.

The reference engine ships first-class runtime statistics (metrics-core
behind ``@app:statistics`` — ``siddhi-core/pom.xml:79``,
``SiddhiStatisticsManager``); our ``StatisticsManager`` covered counters
and *average* latencies only. Every PERF.md decision so far (the
p99-vs-batch cliff, the router eating ~75% of single-shard throughput)
hinged on tail latency and per-stage attribution, which averages cannot
show — and "Scaling Ordered Stream Processing on Shared-Memory
Multicores" (PAPERS.md) makes the same point for ordered pipelines:
diagnosis needs per-stage queue and latency instrumentation. Four parts:

- ``tracing``:   ``span(...)``, the one span primitive — nested,
                 thread-safe; ring-buffered and exported as Chrome-trace
                 JSON (``chrome://tracing`` / Perfetto), and entered as
                 ``siddhi.<name>`` annotations of a ``jax.profiler``
                 trace, on the device's clock. Wired through compile →
                 plan → jit → pack → junction dispatch → query step →
                 meta pull → emit → output pull → sink publish →
                 persist.
- ``histogram``: fixed-bucket log-spaced (HDR-style) latency histograms
                 with p50/p95/p99, embedded in ``LatencyTracker`` so the
                 query/join/NFA runtimes, the @Async junction batcher,
                 and snapshot persist all gain tails for free.
- ``telemetry``: gauges (@Async queue depth, in-flight batches, WAL
                 size, outstanding cluster pulls), counters
                 (backpressure stalls), and jit-compile events (count,
                 wall-ms, cache hit/miss) — one registry per app plus a
                 process-global one for context-free sites.
- ``export``:    Prometheus text exposition + JSON snapshot, served at
                 ``GET /metrics[/{app}]`` on the REST service
                 (``service/rest.py``), with ``POST /trace/start|stop``
                 dumping a span file.

Off, every instrumented site pays a flag check a batch. What spans and
journeys cost while they are on was measured on the chip with the
benchmark's cells: ``journey.enable()`` with no profiler session against
everything off, and beside it what a profiler trace costs (PERF.md,
section 6, PR 25).
"""

from siddhi_tpu.observability.histogram import Histogram
from siddhi_tpu.observability.telemetry import (
    TelemetryRegistry,
    global_registry,
)
from siddhi_tpu.observability.tracing import TRACER, Tracer, span

__all__ = [
    "Histogram",
    "TRACER",
    "TelemetryRegistry",
    "Tracer",
    "global_registry",
    "span",
]
