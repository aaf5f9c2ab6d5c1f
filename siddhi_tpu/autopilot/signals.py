"""Read-only signal snapshot for the autopilot controller.

Everything here is assembled from surfaces the engine ALREADY exports:
``journey.critical_path_report`` (stage quantiles + named bottleneck),
the app's ``TelemetryRegistry`` snapshot (``pipeline.*.inflight``,
``ingest.pool.*``, ``quota.*`` utilization gauges, per-program jit
compile counts). A collect() NEVER issues a device pull — the same scrape-path
discipline as ``GET /metrics`` (gauges read drained instrument lanes
or host mirrors; see ``observability/instruments.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, Optional


@dataclass
class SignalSnapshot:
    """One observation of an app runtime, host-side only."""

    app: str
    # per-query bottleneck verdicts from the critical-path report:
    # {query: {"stage", "kind", "mean_ms", "utilization", ...}}
    bottlenecks: Dict[str, dict] = field(default_factory=dict)
    gauges: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, int] = field(default_factory=dict)
    # sum of per-program jit compiles — the compile-storm signal
    # (export.py renders the per-key detail as siddhi_jit_compiles_total)
    jit_compiles: int = 0
    # quota-utilization gauges with the "quota." prefix stripped
    quota: Dict[str, float] = field(default_factory=dict)
    # max pipeline.<owner>.inflight across owners (0 = nothing pending)
    pipeline_inflight: float = 0.0
    pipeline_depth: int = 1
    # ingest pool: configured workers / live utilization (absent = no pool)
    pool_workers: Optional[int] = None
    pool_utilization: float = 0.0
    pool_queue_depth: float = 0.0
    # routed queries: {query: shard_count}
    routed: Dict[str, int] = field(default_factory=dict)
    fused_groups: int = 0

    def worst_bottleneck(self) -> Optional[dict]:
        """The highest-utilization bottleneck verdict, with its query
        name added under ``"query"`` (None when journeys are off or no
        batch has completed yet)."""
        worst = None
        for q, b in self.bottlenecks.items():
            if not b or b.get("stage") is None:
                continue
            if worst is None or (b.get("utilization") or 0.0) > \
                    (worst.get("utilization") or 0.0):
                worst = dict(b)
                worst["query"] = q
        return worst


def collect(app_runtime) -> SignalSnapshot:
    """Assemble one :class:`SignalSnapshot` from ``app_runtime``'s
    existing observability surfaces. Host reads only."""
    ctx = app_runtime.app_context
    sig = SignalSnapshot(app=ctx.name)
    tel = getattr(ctx, "telemetry", None)
    if tel is not None:
        snap = tel.snapshot()
        sig.gauges = dict(snap.get("gauges", {}))
        sig.counters = dict(snap.get("counters", {}))
        sig.jit_compiles = sum(
            int(v.get("compiles", 0)) for v in snap.get("jit", {}).values())
    for name, val in sig.gauges.items():
        if name.startswith("quota."):
            sig.quota[name[len("quota."):]] = val
        elif name.startswith("pipeline.") and name.endswith(".inflight"):
            sig.pipeline_inflight = max(sig.pipeline_inflight, val or 0.0)
    sig.pipeline_depth = int(getattr(ctx, "pipeline_depth", 1) or 1)
    pool = getattr(ctx, "ingest_pack_pool", None)
    if pool is not None:
        sig.pool_workers = int(pool.workers)
        sig.pool_utilization = float(
            sig.gauges.get("ingest.pool.utilization", 0.0) or 0.0)
        sig.pool_queue_depth = float(
            sig.gauges.get("ingest.pool.queue_depth", 0.0) or 0.0)
    from siddhi_tpu.observability import journey

    if journey.enabled():
        # critical_path_report takes a manager; scope it to this one
        # runtime without touching the (possibly shared) real manager
        shim = SimpleNamespace(app_runtimes={ctx.name: app_runtime})
        try:
            rep = journey.critical_path_report(shim, ctx.name)
            queries = rep["apps"].get(ctx.name, {}).get("queries", {})
            sig.bottlenecks = {
                q: r.get("bottleneck") or {} for q, r in queries.items()}
        except Exception:  # noqa: BLE001 — observation must never throw
            sig.bottlenecks = {}
    for qname, qr in app_runtime.query_runtimes.items():
        layout = getattr(qr, "_route_layout", None)
        if layout is not None:
            sig.routed[qname] = int(layout.n)
    sig.fused_groups = len(getattr(app_runtime, "fused_fanout_groups", ()))
    return sig
