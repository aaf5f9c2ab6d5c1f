"""Metrics exposition: Prometheus text format + JSON snapshot.

Renders every deployed app's ``StatisticsManager`` (throughput, latency
with p50/p95/p99, named counters, DETAIL memory/buffer probes) and
``TelemetryRegistry`` (gauges, counters, jit-compile events), merged
with the process-global registry, as:

- Prometheus text exposition (v0.0.4) for ``GET /metrics`` — the
  scrapeable surface a production deployment points its collector at;
- a JSON snapshot (``?format=json`` / ``Accept: application/json``) for
  humans and tests.

Naming: structured label sets, not dotted metric names — per-query
latency is ``siddhi_latency_ms{app=...,name=...,quantile=...}``, @Async
depth is ``siddhi_junction_queue_depth{app=...,stream=...}``, and named
counters keep their dotted names as a LABEL VALUE
(``siddhi_counter_total{name="resilience.wal_replayed_batches"}``)
where dots are legal. The well-known ``resilience.*`` counters are
always emitted (0 until the event happens) so dashboards and alerts can
be written before the first failure."""

from __future__ import annotations

import math
import re
import time
from typing import Dict, List, Tuple

from siddhi_tpu.observability.telemetry import global_registry

# --- graftlint R3 declarations (metric-registration parity) ----------
# Every dotted telemetry name registered anywhere in the tree
# (.gauge/.count/.histogram/stat_count) must start with one of these
# prefixes; each prefix maps to a dedicated family below or renders as
# the labeled generic siddhi_counter_total/siddhi_gauge ON PURPOSE.
# A registration with an undeclared prefix, and a declared prefix with
# no remaining registration site, are both lint findings — the PR-6
# "gauges registered on one code path but not its twin" class.
TELEMETRY_PREFIXES = (
    "junction",      # @Async queue depth / stalls / sheds / timeouts
    "fanout",        # fused fan-out group size + dispatch counters
    "pipeline",      # CompletionPump depth + metas/pulls/stalls
    "aggregation",   # rollup buckets, shards, shard WALs, flush_ms
    "shard",         # routed-row skew gauges + exchange_ms
    "join",          # device-join partition occupancy, probe/insert_ms
    "serving",       # admission pool, scatter-gather latency
    "quota",         # overload quota-utilization gauges
    "overload",      # always-on overload counters (generic family)
    "wal",           # ingest-WAL size gauges
    "cluster",       # bounded-pull probe (process registry) + the
                     # multi-process cluster fabric: workers-live /
                     # per-worker acked-seq + WAL gauges, ingest / run /
                     # egress / checkpoint counters, per-worker respawn
                     # and replay counters (siddhi_tpu/cluster/ ->
                     # siddhi_cluster_*)
    "resilience",    # StatisticsManager recovery counters (stat_count)
    "stage",         # batch-journey per-stage service/queue histograms
                     # (observability/journey.py -> siddhi_stage_*)
    "jitcost",       # compiled-program cost gauges
                     # (observability/costmodel.py -> siddhi_jit_cost_*)
    "program_cache", # process-global compiled-program cache: hit/miss/
                     # eviction counters + live-entry size gauge
                     # (core/util/program_cache.py ->
                     # siddhi_program_cache_*; the size gauge is removed
                     # at cache drain)
    "scrape",        # /metrics self-timing (siddhi_scrape_ms)
    "device",        # device-instrument slots riding the meta vector
                     # (observability/instruments.py -> siddhi_device_*)
    "ingest",        # multicore ingest front door: pack-pool gauges,
                     # pack/merge histograms, wire-frame counters
                     # (core/stream/input/pack_pool.py + wire.py ->
                     # siddhi_ingest_*)
    "eligibility",   # build-time strategy-eligibility census counters
                     # (core/eligibility.py register_census ->
                     # siddhi_eligibility_total{surface,code,query})
    "window",        # a scheduler-driven window's TIMER steps and a
                     # folded tumbling window's flushes, per query
                     # (core/query/runtime.py; generic counter family)
    "state",         # a query's dense state: key capacity and bytes
                     # gauges, key-capacity growths counter
                     # (core/query/runtime.py, parallel/mesh.py; generic
                     # gauge and counter families)
    "autopilot",     # closed-loop controller: mode gauge, tick/freeze
                     # counters, per-(knob,direction,reason) decision
                     # counters (siddhi_tpu/autopilot/ ->
                     # siddhi_autopilot_*)
)

# --- graftlint R6 declarations (device-instrument parity) ------------
# Every DATA slot name a step builder may declare in its
# instrument_slots() spec (observability/instruments.Slot). The
# exposition regexes below are BUILT from this tuple, and R6 checks the
# declared set against the Slot(...) construction sites and the
# _consume_check_slot consumers bidirectionally — a slot computed on
# device but never decoded (or declared but never computed) is a lint
# finding, not a silent telemetry hole.
DEVICE_SLOTS = (
    "win_fill",        # window ring live rows (keyed: hottest key)
    "groups",          # distinct group keys touched by the batch
    "nfa_runs",        # live NFA partial-match slots
    "shard_rows",      # per-shard routed rows (device-routed exchange)
    "route_residual",  # receive capacity left on the fullest shard
    "fill.left",       # join build directory fill per partition
    "fill.right",
)
# Structural (kind='check') slots: consumed by a runtime's
# _consume_check_slot hook at drain, never rendered as telemetry.
DEVICE_CHECK_SLOTS = (
    "route_overflow",  # exchange overflow -> FatalQueryError
    "seq",             # join cross-stream sequence verification
)
# Gauge templates that live exactly as long as their registry does —
# per-app gauges die with the app's TelemetryRegistry at shutdown, the
# process-registry entries below are deliberate process-lifetime
# probes. Everything else must have a remove_gauge site or it pins a
# dead probe on /metrics (the lint names this list on violation).
PROCESS_LIFETIME_GAUGES = (
    "junction.*",           # app registry — junctions live with the app
    "pipeline.*.inflight",  # app registry; label-keyed, survives rebuilds
    "wal.*",                # app registry — registered at WAL attach
    "aggregation.*",        # app registry — both rollup paths register
    "quota.*",              # app registry — overload registration
    "join.partition_rows.*",  # app registry — device-join attach
    "shard.rows.*",         # app + process registry (legacy host-router
                            # scope "host" is a deprecated shim)
    "cluster.outstanding_pulls",  # process registry, process-lifetime
    "jitcost.*",            # process registry — a compiled program's
                            # cost record outlives any single app
    "device.*",             # app registry — device-instrument last-value
                            # and capacity gauges die with the app
    "state.*",              # app registry — a query's state gauges
                            # die with the app
)
# ---------------------------------------------------------------------

# operationally load-bearing counters, pre-declared at 0 per app
RESILIENCE_COUNTERS = (
    "resilience.worker_restarts",
    "resilience.wal_replayed_batches",
    "resilience.wal_dropped_batches",
    "resilience.source_retries",
    "resilience.sink_retries",
    "resilience.peer_failures",
    "resilience.peer_recoveries",
    # serving tier (siddhi_tpu/serving/)
    "resilience.query_sheds",
    "resilience.shard_rebuilds",
    "resilience.shard_replay_skips",
    "resilience.shard_replay_gaps",
    # overload armor (siddhi_tpu/resilience/overload.py)
    "resilience.shed_events",
    "resilience.quota_denials",
    "resilience.enqueue_timeouts",
)

_JUNCTION_GAUGE = re.compile(r"^junction\.(?P<stream>.+)\.(?P<kind>"
                             r"queue_depth|inflight_batches)$")
_JUNCTION_STALLS = re.compile(r"^junction\.(?P<stream>.+)"
                              r"\.backpressure_stalls$")
# overload armor (resilience/overload.py): per-stream shed / escalation
# counters + per-app quota-utilization gauges
_JUNCTION_SHEDS = re.compile(r"^junction\.(?P<stream>.+)\.shed_events$")
_JUNCTION_TIMEOUTS = re.compile(r"^junction\.(?P<stream>.+)"
                                r"\.enqueue_timeouts$")
_QUOTA_GAUGE = re.compile(r"^quota\.(?P<resource>queue|pipeline|memory)"
                          r"_utilization(?:\.(?P<stream>.+))?$")
_FANOUT_GAUGE = re.compile(r"^fanout\.(?P<stream>.+)\.group_size$")
_FANOUT_COUNTER = re.compile(r"^fanout\.(?P<stream>.+)\.(?P<kind>"
                             r"dispatches|meta_pulls)$")
_PIPELINE_GAUGE = re.compile(r"^pipeline\.(?P<query>.+)\.inflight$")
# eligibility.<surface>.<CODE>.<query> — surface spellings are the
# core/eligibility.py SURFACES tuple, codes its ReasonCode values
_ELIGIBILITY_COUNTER = re.compile(
    r"^eligibility\.(?P<surface>[a-z_]+)\.(?P<code>[A-Z0-9_]+)"
    r"\.(?P<query>.+)$")
# multicore ingest front door (core/stream/input/): pack-pool health
# gauges, per-sub-batch pack + per-batch ordered-merge histograms, and
# wire-frame ingest counters
_INGEST_POOL_GAUGE = re.compile(r"^ingest\.pool\.(?P<kind>"
                                r"queue_depth|workers|utilization)$")
_INGEST_HIST_FAMILY = {
    "ingest.pack_ms": ("siddhi_ingest_pack_ms",
                       "ingest pack-pool sub-batch encode service time "
                       "(ms; one sample per sequence-numbered sub-batch)"),
    "ingest.merge_ms": ("siddhi_ingest_merge_ms",
                        "ordered-merge time per parallel-packed batch "
                        "(ms; serial dictionary miss resolution + column "
                        "finalize)"),
}
_INGEST_COUNTER_FAMILY = {
    "ingest.wire.frames": ("siddhi_ingest_wire_frames_total",
                           "binary wire frames accepted on "
                           "POST /ingest/{stream}"),
    "ingest.wire.bytes": ("siddhi_ingest_wire_bytes_total",
                          "wire-frame bytes accepted on "
                          "POST /ingest/{stream}"),
    "ingest.wire.events": ("siddhi_ingest_wire_events_total",
                           "events ingested through the wire-format "
                           "front door"),
    "ingest.pool.repacks": ("siddhi_ingest_repacks_total",
                            "sub-batches re-packed inline after a dead "
                            "ingest pack worker (re-packed, never lost)"),
    "ingest.pool.worker_deaths": ("siddhi_ingest_worker_deaths_total",
                                  "ingest pack-pool worker threads that "
                                  "died (respawned by pool/supervisor)"),
    "ingest.wire.decoder_evictions": (
        "siddhi_wire_decoder_evictions_total",
        "wire decoder delta-state entries evicted at the registry LRU "
        "cap (a sender whose state was evicted must WireEncoder.reset())"),
}
# pipeline.metas / pipeline.pulls: metas-per-pull batching ratio;
# pipeline.stalls: forced drains that had to wait on an unready meta
_PIPELINE_COUNTER_FAMILY = {
    "pipeline.stalls": ("siddhi_pipeline_stalls_total",
                        "pipeline drains that blocked on an unready "
                        "__meta__ (producer stalled on the device)"),
    "pipeline.metas": ("siddhi_pipeline_metas_total",
                       "batch metas drained through the dispatch "
                       "pipeline (divide by pulls for the batching "
                       "ratio)"),
    "pipeline.pulls": ("siddhi_pipeline_meta_pulls_total",
                       "device->host round trips made by pipeline "
                       "drains"),
}

# serving tier (siddhi_tpu/serving/): aggregation rollup + scatter-gather
_AGG_BUCKETS = re.compile(r"^aggregation\.(?P<agg>.+)\.(?P<dur>[a-z]+)"
                          r"\.buckets$")
_AGG_SHARDS = re.compile(r"^aggregation\.(?P<agg>.+)\.shards$")
_AGG_SHARD_WAL = re.compile(r"^aggregation\.(?P<agg>.+)\.shard"
                            r"(?P<shard>\d+)\.wal_batches$")
_AGG_FLUSH_HIST = re.compile(r"^aggregation\.(?P<agg>.+)\.flush_ms$")
_SERVING_QUERY_HIST = re.compile(r"^serving\.query\.(?P<dur>[a-z]+)_ms$")
# sharded keyed steps (parallel/mesh.py): per-shard routed-row gauges
# (key-skew visibility) + exchange/prep latency histogram — fed by BOTH
# the legacy host router (scope "host") and the device-routed path
# (scope = query name)
_SHARD_ROWS = re.compile(r"^shard\.rows\.(?P<scope>.+)\.(?P<shard>\d+)$")
_SHARD_EXCHANGE_HIST = re.compile(r"^shard\.exchange_ms\.(?P<scope>.+)$")
# device join engine (core/join/): per-partition build-side occupancy
# gauges + probe/insert host-latency histograms per join query
_JOIN_PART_ROWS = re.compile(r"^join\.partition_rows\.(?P<query>.+)"
                             r"\.(?P<side>left|right)\.(?P<part>\d+)$")
_JOIN_HIST = re.compile(r"^join\.(?P<kind>probe|insert)_ms\.(?P<query>.+)$")
# critical-path profiler (observability/journey.py): per-query per-stage
# service-time and queueing-time histograms of the batch journey
_STAGE_HIST = re.compile(r"^stage\.(?P<query>.+)\.(?P<stage>[a-z_]+)"
                         r"\.(?P<kind>service|queue)_ms$")
# device-instrument slots (observability/instruments.py): per-query
# last-drained value + capacity gauges and per-batch value histograms,
# slot names anchored to the DEVICE_SLOTS declaration above (query
# names may contain dots — the slot tail is the fixed part)
_DEVICE_SLOT_RX = "|".join(
    re.escape(s) for s in sorted(DEVICE_SLOTS, key=len, reverse=True))
_DEVICE_GAUGE = re.compile(
    r"^device\.(?P<query>.+)\.(?P<slot>" + _DEVICE_SLOT_RX +
    r")(?P<cap>\.capacity)?$")
_DEVICE_HIST = re.compile(
    r"^device\.(?P<query>.+)\.(?P<slot>" + _DEVICE_SLOT_RX + r")$")
# compiled-program cost registry (observability/costmodel.py): one gauge
# per (jit key, metric) on the process registry
_JITCOST_GAUGE = re.compile(
    r"^jitcost\.(?P<key>.+)\.(?P<metric>flops|bytes_accessed|arg_bytes|"
    r"out_bytes|temp_bytes|code_bytes|compile_ms)$")
_JITCOST_HELP = {
    "flops": ("siddhi_jit_cost_flops",
              "XLA cost analysis: floating-point ops per execution of "
              "the compiled program"),
    "bytes_accessed": ("siddhi_jit_cost_bytes_accessed",
                       "XLA cost analysis: bytes read+written per "
                       "execution"),
    "arg_bytes": ("siddhi_jit_cost_arg_bytes",
                  "compiled-program argument buffer bytes"),
    "out_bytes": ("siddhi_jit_cost_out_bytes",
                  "compiled-program output buffer bytes"),
    "temp_bytes": ("siddhi_jit_cost_temp_bytes",
                   "compiled-program temp (scratch) buffer bytes"),
    "code_bytes": ("siddhi_jit_cost_code_bytes",
                   "generated code size in bytes"),
    "compile_ms": ("siddhi_jit_cost_compile_ms",
                   "ahead-of-time capture compile wall ms"),
}
# autopilot (siddhi_tpu/autopilot/): decision counters are dotted
# autopilot.decisions.<knob>.<direction>.<rule> — knob / direction /
# rule segments are code-controlled [a-z0-9_] identifiers (never
# user-named), so the dotted split is unambiguous
_AUTOPILOT_DECISION = re.compile(
    r"^autopilot\.decisions\.(?P<knob>[a-z0-9_]+)"
    r"\.(?P<direction>up|down)\.(?P<reason>[a-z0-9_]+)$")
_AUTOPILOT_COUNTER_FAMILY = {
    "autopilot.ticks": ("siddhi_autopilot_ticks_total",
                        "autopilot observe/decide cycles run"),
    "autopilot.freezes": ("siddhi_autopilot_freezes_total",
                          "autopilot ticks skipped by compile-storm "
                          "backoff (jit compiles still climbing)"),
}
# process-global compiled-program cache (core/util/program_cache.py):
# counters on the process registry; hits are first-call executable
# shares (a hit is a compile that did NOT happen). The size family is
# public: tools/fleet_soak.py greps the exposition for it (R3 keeps the
# literal declared HERE only).
PROGRAM_CACHE_SIZE_FAMILY = "siddhi_program_cache_size"
_PROGRAM_CACHE_COUNTER_FAMILY = {
    "program_cache.hits": (
        "siddhi_program_cache_hits_total",
        "first-call program-cache hits: an equal compiled program "
        "(jaxpr + consts + output tree + backend/sharding witness) was "
        "shared instead of compiled"),
    "program_cache.misses": (
        "siddhi_program_cache_misses_total",
        "first-call program-cache misses: no equal program was live, "
        "this jit compiled and registered as the shared executable"),
    "program_cache.evictions": (
        "siddhi_program_cache_evictions_total",
        "program-cache entries evicted (refcount zero at owner "
        "release, LRU zero-ref at the program_cache_max cap, or a "
        "drain)"),
}
_SERVING_COUNTER_FAMILY = {
    "serving.queries": ("siddhi_serving_queries_total",
                        "on-demand queries admitted by the serving tier"),
    "serving.sheds": ("siddhi_serving_shed_total",
                      "on-demand queries shed at the per-endpoint "
                      "admission cap (HTTP 503)"),
    "serving.shard_rebuilds": ("siddhi_serving_shard_rebuilds_total",
                               "aggregation shards rebuilt from "
                               "checkpoint blob + WAL suffix"),
}
# cluster fabric (siddhi_tpu/cluster/): router-side gauges live exactly
# as long as the ClusterRuntime (remove_gauge in shutdown); per-worker
# names carry the worker index as a LABEL, not a metric name
_CLUSTER_WORKER_GAUGE = re.compile(
    r"^cluster\.worker\.(?P<kind>acked_seq|wal_batches)\.(?P<worker>\d+)$")
_CLUSTER_WORKER_COUNTER = re.compile(
    r"^cluster\.worker\.(?P<kind>respawns|replayed_batches|replay_gaps|"
    r"link_drops)\.(?P<worker>\d+)$")
_CLUSTER_WORKER_GAUGE_HELP = {
    "acked_seq": ("siddhi_cluster_worker_acked_seq",
                  "highest global ingest sequence the worker has acked"),
    "wal_batches": ("siddhi_cluster_worker_wal_batches",
                    "retained router-side ingest-WAL batches for the "
                    "worker (replay suffix; trimmed at checkpoint cuts)"),
}
_CLUSTER_WORKER_COUNTER_HELP = {
    "respawns": ("siddhi_cluster_worker_respawns_total",
                 "worker processes respawned after peer-death detection"),
    "replayed_batches": ("siddhi_cluster_worker_replayed_batches_total",
                         "WAL batches replayed into a recovered worker"),
    "replay_gaps": ("siddhi_cluster_worker_replay_gaps_total",
                    "runs unrecoverable at replay (WAL overflow) — "
                    "released as counted gaps, never silent hangs"),
    "link_drops": ("siddhi_cluster_worker_link_drops_total",
                   "worker link sessions dropped (EOF/error on the "
                   "router-worker socket)"),
}
_CLUSTER_COUNTER_FAMILY = {
    "cluster.ingest_batches": ("siddhi_cluster_ingest_batches_total",
                               "batches sequenced by the router ingest "
                               "front door"),
    "cluster.ingest_rows": ("siddhi_cluster_ingest_rows_total",
                            "rows sequenced by the router ingest front "
                            "door"),
    "cluster.runs_sent": ("siddhi_cluster_runs_sent_total",
                          "contiguous same-owner runs relayed to workers"),
    "cluster.runs_acked": ("siddhi_cluster_runs_acked_total",
                           "runs completed (seq-acked) by workers and "
                           "merged in global order"),
    "cluster.egress_rows": ("siddhi_cluster_egress_rows_total",
                            "output rows re-merged into exact global "
                            "order by the egress stitch"),
    "cluster.duplicate_emits_dropped": (
        "siddhi_cluster_duplicate_emits_dropped_total",
        "replayed emissions for already-merged runs dropped at the "
        "egress (the effectively-once dedup)"),
    "cluster.checkpoints": ("siddhi_cluster_checkpoints_total",
                            "cluster-wide checkpoint barriers completed"),
    "cluster.queries": ("siddhi_cluster_queries_total",
                        "scatter-gather on-demand queries served by the "
                        "cluster router"),
}
_SERVING_HIST_FAMILY = {
    "serving.fanout_ms": ("siddhi_serving_fanout_ms",
                          "scatter fan-out time across aggregation "
                          "shards (ms)"),
    "serving.merge_ms": ("siddhi_serving_merge_ms",
                         "ordered cross-shard rollup merge time (ms)"),
}


def _esc(v: str) -> str:
    """Label-VALUE escaping per the text-format spec: backslash, double
    quote, and line feed (stream/app/query names are user-controlled
    SiddhiQL identifiers — a hostile name must not break the sample
    grammar or inject bogus series)."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n")


def _esc_help(v: str) -> str:
    """HELP-text escaping per the spec: backslash and line feed only
    (quotes are legal in HELP)."""
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(v) -> str:
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Families:
    """Accumulates samples grouped per metric family so each family's
    ``# TYPE`` header is emitted exactly once, before its samples."""

    def __init__(self):
        self._fam: Dict[str, Tuple[str, str, List[str]]] = {}

    def add(self, family: str, ftype: str, help_: str,
            labels: Dict[str, str], value, suffix: str = ""):
        rec = self._fam.get(family)
        if rec is None:
            rec = self._fam[family] = (ftype, help_, [])
        lbl = ",".join(f'{k}="{_esc(v)}"' for k, v in labels.items())
        lbl = "{" + lbl + "}" if lbl else ""
        rec[2].append(f"{family}{suffix}{lbl} {_fmt(value)}")

    def render(self) -> str:
        lines = []
        for family in sorted(self._fam):
            ftype, help_, samples = self._fam[family]
            lines.append(f"# HELP {family} {_esc_help(help_)}")
            lines.append(f"# TYPE {family} {ftype}")
            lines.extend(samples)
        return "\n".join(lines) + "\n"


def _add_histogram(fams: _Families, family: str, help_: str,
                   labels: Dict[str, str], snap: dict) -> None:
    """Render one telemetry histogram snapshot as a Prometheus summary
    (quantile samples + _sum/_count), matching the latency-tracker
    exposition shape."""
    for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
        fams.add(family, "summary", help_,
                 {**labels, "quantile": q}, snap.get(key, 0.0))
    fams.add(family, "summary", help_, labels, snap.get("sum", 0.0),
             suffix="_sum")
    fams.add(family, "summary", help_, labels, snap.get("count", 0),
             suffix="_count")


def app_snapshot(rt) -> dict:
    """JSON-ready metrics for one app runtime."""
    sm = rt.app_context.statistics_manager
    return {
        "app": rt.name,
        "statistics": rt.statistics() if sm is not None else {"level": "off"},
        "telemetry": rt.app_context.telemetry.snapshot(),
    }


def json_snapshot(manager) -> dict:
    t0 = time.perf_counter()
    try:
        return {
            "apps": {name: app_snapshot(rt)
                     for name, rt in sorted(manager.app_runtimes.items())},
            "process": global_registry().snapshot(),
        }
    finally:
        _record_scrape_ms(t0)


def _record_scrape_ms(t0: float) -> None:
    """Scrape self-timing (``siddhi_scrape_ms``): the duration lands in
    the process registry AFTER the snapshot is taken, so each scrape
    reports its predecessors — a scrape crossing its SLO is visible on
    the dashboard scraping it."""
    global_registry().histogram("scrape.ms").record(
        (time.perf_counter() - t0) * 1000.0)


def _add_telemetry(fams: _Families, tel_snapshot: dict, app: str):
    base = {"app": app} if app else {}
    for name, v in sorted(tel_snapshot.get("gauges", {}).items()):
        m = _JUNCTION_GAUGE.match(name)
        if m:
            fams.add(f"siddhi_junction_{m.group('kind')}", "gauge",
                     ("@Async junction queue depth"
                      if m.group("kind") == "queue_depth"
                      else "@Async junction in-flight delivery units"),
                     {**base, "stream": m.group("stream")}, v)
        else:
            m = _FANOUT_GAUGE.match(name)
            if m:
                fams.add("siddhi_fanout_group_size", "gauge",
                         "queries fused into one dispatch per stream batch",
                         {**base, "stream": m.group("stream")}, v)
            else:
                m = _PIPELINE_GAUGE.match(name)
                if m:
                    fams.add("siddhi_pipeline_depth", "gauge",
                             "device batches riding the dispatch pipeline",
                             {**base, "query": m.group("query")}, v)
                elif _AGG_SHARD_WAL.match(name):
                    m = _AGG_SHARD_WAL.match(name)
                    fams.add("siddhi_aggregation_shard_wal_batches", "gauge",
                             "retained per-shard WAL batches (rebuild "
                             "replay suffix)",
                             {**base, "name": m.group("agg"),
                              "shard": m.group("shard")}, v)
                elif _AGG_SHARDS.match(name):
                    m = _AGG_SHARDS.match(name)
                    fams.add("siddhi_aggregation_shards", "gauge",
                             "in-process key shards of the aggregation "
                             "rollup state",
                             {**base, "name": m.group("agg")}, v)
                elif _AGG_BUCKETS.match(name):
                    m = _AGG_BUCKETS.match(name)
                    fams.add("siddhi_aggregation_buckets", "gauge",
                             "live rollup buckets per granularity",
                             {**base, "name": m.group("agg"),
                              "duration": m.group("dur")}, v)
                elif _SHARD_ROWS.match(name):
                    m = _SHARD_ROWS.match(name)
                    fams.add("siddhi_shard_rows", "gauge",
                             "batch rows routed to each key shard (last "
                             "batch; skew shows as imbalance)",
                             {**base, "query": m.group("scope"),
                              "shard": m.group("shard")}, v)
                elif _JOIN_PART_ROWS.match(name):
                    m = _JOIN_PART_ROWS.match(name)
                    fams.add("siddhi_join_partition_rows", "gauge",
                             "live build-side rows per join hash "
                             "partition (skew shows as imbalance)",
                             {**base, "query": m.group("query"),
                              "side": m.group("side"),
                              "partition": m.group("part")}, v)
                elif _QUOTA_GAUGE.match(name):
                    m = _QUOTA_GAUGE.match(name)
                    labels = {**base, "resource": m.group("resource")}
                    if m.group("stream"):
                        labels["stream"] = m.group("stream")
                    fams.add("siddhi_quota_utilization", "gauge",
                             "fraction of the app's overload quota in "
                             "use (queue depth / pipeline entries / "
                             "device-memory budget)", labels, v)
                elif _DEVICE_GAUGE.match(name):
                    m = _DEVICE_GAUGE.match(name)
                    if m.group("cap"):
                        fams.add("siddhi_device_instrument_capacity",
                                 "gauge",
                                 "capacity denominator of a device "
                                 "instrument slot (ring size, Wp, "
                                 "rows_per_shard, ...)",
                                 {**base, "query": m.group("query"),
                                  "slot": m.group("slot")}, v)
                    else:
                        fams.add("siddhi_device_instrument", "gauge",
                                 "last drained device-instrument value "
                                 "(rides the per-batch meta pull — "
                                 "zero extra device transfers)",
                                 {**base, "query": m.group("query"),
                                  "slot": m.group("slot")}, v)
                elif _JITCOST_GAUGE.match(name):
                    m = _JITCOST_GAUGE.match(name)
                    family, help_ = _JITCOST_HELP[m.group("metric")]
                    fams.add(family, "gauge", help_,
                             {**base, "key": m.group("key")}, v)
                elif _INGEST_POOL_GAUGE.match(name):
                    m = _INGEST_POOL_GAUGE.match(name)
                    kind = m.group("kind")
                    fams.add(f"siddhi_ingest_pool_{kind}", "gauge",
                             {"queue_depth": "sub-batch tasks queued on "
                                             "the ingest pack pool",
                              "workers": "live ingest pack-pool worker "
                                         "threads",
                              "utilization": "fraction of ingest pack "
                                             "workers busy"}[kind],
                             base, v)
                elif name == "autopilot.mode":
                    fams.add("siddhi_autopilot_mode", "gauge",
                             "closed-loop controller mode per app "
                             "(0=off, 1=dry_run, 2=on)", base, v)
                elif name == "program_cache.size":
                    fams.add(PROGRAM_CACHE_SIZE_FAMILY, "gauge",
                             "live entries in the process-global "
                             "compiled-program cache (distinct shared "
                             "executables)", base, v)
                elif name == "cluster.workers.live":
                    fams.add("siddhi_cluster_workers_live", "gauge",
                             "worker processes with a live attached link "
                             "(out of cluster_workers)", base, v)
                elif _CLUSTER_WORKER_GAUGE.match(name):
                    m = _CLUSTER_WORKER_GAUGE.match(name)
                    family, help_ = _CLUSTER_WORKER_GAUGE_HELP[
                        m.group("kind")]
                    fams.add(family, "gauge", help_,
                             {**base, "worker": m.group("worker")}, v)
                elif name in ("serving.pool.pending", "serving.pool.active"):
                    kind = name.rsplit(".", 1)[1]
                    fams.add(f"siddhi_serving_pool_{kind}", "gauge",
                             ("on-demand queries admitted and not yet "
                              "finished" if kind == "pending"
                              else "on-demand queries currently "
                                   "executing"), base, v)
                else:
                    fams.add("siddhi_gauge", "gauge",
                             "registered telemetry gauge",
                             {**base, "name": name}, v)
    for name, v in sorted(tel_snapshot.get("counters", {}).items()):
        m = _JUNCTION_STALLS.match(name)
        if m:
            fams.add("siddhi_junction_backpressure_stalls_total", "counter",
                     "producer sends that blocked on a full @Async queue",
                     {**base, "stream": m.group("stream")}, v)
            continue
        m = _JUNCTION_SHEDS.match(name)
        if m:
            fams.add("siddhi_junction_shed_events_total", "counter",
                     "events shed by overload admission (shed_oldest / "
                     "shed_newest past the queue quota)",
                     {**base, "stream": m.group("stream")}, v)
            continue
        m = _JUNCTION_TIMEOUTS.match(name)
        if m:
            fams.add("siddhi_junction_enqueue_timeouts_total", "counter",
                     "bounded enqueue waits that timed out and escalated "
                     "to the supervisor",
                     {**base, "stream": m.group("stream")}, v)
            continue
        m = _ELIGIBILITY_COUNTER.match(name)
        if m:
            fams.add("siddhi_eligibility_total", "counter",
                     "build-time strategy-eligibility census: queries "
                     "classified per surface (route / fusion / "
                     "join_engine / join_pipeline) with stable reason "
                     "codes (core/eligibility.py; ELIGIBLE = the "
                     "strategy applies)",
                     {**base, "surface": m.group("surface"),
                      "code": m.group("code"),
                      "query": m.group("query")}, v)
            continue
        m = _FANOUT_COUNTER.match(name)
        if m:
            fams.add(f"siddhi_fanout_{m.group('kind')}_total", "counter",
                     ("fused fan-out device dispatches (one per group "
                      "per stream batch)"
                      if m.group("kind") == "dispatches"
                      else "fused fan-out combined __meta__ round trips"),
                     {**base, "stream": m.group("stream")}, v)
            continue
        m = _AUTOPILOT_DECISION.match(name)
        if m:
            fams.add("siddhi_autopilot_decisions_total", "counter",
                     "autopilot policy decisions (includes dry_run and "
                     "cooldown/damped-blocked decisions; every entry in "
                     "the GET /autopilot decision log counts here once)",
                     {**base, "knob": m.group("knob"),
                      "direction": m.group("direction"),
                      "reason": m.group("reason")}, v)
            continue
        m = _CLUSTER_WORKER_COUNTER.match(name)
        if m:
            family, help_ = _CLUSTER_WORKER_COUNTER_HELP[m.group("kind")]
            fams.add(family, "counter", help_,
                     {**base, "worker": m.group("worker")}, v)
            continue
        fam = _PIPELINE_COUNTER_FAMILY.get(name)
        if fam is None:
            fam = _SERVING_COUNTER_FAMILY.get(name)
        if fam is None:
            fam = _CLUSTER_COUNTER_FAMILY.get(name)
        if fam is None:
            fam = _INGEST_COUNTER_FAMILY.get(name)
        if fam is None:
            fam = _AUTOPILOT_COUNTER_FAMILY.get(name)
        if fam is None:
            fam = _PROGRAM_CACHE_COUNTER_FAMILY.get(name)
        if fam is not None:
            fams.add(fam[0], "counter", fam[1], base, v)
            continue
        fams.add("siddhi_counter_total", "counter",
                 "named event counter",
                 {**base, "name": name}, v)
    for name, snap in sorted(tel_snapshot.get("histograms", {}).items()):
        fam = _SERVING_HIST_FAMILY.get(name) or _INGEST_HIST_FAMILY.get(name)
        labels = dict(base)
        if fam is not None:
            family, help_ = fam
        else:
            m = _AGG_FLUSH_HIST.match(name)
            if m:
                family = "siddhi_aggregation_flush_ms"
                help_ = "aggregation ingest fold latency per batch (ms)"
                labels["name"] = m.group("agg")
            elif _SHARD_EXCHANGE_HIST.match(name):
                m = _SHARD_EXCHANGE_HIST.match(name)
                family = "siddhi_shard_exchange_ms"
                help_ = ("host time spent routing/prepping one batch for "
                         "the sharded keyed step (ms; device-routed path "
                         "pays only pad+precheck here)")
                labels["query"] = m.group("scope")
            elif _JOIN_HIST.match(name):
                m = _JOIN_HIST.match(name)
                family = f"siddhi_join_{m.group('kind')}_ms"
                help_ = (
                    "host prep+pack time per join side batch (ms)"
                    if m.group("kind") == "insert"
                    else "probe dispatch+finish time per join side "
                         "batch (ms)")
                labels["query"] = m.group("query")
            elif _STAGE_HIST.match(name):
                m = _STAGE_HIST.match(name)
                if m.group("kind") == "service":
                    family = "siddhi_stage_ms"
                    help_ = ("batch-journey per-stage service time (ms) "
                             "— see observability/journey.py stage "
                             "glossary")
                else:
                    family = "siddhi_stage_queue_ms"
                    help_ = ("batch-journey per-stage queueing/slack "
                             "time (ms)")
                labels["query"] = m.group("query")
                labels["stage"] = m.group("stage")
            elif _DEVICE_HIST.match(name):
                m = _DEVICE_HIST.match(name)
                family = "siddhi_device_instrument_value"
                help_ = ("per-batch device-instrument slot value "
                         "(observability/instruments.py slot glossary)")
                labels["query"] = m.group("query")
                labels["slot"] = m.group("slot")
            elif name == "scrape.ms":
                family = "siddhi_scrape_ms"
                help_ = "/metrics scrape self-timing (ms)"
            else:
                m = _SERVING_QUERY_HIST.match(name)
                if m:
                    family = "siddhi_serving_query_ms"
                    help_ = ("on-demand store-query latency per "
                             "granularity (ms)")
                    labels["granularity"] = m.group("dur")
                else:
                    family = "siddhi_histogram_ms"
                    help_ = "registered telemetry histogram (ms)"
                    labels["name"] = name
        _add_histogram(fams, family, help_, labels, snap)
    for key, rec in sorted(tel_snapshot.get("jit", {}).items()):
        kl = {**base, "key": key}
        fams.add("siddhi_jit_compiles_total", "counter",
                 "jitted step functions compiled", kl, rec["compiles"])
        fams.add("siddhi_jit_compile_ms_total", "counter",
                 "wall-clock ms spent in first-call jit compiles", kl,
                 rec["compile_ms"])
        fams.add("siddhi_jit_cache_hits_total", "counter",
                 "jitted step cache hits", kl, rec["hits"])


def _add_statistics(fams: _Families, rt):
    app = rt.name
    sm = rt.app_context.statistics_manager
    report = rt.statistics() if sm is not None else {"level": "off"}
    fams.add("siddhi_statistics_level", "gauge",
             "statistics level (0=off 1=basic 2=detail)",
             {"app": app},
             {"off": 0, "basic": 1, "detail": 2}.get(report.get("level"), 0))
    for name, t in sorted(report.get("throughput", {}).items()):
        fams.add("siddhi_stream_events_total", "counter",
                 "events published through the stream junction",
                 {"app": app, "stream": name}, t["events"])
        fams.add("siddhi_stream_batches_total", "counter",
                 "batches published through the stream junction",
                 {"app": app, "stream": name}, t["batches"])
    for name, lat in sorted(report.get("latency", {}).items()):
        labels = {"app": app, "name": name}
        for q in ("0.5", "0.95", "0.99"):
            key = {"0.5": "p50_ms", "0.95": "p95_ms", "0.99": "p99_ms"}[q]
            fams.add("siddhi_latency_ms", "summary",
                     "per-stage batch processing latency (ms)",
                     {**labels, "quantile": q}, lat.get(key, 0.0))
        fams.add("siddhi_latency_ms", "summary",
                 "per-stage batch processing latency (ms)",
                 labels, lat.get("total_ms", 0.0), suffix="_sum")
        fams.add("siddhi_latency_ms", "summary",
                 "per-stage batch processing latency (ms)",
                 labels, lat["batches"], suffix="_count")
        fams.add("siddhi_latency_ms_max", "gauge",
                 "max batch processing latency (ms)",
                 labels, lat.get("max_ms", 0.0))
    counters = dict(report.get("counters", {}))
    for name in RESILIENCE_COUNTERS:
        counters.setdefault(name, 0)
    for name, v in sorted(counters.items()):
        fams.add("siddhi_counter_total", "counter", "named event counter",
                 {"app": app, "name": name}, v)
    for name, v in sorted(report.get("memory_bytes", {}).items()):
        fams.add("siddhi_state_memory_bytes", "gauge",
                 "dense state footprint (bytes)",
                 {"app": app, "name": name}, v)
    for name, v in sorted(report.get("buffered_events", {}).items()):
        fams.add("siddhi_buffered_events", "gauge",
                 "pending buffered events/batches",
                 {"app": app, "name": name}, v)


def prometheus_text(manager, app_name=None) -> str:
    """Prometheus text exposition for every app (or one app) plus the
    process-global telemetry. Scrape hygiene: this function takes NO app
    barrier and makes no device pulls beyond registered gauges (which
    are themselves cached or host-side — a wedged worker or a busy app
    must never stall a scrape), and times itself into
    ``siddhi_scrape_ms``."""
    t0 = time.perf_counter()
    try:
        fams = _Families()
        runtimes = manager.app_runtimes
        if app_name is not None:
            rt = runtimes.get(app_name)
            if rt is None:
                raise KeyError(f"app '{app_name}' is not deployed")
            runtimes = {app_name: rt}
        for name in sorted(runtimes):
            rt = runtimes[name]
            _add_statistics(fams, rt)
            _add_telemetry(fams, rt.app_context.telemetry.snapshot(), name)
        _add_telemetry(fams, global_registry().snapshot(), "")
        return fams.render()
    finally:
        _record_scrape_ms(t0)
