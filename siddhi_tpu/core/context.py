"""Dependency-injection contexts threaded through the runtime.

Mirror of the reference's ``SiddhiContext`` (per-manager),
``SiddhiAppContext`` (per-app: executors, snapshot service, playback clock,
root timestamp) and ``SiddhiQueryContext`` (per-query state-holder factory)
— ``core/config/*.java``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from siddhi_tpu.core.event import StringDictionary


class SiddhiContext:
    """Per-SiddhiManager shared services (reference ``SiddhiContext.java``)."""

    def __init__(self):
        self.extensions: Dict[str, type] = {}
        self.persistence_store = None
        self.incremental_persistence_store = None
        self.config_manager = None
        self.attributes: Dict[str, object] = {}


class TimestampGenerator:  # graftlint: disable=R8 — listener list is
    # mutated at single-threaded wiring time only; the heartbeat thread
    # iterates a snapshot under the app barrier, and one-shot listeners
    # remove themselves inside that same barrier'd iteration
    """Event/wall clock (reference ``util/timestamp/TimestampGeneratorImpl.java:31``):
    live mode returns wall time; playback mode returns the last event
    timestamp (+ configurable idle increment handled by the scheduler)."""

    def __init__(self):
        self.playback = False
        self._last_event_ts: int = -1
        self._increment_listeners = []
        # @app:playback(idle.time, increment) heartbeat (reference
        # TimestampGeneratorImpl idle task): when no event arrives for
        # idle_ms of WALL time, the event clock advances by increment_ms
        self._hb_idle_ms: int = 0
        self._hb_increment_ms: int = 0
        self._hb_thread = None
        self._hb_stop = None

    def current_time(self) -> int:
        if self.playback and self._last_event_ts >= 0:
            return self._last_event_ts
        return int(time.time() * 1000)

    def set_current_timestamp(self, ts: int):
        if ts > self._last_event_ts:
            self._last_event_ts = ts
            # snapshot: one-shot listeners remove themselves mid-iteration
            for listener in tuple(self._increment_listeners):
                listener(ts)
        if self._hb_idle_ms and self._hb_thread is None:
            self._start_heartbeat()

    def configure_heartbeat(self, idle_ms: int, increment_ms: int):
        self._hb_idle_ms = int(idle_ms)
        self._hb_increment_ms = int(increment_ms)

    def set_heartbeat_barrier(self, lock):
        """The app's ingestion barrier (snapshot quiesce gate): heartbeat
        ticks advance the clock under it so they serialize with
        InputHandler.send and persistence snapshots."""
        self._hb_barrier = lock

    def _start_heartbeat(self):
        import threading

        self._hb_stop = threading.Event()
        stop = self._hb_stop
        barrier = getattr(self, "_hb_barrier", None) or threading.RLock()

        def _run():
            seen = self._last_event_ts
            while not stop.wait(self._hb_idle_ms / 1000.0):
                with barrier:
                    cur = self._last_event_ts
                    if cur == seen and cur >= 0 and not stop.is_set():
                        # idle: advance the event clock (fires timers)
                        self.set_current_timestamp(cur + self._hb_increment_ms)
                    seen = self._last_event_ts

        self._hb_thread = threading.Thread(
            target=_run, name="playback-heartbeat", daemon=True)
        self._hb_thread.start()

    def stop_heartbeat(self):
        # zero idle_ms FIRST: the lazy-start guard in set_current_timestamp
        # must never resurrect a thread after shutdown (a tick in flight
        # could otherwise re-enter it with _hb_thread already None)
        self._hb_idle_ms = 0
        if self._hb_stop is not None:
            self._hb_stop.set()
            self._hb_thread = None
            self._hb_stop = None

    def reset_timestamp(self, ts: int):
        """Force the event clock (restore/rollback): unlike
        ``set_current_timestamp`` this may move BACKWARD, and fires no
        time-change listeners (restored timers re-arm separately)."""
        self._last_event_ts = int(ts)

    def add_time_change_listener(self, fn):
        self._increment_listeners.append(fn)

    def remove_time_change_listener(self, fn):
        """One-shot listeners (e.g. playback head-wait arming) unregister
        themselves so the per-event clock path stays listener-free."""
        try:
            self._increment_listeners.remove(fn)
        except ValueError:
            pass

    def once_first_time(self, fn):
        """Run ``fn(first_ts)`` when the event clock first advances
        (playback arming: wall time is unreachable by the event clock, so
        periodic cycles and quiet windows anchor at the FIRST event ts).
        Returns a cancel() callable — callers MUST cancel on re-arm or
        job cancellation, or a stale anchor starts a second chain."""
        def _listener(ts: int):
            self.remove_time_change_listener(_listener)
            fn(ts)

        self.add_time_change_listener(_listener)

        def cancel():
            self.remove_time_change_listener(_listener)

        return cancel


class SiddhiAppContext:
    """Per-app context (reference ``core/config/SiddhiAppContext.java``)."""

    def __init__(self, siddhi_context: SiddhiContext, name: str):
        self.siddhi_context = siddhi_context
        self.name = name
        self.timestamp_generator = TimestampGenerator()
        self.string_dictionary = StringDictionary()
        self.snapshot_service = None
        self.scheduler = None
        self.statistics_manager = None
        # always-on telemetry registry (observability/telemetry.py):
        # gauges (@Async queue depth, WAL size), backpressure counters,
        # jit-compile events — scraped via GET /metrics; kept separate
        # from statistics_manager, which only exists under
        # @app:statistics and gates by level
        from siddhi_tpu.observability.telemetry import TelemetryRegistry

        self.telemetry = TelemetryRegistry()
        # bind the registry to this context: InstrumentedJit reads the
        # program-cache knobs through it, and the registry INSTANCE is
        # the app's identity-pinned owner token in the process-global
        # compiled-program cache (core/util/program_cache.py) — unique
        # per runtime, so a blue/green replace's old-runtime shutdown
        # can never release the new runtime's refs
        self.telemetry.app_context = self
        self.telemetry.owner_name = name
        self.playback = False
        self.enforce_order = False
        self.root_metrics_level = "OFF"
        # key-capacity defaults for dense state (padded, grows by recompile)
        self.initial_key_capacity = 16
        # ring-buffer capacity for unbounded (time-based) windows
        self.window_capacity = 4096
        # per-key ring capacity for time windows inside partitions
        self.partition_window_capacity = 256
        # pending-match slot capacity per key for pattern/sequence queries
        self.nfa_slots = 32
        # device numeric precision: 'exact' = 64-bit accumulators (matches
        # the reference's double math bit-for-bit; CPU default), 'fast' =
        # 32-bit on-device (TPU default — v5e emulates 64-bit in software).
        # Overridable with @app:precision('exact'|'fast').
        self.precision = _default_precision()
        # >1: batch N step metas into ONE device->host round trip, emitting
        # outputs (and surfacing overflow errors) up to N batches late
        # (the cost of one pull on a co-located chip: not measured). Set via
        # ConfigManager key siddhi_tpu.defer_meta. DEPRECATED: values >1
        # are remapped onto pipeline_depth at app build (app_runtime.py).
        self.defer_meta = 1
        # dispatch pipeline depth: up to N device batches per query ride
        # in flight while the host packs the next batch; emission stays
        # in per-query dispatch order and overflow errors surface on the
        # producer's next send (core/query/completion.py). 1 = fully
        # synchronous (today's pull-per-batch). Set via ConfigManager key
        # siddhi_tpu.pipeline_depth; SIDDHI_TPU_PIPELINE_DEPTH overrides
        # the process default (typed read — junk spellings raise naming
        # the variable, core/util/knobs.py).
        from siddhi_tpu.core.util.knobs import env_knob

        self.pipeline_depth = env_knob("SIDDHI_TPU_PIPELINE_DEPTH",
                                       "int", 2)
        from siddhi_tpu.core.query.completion import CompletionPump

        self.completion_pump = CompletionPump(self)
        # multi-process clusters: bound every device pull by this many
        # seconds; a peer process dying mid-collective otherwise hangs
        # the coordinator forever (ClusterPeerError surfaces through the
        # junction's @OnError/fault-stream machinery). Set via
        # ConfigManager key siddhi_tpu.cluster_step_timeout. None = off.
        self.cluster_step_timeout = None
        # fold window evictions into invertible aggregator deltas where the
        # query shape allows (ops/fused_agg.py); off = always-generic path
        self.enable_fusion = True
        # fan-out fusion: sibling single-stream queries on one junction
        # compile into ONE jitted step with ONE combined __meta__ pull per
        # batch (core/plan/fanout_plan.py + core/query/fused_fanout.py).
        # Off = every query keeps its own dispatch. Set via ConfigManager
        # key siddhi_tpu.fuse_fanout.
        self.fuse_fanout = True
        # critical-path profiler (siddhi_tpu/observability/journey.py +
        # costmodel.py): batch-journey stage tracing and first-compile
        # program-cost capture. Both enable a PROCESS-wide collector for
        # this runtime's lifetime (refcounted across apps). Keys
        # siddhi_tpu.profile_journeys / siddhi_tpu.profile_costs;
        # SIDDHI_TPU_PROFILE_COSTS=1 and POST /profile/* flip them
        # process-wide without a config.
        self.profile_journeys = False
        self.profile_costs = False
        # process-global compiled-program cache (core/util/program_cache.py):
        # identical step programs compile ONCE and share the immutable
        # executable across tenant apps (per-app state pytrees stay
        # private). Default on; 'false' restores per-app compiles.
        # program_cache_max caps live entries. Keys
        # siddhi_tpu.program_cache / siddhi_tpu.program_cache_max;
        # SIDDHI_TPU_PROGRAM_CACHE / _MAX set the process defaults.
        self.program_cache = env_knob("SIDDHI_TPU_PROGRAM_CACHE",
                                      "bool", True)
        self.program_cache_max = env_knob("SIDDHI_TPU_PROGRAM_CACHE_MAX",
                                          "int", 256)
        # device telemetry plane (observability/instruments.py): jitted
        # steps append declared instrument slots (window ring fill, join
        # partition fill, NFA active runs, routed-row skew, distinct
        # groups) behind the standard [overflow, notify, count] meta
        # prefix — device truth per batch at ZERO extra host transfers.
        # Default ON; 'false' keeps the pre-round-9 meta layouts
        # bit-for-bit. Key siddhi_tpu.profile_device_instruments.
        self.profile_device_instruments = True
        # serving tier (siddhi_tpu/serving/): >1 key-partitions every
        # incremental aggregation's bucket state across this many
        # in-process shards (round-robin over mesh devices) and answers
        # on-demand `within ... per ...` queries by scatter-gather ordered
        # merge. Set via ConfigManager key siddhi_tpu.agg_shards.
        # @PartitionById (DB shard-stitch) aggregations keep the legacy
        # single-store runtime regardless — see MIGRATION.md.
        self.agg_shards = 1
        # per-shard bounded WAL (batches) backing the shard rebuild
        # protocol; 0 disables shard WALs. Key siddhi_tpu.agg_shard_wal.
        self.agg_shard_wal = 1024
        # device join engine (core/join/): 'device' attaches the
        # PanJoin-style partitioned probe engine to eligible stream-stream
        # window joins (pipeline/fusion-eligible fused insert+probe step);
        # 'legacy' keeps the reference synchronous broadcast-probe path
        # wholesale. Key siddhi_tpu.join_engine.
        self.join_engine = "device"
        # build-side hash partitions per join side (pow2, clamped to 64);
        # partition-local probes cut the [N, W] probe surface ~P-fold.
        # 0 = auto: 8 on accelerator backends, 1 on the CPU fallback —
        # the directory's gathers + emission-order sort lose to the
        # vectorized broadcast compare on a scalar core (a CPU timing;
        # AUTO is not measured on the chip: PERF.md), while P = 1 keeps
        # the fused in-state step (pipeline/fusion/mesh eligibility) at
        # legacy speed. An
        # explicit value is always honored. Key siddhi_tpu.join_partitions.
        self.join_partitions = 0
        # per-partition sub-window slack factor: each [P, W*slack/P]
        # sub-window tolerates key skew up to slack/P of the ring before
        # adaptive growth (or, with growth off, a partition overflow
        # naming this knob). Key siddhi_tpu.join_partition_slack.
        self.join_partition_slack = 2
        # adaptive sub-window growth (PanJoin re-partitioning): the host
        # mirrors each side's ring occupancy and grows Wp (capped at
        # pow2(W)) before a skewed batch could overflow a partition. Off
        # = static provisioning; overflow becomes FatalQueryError naming
        # siddhi_tpu.join_partition_slack. Key
        # siddhi_tpu.join_partition_grow.
        self.join_partition_grow = True
        # multicore ingest front door (core/stream/input/pack_pool.py):
        # ingest_pool > 0 shards HostBatch pack/encode work across that
        # many worker threads as sequence-numbered sub-batches with an
        # ordered merge — outputs and dictionary id assignment stay
        # bit-identical to the inline path. 0 (default) = inline.
        # Keys siddhi_tpu.ingest_pool / siddhi_tpu.ingest_split.
        self.ingest_pool = 0
        self.ingest_split = 8192
        # the live IngestPackPool instance (created by SiddhiAppRuntime
        # at start when ingest_pool > 0; every pack call site reads it
        # through core.event.pack_pool_of)
        self.ingest_pack_pool = None
        # resilience subsystem attach points (siddhi_tpu/resilience/):
        # bounded ingest replay log + app supervisor, set by
        # SiddhiAppRuntime.enable_wal() / .supervise()
        self.ingest_wal = None
        self.supervisor = None
        # overload armor (resilience/overload.py): per-app ingest quotas,
        # shed-policy backpressure, device-memory budget, weighted fair
        # scheduling. None = no quotas (bit-identical default behavior);
        # set by OverloadManager.register via the siddhi_tpu.quota_* /
        # siddhi_tpu.shed_policy config keys or rt.enable_overload().
        self.overload = None
        # closed-loop controller (siddhi_tpu/autopilot/): 'off'
        # (default) = no controller thread, bit-identical engine;
        # 'dry_run' = observe + decide + log, never actuate; 'on' =
        # actuate live knobs within per-knob bounds. Keys
        # siddhi_tpu.autopilot / .autopilot_interval_s /
        # .autopilot_cooldown_s; rt.enable_autopilot() flips it
        # programmatically.
        self.autopilot = "off"
        self.autopilot_interval_s = 0.25
        self.autopilot_cooldown_s = 5.0
        # reshard-actuator shard-count ceiling (0 = all addressable
        # devices); also records the autopilot's current target so a
        # report can show where the controller has driven the layout
        self.route_shards = 0
        # shared stores, filled by SiddhiAppRuntime during assembly
        self.tables = {}
        self.named_windows = {}


def _default_precision() -> str:
    """``"exact"`` on the CPU backend, ``"fast"`` on an accelerator. A
    backend that cannot initialize raises here, by name, instead of
    being answered with the CPU's default."""
    import jax

    return "exact" if jax.default_backend() == "cpu" else "fast"


@dataclass
class SiddhiQueryContext:
    """Per-query context (reference ``core/config/SiddhiQueryContext.java``)."""

    siddhi_app_context: SiddhiAppContext = None
    name: str = ""
    partitioned: bool = False
    _state_counter: int = field(default=0)

    def generate_state_id(self) -> str:
        self._state_counter += 1
        return f"{self.name}-s{self._state_counter}"
