"""SiddhiAppRuntime: one running app — junctions, query runtimes, callbacks.

Mirror of reference ``core/SiddhiAppRuntime.java`` /
``SiddhiAppRuntimeImpl.java`` and the assembly logic of
``util/parser/SiddhiAppParser.java:91-212`` +
``util/SiddhiAppRuntimeBuilder.java``: reads @app annotations (playback,
async, statistics), materializes a StreamJunction per stream definition,
plans each query, auto-defines insert-into target streams
(``OutputParser``), and wires callbacks.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from siddhi_tpu.analysis.locks import make_lock
from siddhi_tpu.compiler.errors import SiddhiAppValidationException
from siddhi_tpu.core.context import SiddhiAppContext, SiddhiContext
from siddhi_tpu.core.event import Event
from siddhi_tpu.core.plan.query_planner import plan_query
from siddhi_tpu.core.query.callback import QueryCallback
from siddhi_tpu.core.query.ratelimit import (create_rate_limiter,
                                             rate_uses_group_key)
from siddhi_tpu.core.query.runtime import QueryRuntime
from siddhi_tpu.core.stream.input.input_handler import InputHandler, InputManager
from siddhi_tpu.core.stream.junction import StreamJunction
from siddhi_tpu.core.stream.output.stream_callback import StreamCallback
from siddhi_tpu.core.util.scheduler import Scheduler
from siddhi_tpu.query_api.annotations import find_annotation
from siddhi_tpu.query_api.definitions import Attribute, AttrType, StreamDefinition
from siddhi_tpu.query_api.execution import InsertIntoStream, Partition, Query
from siddhi_tpu.query_api.siddhi_app import SiddhiApp


def _compile_script_function(fdef):
    """``define function f[python] return <type> { <expression> }`` — the
    body is a Python expression over ``arg0..argN`` (aka ``data0..``) with
    ``xp`` (jax.numpy on device) in scope, vectorized over columns
    (reference ``ScriptFunctionExecutor`` evaluates per event; here one
    call per batch). String arguments arrive dictionary-encoded."""
    from siddhi_tpu.ops.expressions import CompileError

    if fdef.language.lower() not in ("python", "py"):
        raise CompileError(
            f"function '{fdef.id}': script language '{fdef.language}' is not "
            f"supported (use [python])")
    import numpy as _np

    code = compile(fdef.body.strip(), f"<function {fdef.id}>", "eval")
    rtype = fdef.return_type

    class _Script:
        return_type = rtype

        @staticmethod
        def apply(xp, *args):
            ns = {"xp": xp, "np": _np}
            for i, a in enumerate(args):
                ns[f"arg{i}"] = a
                ns[f"data{i}"] = a
            return eval(code, ns)  # noqa: S307 — user-defined app function

    return _Script


def _parse_playback_time(s: str, what: str) -> int:
    """Strict time-constant string for @app:playback elements — requires
    '<int> <unit>' pairs; bare numbers or empty strings fail creation the
    way the reference's SiddhiCompiler.parseTimeConstantDefinition does
    (PlaybackTestCase test9/test10)."""
    from siddhi_tpu.compiler.errors import SiddhiParserException
    from siddhi_tpu.compiler.tokenizer import is_time_unit, time_unit_ms

    parts = (s or "").split()
    if not parts or len(parts) % 2 != 0:
        raise SiddhiParserException(
            f"Invalid {what} constant '{s}' in playback annotation")
    total = 0
    for num, unit in zip(parts[::2], parts[1::2]):
        if not num.isdigit() or not is_time_unit(unit.lower()):
            raise SiddhiParserException(
                f"Invalid {what} constant '{s}' in playback annotation")
        total += int(num) * time_unit_ms(unit.lower())
    return total


def _default_app_name(siddhi_app: SiddhiApp) -> str:
    """Deterministic fallback name so snapshots of the same (unnamed) app
    text restore across process restarts."""
    import hashlib

    # dataclass reprs are deterministic and cover definitions, queries and
    # expressions — distinct apps hash apart, identical text hashes equal
    return "siddhi-app-" + hashlib.md5(repr(siddhi_app).encode()).hexdigest()[:12]


class SiddhiAppRuntime:  # graftlint: disable=R8 — the junction/query/
    # adapter registries are populated during single-threaded wiring
    # (parse + add_callback before start()); runtime threads only read
    # them, and lifecycle transitions serialize on the app barrier
    def __init__(self, siddhi_app: SiddhiApp, siddhi_context: SiddhiContext):
        self.siddhi_app = siddhi_app
        self.name = siddhi_app.name or _default_app_name(siddhi_app)
        self.app_context = SiddhiAppContext(siddhi_context, self.name)
        self._barrier = make_lock("barrier")
        self.app_context.timestamp_generator.set_heartbeat_barrier(self._barrier)
        self.stream_definitions: Dict[str, StreamDefinition] = dict(siddhi_app.stream_definitions)
        self.junctions: Dict[str, StreamJunction] = {}
        self.query_runtimes: Dict[str, QueryRuntime] = {}
        self._stream_callback_adapters: List = []
        self._started = False
        self._profiling_on = False  # holds one journey/costmodel enable
        self._instruments_on = False  # holds one device-instruments enable

        # @app:playback (reference SiddhiAppParser.java:171-212): optional
        # idle.time + increment enable the idle heartbeat — when no event
        # arrives for idle.time of wall time, the event clock advances by
        # increment so time windows keep draining
        pb = siddhi_app.app_annotation("playback")
        if pb is not None:
            self.app_context.playback = True
            self.app_context.timestamp_generator.playback = True
            elems = pb.elements_map()
            unknown = [k for k in elems if k not in ("idle.time", "increment")]
            if unknown:
                raise SiddhiAppValidationException(
                    "Playback annotation accepts only idle.time and "
                    f"increment but found {unknown[0]}")
            idle_s, inc_s = elems.get("idle.time"), elems.get("increment")
            if (idle_s is None) != (inc_s is None):
                raise SiddhiAppValidationException(
                    "Playback annotation requires both idle.time and "
                    "increment when either is given")
            if idle_s is not None:
                self.app_context.timestamp_generator.configure_heartbeat(
                    _parse_playback_time(idle_s, "idle.time"),
                    _parse_playback_time(inc_s, "increment"))
        if siddhi_app.app_annotation("enforceOrder") is not None:
            self.app_context.enforce_order = True
        if siddhi_app.app_annotation("async") is not None:
            # reference SiddhiAppParser.java:105-111: @Async is a STREAM
            # annotation; the app-level form fails creation
            raise SiddhiAppValidationException(
                "@Async not supported in SiddhiApp level, instead use "
                "@Async with streams")
        prec = siddhi_app.app_annotation("precision")
        if prec is not None:
            v = (prec.element() or "").lower()
            if v not in ("exact", "fast"):
                raise SiddhiAppValidationException(
                    "@app:precision must be 'exact' or 'fast'")
            self.app_context.precision = v
        self.app_context.scheduler = Scheduler(self.app_context)

        # deployment config: ConfigManager system keys override the
        # capacity knobs (reference ConfigManager consulted at parse
        # time). Every siddhi_tpu.* key resolves through the typed
        # parser registry (core/util/knobs.py): junk spellings raise
        # SiddhiAppValidationException naming the key and the accepted
        # values, and graftlint R2 keeps ad-hoc reads out of the tree.
        from siddhi_tpu.core.util.knobs import apply_app_knobs

        cm = siddhi_context.config_manager
        explicit_knobs = apply_app_knobs(cm, self.app_context)
        explicit_depth = explicit_knobs.get("pipeline_depth")
        if self.app_context.defer_meta > 1:
            # deprecation shim: the hold-N-then-flush defer queue is
            # subsumed by the dispatch pipeline (core/query/completion.py)
            # — same pull batching, no emission lag under trickle, joins/
            # scheduler windows no longer excluded. See MIGRATION.md.
            import warnings

            if explicit_depth is None:
                warnings.warn(
                    "siddhi_tpu.defer_meta is deprecated — use "
                    "siddhi_tpu.pipeline_depth (the dispatch pipeline "
                    "subsumes meta-defer batching); mapping defer_meta="
                    f"{self.app_context.defer_meta} onto pipeline_depth",
                    DeprecationWarning, stacklevel=2)
                self.app_context.pipeline_depth = max(
                    self.app_context.pipeline_depth,
                    self.app_context.defer_meta)
                self.app_context.defer_meta = 1
            else:
                # an explicit pipeline_depth wins; defer_meta is left
                # as-is — the legacy hold-N path only engages when the
                # pipeline is pinned off (depth 1), and silently zeroing
                # it would remove the batching the user asked for
                warnings.warn(
                    "siddhi_tpu.defer_meta is deprecated — use "
                    "siddhi_tpu.pipeline_depth; explicit pipeline_depth="
                    f"{explicit_depth} set, defer_meta="
                    f"{self.app_context.defer_meta} kept for the legacy "
                    "path (engages only at pipeline_depth 1)",
                    DeprecationWarning, stacklevel=2)

        # @app:statistics (reference SiddhiStatisticsManager wiring)
        stats_ann = siddhi_app.app_annotation("statistics")
        if stats_ann is not None:
            from siddhi_tpu.core.util.statistics import (
                StatisticsManager,
                parse_level,
            )
            from siddhi_tpu.core.aggregation.incremental import _parse_time_str

            level = parse_level(stats_ann.element("level")
                                or stats_ann.element())
            reporter = stats_ann.element("reporter")
            interval = stats_ann.element("interval")
            self.app_context.statistics_manager = StatisticsManager(
                level=level,
                reporter=reporter,
                interval_ms=_parse_time_str(interval) if interval else 60_000,
            )

        # activate the manager's extension registry for query compilation
        # (custom functions/windows resolve through it — the role of
        # reference SiddhiExtensionLoader.java:58-98), merged with this
        # app's `define function` scripts (ScriptFunctionExecutor role)
        from siddhi_tpu.ops import expressions as _expr_mod

        self._script_functions = {
            f"function:{fid}": _compile_script_function(fdef)
            for fid, fdef in siddhi_app.function_definitions.items()
        }
        self._extensions = {**siddhi_context.extensions, **self._script_functions}
        _expr_mod.set_active_extensions(self._extensions)

        for sid, sdef in list(self.stream_definitions.items()):
            self._create_junction(sdef)   # may register '!sid' fault streams

        # tables, named windows, triggers (reference
        # SiddhiAppRuntimeBuilder.defineTable/defineWindow/defineTrigger)
        from siddhi_tpu.core.table import InMemoryTable
        from siddhi_tpu.core.trigger import TriggerRuntime
        from siddhi_tpu.core.window import NamedWindowRuntime

        dictionary = self.app_context.string_dictionary
        from siddhi_tpu.core.table.record_table import create_table

        self.tables: Dict[str, InMemoryTable] = {
            tid: create_table(tdef, dictionary, siddhi_context.extensions)
            for tid, tdef in siddhi_app.table_definitions.items()
        }
        # cache retention clocks wire at BUILD time: a row cached before
        # start() (lazy start, on-demand reads) must stamp the same
        # event-aware clock the expirer sweeps with — mixing wall time in
        # would make @app:playback rows immortal
        for t in self.tables.values():
            # overload layer (resilience/overload.py): tables gate their
            # capacity growth on the app's device-memory budget
            t.app_context = self.app_context
            cache = getattr(t, "cache", None)
            if cache is not None:
                cache.now_fn = self.app_context.timestamp_generator.current_time
        self.named_windows: Dict[str, NamedWindowRuntime] = {}
        for wid, wdef in siddhi_app.window_definitions.items():
            w = NamedWindowRuntime(wdef, self.app_context, dictionary)
            w.scheduler = self.app_context.scheduler
            self.named_windows[wid] = w
        self.app_context.tables = self.tables
        self.app_context.named_windows = self.named_windows

        # incremental aggregations (reference AggregationParser/-Runtime)
        from siddhi_tpu.core.aggregation import IncrementalAggregationRuntime

        self.aggregations: Dict[str, IncrementalAggregationRuntime] = {}
        for aid, adef in siddhi_app.aggregation_definitions.items():
            n_shards = int(getattr(self.app_context, "agg_shards", 1) or 1)
            # @PartitionById (annotation or system property) keeps the
            # legacy DB shard-stitch runtime — the two sharding modes are
            # mutually exclusive (MIGRATION.md)
            pbi = find_annotation(adef.annotations or [], "PartitionById")
            sys_pbi = ((cm.get_property("partitionById") or "")
                       if cm is not None else "").lower() == "true"
            if n_shards > 1 and pbi is None and not sys_pbi:
                from siddhi_tpu.serving import ShardedIncrementalAggregation

                agg = ShardedIncrementalAggregation(
                    adef, self.app_context, dictionary,
                    self.stream_definitions, n_shards=n_shards,
                    wal_batches=getattr(self.app_context,
                                        "agg_shard_wal", 1024) or None)
            else:
                agg = IncrementalAggregationRuntime(
                    adef, self.app_context, dictionary,
                    self.stream_definitions)
            self.junctions[agg.input_stream_id].subscribe(agg)
            self.aggregations[aid] = agg
        self.app_context.aggregations = self.aggregations

        self.trigger_runtimes: List[TriggerRuntime] = []
        for tid, tdef in siddhi_app.trigger_definitions.items():
            if tid in self.junctions:
                # an explicitly defined `(triggered_time long)` stream may
                # share the trigger's id (TriggerTestCase testQuery4) —
                # reuse its junction so @async config and subscribers stay
                junction = self.junctions[tid]
            else:
                sdef = StreamDefinition(
                    id=tid,
                    attributes=[Attribute("triggered_time", AttrType.LONG)])
                self.stream_definitions[tid] = sdef
                junction = self._create_junction(sdef)
            self.trigger_runtimes.append(
                TriggerRuntime(tdef, junction, self.app_context,
                               barrier=self._barrier))

        self.input_manager = InputManager(self.app_context, self.junctions, self._barrier)
        # first send() starts the app lazily, AFTER callbacks are attached —
        # at-start triggers then fire with subscribers in place
        self.input_manager.ensure_started = self.start

        self.partition_contexts: List = []
        # pre-register set metadata on EXPLICITLY defined target streams so
        # a consumer query written before its producer still compiles with
        # the right multi/element-type knowledge (assembly is one pass in
        # text order; auto-defined streams cannot be forward-referenced)
        self._prescan_object_metadata(siddhi_app)
        q_index = 0
        p_index = 0
        for element in siddhi_app.execution_elements:
            if isinstance(element, Query):
                q_index += 1
                self._add_query(element, q_index)
            elif isinstance(element, Partition):
                p_index += 1
                q_index = self._add_partition(element, p_index, q_index)

        # transport boundary: @source / @sink stream annotations
        # (reference SiddhiAppRuntimeBuilder + SiddhiExtensionLoader)
        from siddhi_tpu.query_api.annotations import find_annotations
        from siddhi_tpu.core.stream.input.source import create_source_runtime
        from siddhi_tpu.core.stream.output.sink import create_sink_runtime

        extensions = siddhi_context.extensions
        self.source_runtimes: List = []
        self.sink_runtimes: List = []
        for sid, sdef in list(self.stream_definitions.items()):
            for ann in find_annotations(sdef.annotations, "source"):
                self.source_runtimes.append(create_source_runtime(
                    ann, sdef, self.get_input_handler(sid),
                    self.app_context, extensions))
            for ann in find_annotations(sdef.annotations, "sink"):
                sr = create_sink_runtime(ann, sdef, self.app_context, extensions)
                self.junctions[sid].subscribe(sr)
                self.sink_runtimes.append(sr)

        # fan-out fusion: contiguous runs of sibling single-stream queries
        # on one junction fuse into ONE jitted step + ONE __meta__ round
        # trip per batch (core/plan/fanout_plan.py); opt out with the
        # app_context.fuse_fanout knob / siddhi_tpu.fuse_fanout config key
        from siddhi_tpu.core.plan.fanout_plan import plan_fanout_groups

        self.fused_fanout_groups: List = plan_fanout_groups(self)

        # eligibility census (core/eligibility.py): classify every query
        # on every strategy surface (route / fusion / join engine / join
        # pipeline) with stable reason codes — stashed on
        # self.eligibility_census for tooling (the semantic fuzzer) and
        # counted as the siddhi_eligibility_total{surface,code,query}
        # family on /metrics
        from siddhi_tpu.core.eligibility import register_census

        register_census(self)

        # overload armor (resilience/overload.py): siddhi_tpu.quota_* /
        # siddhi_tpu.shed_policy config keys register per-app ingest
        # quotas, shed policies, a device-memory budget and a fair-share
        # weight. No keys set => app_context.overload stays None and the
        # engine is bit-identical to the pre-quota default.
        if cm is not None:
            self._overload_from_config(cm)

    def _overload_from_config(self, cm) -> None:
        from siddhi_tpu.core.util.knobs import read_knob

        queue_quota = read_knob(cm, "quota_queue_depth")
        policy = read_knob(cm, "shed_policy")
        pipeline_quota = read_knob(cm, "quota_pipeline_depth")
        memory_mb = read_knob(cm, "quota_memory_mb")
        block_timeout = read_knob(cm, "quota_block_timeout_s")
        fair_weight = read_knob(cm, "fair_weight")
        query_cap = read_knob(cm, "quota_query_cap")
        per_stream_quota = {}
        per_stream_policy = {}
        for sid in self.junctions:
            v = read_knob(cm, "quota_queue_depth", stream=sid)
            if v is not None:
                per_stream_quota[sid] = v
            v = read_knob(cm, "shed_policy", stream=sid)
            if v is not None:
                per_stream_policy[sid] = v
        # presence, not truthiness: the values are TYPED now, and an
        # explicit `quota_queue_depth: 0` / `fair_weight: 0` must still
        # register overload enforcement
        if all(v is None for v in (queue_quota, policy, pipeline_quota,
                                   memory_mb, block_timeout, fair_weight,
                                   query_cap)) \
                and not per_stream_quota and not per_stream_policy:
            return
        self.enable_overload(
            queue_quota=queue_quota,
            shed_policy=policy if policy else "block",
            queue_quota_per_stream=per_stream_quota,
            shed_policy_per_stream=per_stream_policy,
            pipeline_quota=pipeline_quota,
            memory_budget_mb=memory_mb,
            block_timeout_s=block_timeout,
            fair_weight=fair_weight if fair_weight is not None else 1.0,
            query_cap=query_cap)

    def enable_overload(self, queue_quota=None, shed_policy="block",
                        queue_quota_per_stream=None,
                        shed_policy_per_stream=None, pipeline_quota=None,
                        memory_budget_mb=None, block_timeout_s=None,
                        fair_weight=1.0, query_cap=None):
        """Register this app with the process-global overload layer
        (``resilience/overload.py``): @Async queue-depth quotas with
        per-stream ``block`` / ``shed_oldest`` / ``shed_newest``
        policies, an app-wide dispatch-pipeline quota, an approximate
        device-memory budget gating every capacity-growth site, and a
        weighted fair share against sibling apps. Idempotent (re-enable
        replaces the config); returns the ``AppOverloadControl``."""
        from siddhi_tpu.resilience.overload import (
            DEFAULT_BLOCK_TIMEOUT_S,
            OverloadConfig,
            OverloadManager,
        )

        cfg = OverloadConfig(
            queue_quota=queue_quota,
            queue_quota_per_stream=dict(queue_quota_per_stream or {}),
            shed_policy=shed_policy or "block",
            shed_policy_per_stream=dict(shed_policy_per_stream or {}),
            pipeline_quota=pipeline_quota,
            memory_budget_bytes=(int(memory_budget_mb * 1024 * 1024)
                                 if memory_budget_mb is not None else None),
            block_timeout_s=(block_timeout_s if block_timeout_s is not None
                             else DEFAULT_BLOCK_TIMEOUT_S),
            fair_weight=fair_weight,
            query_cap=query_cap)
        return OverloadManager.instance().register(self, cfg)

    # ------------------------------------------------------------ assembly

    def _create_junction(self, sdef: StreamDefinition) -> StreamJunction:
        j = StreamJunction(sdef, self.app_context)
        async_ann = find_annotation(sdef.annotations, "async")
        if async_ann is not None:
            if self.app_context.enforce_order:
                raise SiddhiAppValidationException(
                    f"@app:enforceOrder is incompatible with @Async on "
                    f"stream '{sdef.id}': async buffering can interleave "
                    f"producer batches out of timestamp order")
            from siddhi_tpu.core.aggregation.incremental import _parse_time_str

            buffer_size = int(async_ann.element("buffer.size") or 1024)
            batch_size = int(async_ann.element("batch.size") or 256)
            max_delay = async_ann.element("max.delay")
            latency_target = async_ann.element("latency.target")
            j.enable_async(
                buffer_size, batch_size,
                max_delay_ms=_parse_time_str(max_delay)
                if max_delay else None,
                latency_target_ms=_parse_time_str(latency_target)
                if latency_target else None)
        onerr = find_annotation(sdef.annotations, "OnError")
        if onerr is not None and (
                onerr.element("action") or "log").lower() == "stream":
            # @OnError(action='stream'): failing events route to the
            # '!stream' fault junction with an appended `_error` column
            # (reference StreamJunction.handleError +
            # FaultStreamEventConverter — FaultStreamTestCase test3-5)
            fdef = StreamDefinition(
                id="!" + sdef.id,
                attributes=list(sdef.attributes)
                + [Attribute("_error", AttrType.STRING)])
            fj = StreamJunction(fdef, self.app_context)
            self.junctions[fdef.id] = fj
            self.stream_definitions[fdef.id] = fdef
            j.fault_junction = fj
            j.on_error_action = "STREAM"
        self.junctions[sdef.id] = j
        return j

    def _add_partition(self, partition: Partition, p_index: int, q_index: int) -> int:
        """Assemble a ``partition with (...) begin ... end`` block — the
        role of reference ``util/parser/PartitionParser.java`` +
        ``partition/PartitionRuntimeImpl.java``, with per-key processor
        instances replaced by dense-keyed state (ops/keyed_windows.py)."""
        from siddhi_tpu.core.partition import (
            PartitionContext,
            RangePartitionKeyer,
            ValuePartitionKeyer,
        )
        from siddhi_tpu.core.plan.resolvers import SingleStreamResolver
        from siddhi_tpu.ops.expressions import compile_condition, compile_expr
        from siddhi_tpu.query_api.execution import RangePartitionType, ValuePartitionType

        pctx = PartitionContext(p_index)
        self.partition_contexts.append(pctx)
        purge_ann = find_annotation(partition.annotations or [], "purge")
        if purge_ann is not None and (
            purge_ann.element("enable") or "true"
        ).lower() == "true":
            from siddhi_tpu.core.aggregation.incremental import _parse_time_str

            interval = purge_ann.element("interval")
            idle = purge_ann.element("idle.period")
            pctx.purge_interval_ms = _parse_time_str(interval) if interval else 60_000
            pctx.purge_idle_ms = _parse_time_str(idle) if idle else 3600_000
            pctx.keyspace.enable_purge_tracking()
        for ptype in partition.partition_types:
            sid = ptype.stream_id
            if sid not in self.stream_definitions:
                raise SiddhiAppValidationException(
                    f"partition with (... of {sid}): stream '{sid}' is not defined"
                )
            resolver = SingleStreamResolver(
                self.stream_definitions[sid], self.app_context.string_dictionary
            )
            if isinstance(ptype, ValuePartitionType):
                fn, t = compile_expr(ptype.expression, resolver)
                pctx.keyers[sid] = ValuePartitionKeyer([(fn, t)], pctx.keyspace)
            elif isinstance(ptype, RangePartitionType):
                conds = [
                    (rc.partition_key, compile_condition(rc.condition, resolver))
                    for rc in ptype.conditions
                ]
                pctx.keyers[sid] = RangePartitionKeyer(conds)
            else:
                raise SiddhiAppValidationException(f"unknown partition type {ptype!r}")

        # streams PRODUCED by queries inside this partition (non-inner
        # insert targets): a later partition query may consume them, and
        # their events stay in the producing instance's flow (reference
        # partition ThreadLocal flow — WindowPartitionTestCase q6 chains
        # `insert events into OutputStream` -> `from OutputStream`)
        produced = {
            q.output_stream.target_id
            for q in partition.queries
            if isinstance(q.output_stream, InsertIntoStream)
            and not q.output_stream.is_inner_stream
        }
        consumed = set()
        for q in partition.queries:
            ist = q.input_stream
            for s in ("stream_id", "unique_stream_id"):
                sid = getattr(ist, s, None)
                if isinstance(sid, str):
                    consumed.add(sid)
            for side in ("left_input_stream", "right_input_stream"):
                sub = getattr(ist, side, None)
                sid = getattr(sub, "stream_id", None)
                if isinstance(sid, str):
                    consumed.add(sid)
        pctx.local_streams = produced & consumed
        for query in partition.queries:
            q_index += 1
            self._add_query(query, q_index, partition_ctx=pctx)
        return q_index

    def _prescan_object_metadata(self, siddhi_app):
        """Best-effort first pass over query ASTs: record which object
        attributes of explicitly defined streams are MULTI-element sets
        (unionSet outputs) and their element types (createSet args), so
        query text order does not change set semantics."""
        from siddhi_tpu.query_api.execution import (
            InsertIntoStream,
            Partition,
            Query,
        )
        from siddhi_tpu.query_api.expressions import AttributeFunction, Variable

        def input_attr_type(query, var):
            ist = getattr(query, "input_stream", None)
            sid = getattr(ist, "stream_id", None)
            sdef = self.stream_definitions.get(sid) if sid else None
            if sdef is None:
                return None
            try:
                return sdef.attribute(var.attribute_name).type
            except Exception:
                return None

        def elem_of(query, expr):
            # element type of createSet(<arg>) when statically resolvable
            if not (isinstance(expr, AttributeFunction)
                    and expr.name.lower() == "createset" and expr.parameters):
                return None
            arg = expr.parameters[0]
            if isinstance(arg, Variable):
                return input_attr_type(query, arg)
            return None

        def scan(query):
            out = getattr(query, "output_stream", None)
            if not isinstance(out, InsertIntoStream):
                return
            tdef = self.stream_definitions.get(out.target_id)
            if tdef is None or query.selector is None:
                return
            for oa in query.selector.selection_list or []:
                expr = oa.expression
                if not isinstance(expr, AttributeFunction):
                    continue
                name = expr.name.lower()
                elem = None
                multi = False
                if name == "unionset" and expr.parameters:
                    multi = True
                    elem = elem_of(query, expr.parameters[0])
                elif name == "createset":
                    elem = elem_of(query, expr)
                else:
                    continue
                if multi:
                    ms = set(getattr(tdef, "object_multi_attrs", None) or set())
                    ms.add(oa.name)
                    tdef.object_multi_attrs = ms
                if elem is not None:
                    et = dict(getattr(tdef, "object_elem_types", None) or {})
                    et[oa.name] = elem
                    tdef.object_elem_types = et

        for element in siddhi_app.execution_elements:
            if isinstance(element, Query):
                scan(element)
            elif isinstance(element, Partition):
                for q in element.queries:
                    scan(q)

    def _add_query(self, query: Query, index: int, partition_ctx=None):
        query_name = query.name or f"query_{index}"
        definitions = dict(self.stream_definitions)
        for wid, w in self.named_windows.items():
            definitions[wid] = w.definition
        for tid, t in self.tables.items():
            definitions[tid] = t.definition
        if partition_ctx is not None:
            definitions.update(partition_ctx.inner_definitions)

        from siddhi_tpu.query_api.execution import SingleInputStream

        if (
            isinstance(query.input_stream, SingleInputStream)
            and query.input_stream.unique_stream_id in self.tables
        ):
            raise SiddhiAppValidationException(
                f"'{query.input_stream.stream_id}' is a table — consume it via a "
                f"join or an on-demand query (runtime.query(...))"
            )
        from siddhi_tpu.observability.tracing import span

        with span("plan", query=query_name):
            runtime = plan_query(query, query_name, self.app_context,
                                 definitions, partition_ctx=partition_ctx)

        from siddhi_tpu.core.query.output_callbacks import create_table_callback
        from siddhi_tpu.query_api.execution import (
            DeleteStream,
            UpdateOrInsertStream,
            UpdateStream,
        )

        out = query.output_stream
        if isinstance(out, (DeleteStream, UpdateStream, UpdateOrInsertStream)):
            if out.target_id not in self.tables:
                raise SiddhiAppValidationException(
                    f"'{out.target_id}' is not a defined table"
                )
            runtime.output_action = create_table_callback(
                out, self.tables[out.target_id], query_name, runtime.output_attrs,
                self.app_context.string_dictionary)
        elif isinstance(out, InsertIntoStream) and out.target_id in self.tables \
                and not out.is_inner_stream:
            runtime.output_action = create_table_callback(
                out, self.tables[out.target_id], query_name, runtime.output_attrs,
                self.app_context.string_dictionary)
        elif isinstance(out, InsertIntoStream) and out.target_id in self.named_windows \
                and not out.is_inner_stream:
            w = self.named_windows[out.target_id]
            if len(runtime.output_attrs) != len(w.definition.attributes):
                raise SiddhiAppValidationException(
                    f"insert into window '{out.target_id}': query outputs "
                    f"{len(runtime.output_attrs)} attributes, window has "
                    f"{len(w.definition.attributes)}"
                )
            runtime.output_junction = w
        elif isinstance(out, InsertIntoStream):
            target = out.target_id
            if partition_ctx is not None and out.is_inner_stream:
                # '#stream' scoped to this partition; events carry pk ids
                inner_id = "#" + target
                if inner_id not in partition_ctx.inner_definitions:
                    sdef = StreamDefinition(
                        id=inner_id,
                        attributes=[Attribute(n, t) for n, t in runtime.output_attrs],
                    )
                    partition_ctx.inner_definitions[inner_id] = sdef
                    partition_ctx.inner_junctions[inner_id] = StreamJunction(
                        sdef, self.app_context
                    )
                runtime.output_junction = partition_ctx.inner_junctions[inner_id]
                runtime.attach_pk = True
            else:
                if target not in self.stream_definitions:
                    # auto-define the output stream (reference OutputParser)
                    sdef = StreamDefinition(
                        id=target,
                        attributes=[Attribute(n, t) for n, t in runtime.output_attrs],
                    )
                    self.stream_definitions[target] = sdef
                    self._create_junction(sdef)
                else:
                    # inserting into an existing stream requires an
                    # equivalent schema (reference
                    # AbstractDefinition.checkEquivalency via OutputParser —
                    # SimpleQueryValidatorTestCase duplicate-definition)
                    existing = self.stream_definitions[target]
                    dattrs = [(a.name, a.type) for a in existing.attributes]
                    if list(runtime.output_attrs) != dattrs:
                        raise SiddhiAppValidationException(
                            f"query '{query_name}' inserts "
                            f"{list(runtime.output_attrs)} into stream "
                            f"'{target}' defined as {dattrs}")
                runtime.output_junction = self.junctions[target]
                if (partition_ctx is not None
                        and target in getattr(partition_ctx,
                                              "local_streams", ())):
                    # a partition-mate consumes this stream: outputs must
                    # carry the producing instance's pk
                    runtime.attach_pk = True
                # record set-element types on the target stream so later
                # queries (unionSet/sizeOfSet over this stream) and event
                # decode know how to interpret object set columns
                ometa = {n: t for n, t in getattr(
                    runtime.selector_plan, "object_meta", {}).items()
                    if t is not None}
                omulti = getattr(runtime.selector_plan, "object_multi", [])
                if ometa or omulti:
                    tdef = self.stream_definitions[target]
                    merged = dict(getattr(tdef, "object_elem_types", None) or {})
                    merged.update(ometa)
                    tdef.object_elem_types = merged
                    tdef.object_multi_attrs = (
                        set(getattr(tdef, "object_multi_attrs", None) or set())
                        | set(omulti))
        elif out is not None:
            raise SiddhiAppValidationException(
                f"unsupported output action {type(out).__name__}")

        from siddhi_tpu.query_api.execution import JoinInputStream, StateInputStream

        sp = getattr(runtime, "selector_plan", None)
        agg_positions = tuple(getattr(sp, "agg_positions", ()) or ())
        # every join counts as windowed (QueryParser.java:149); a named
        # window source is windowed too (the window junction delivers its
        # expireds); else a #window handler on the single stream
        src_id = getattr(query.input_stream, "unique_stream_id", None)
        windowed = (isinstance(query.input_stream, JoinInputStream)
                    or src_id in self.named_windows
                    or getattr(runtime, "window_stage", None) is not None
                    or getattr(runtime, "host_window", None) is not None)
        group_key_fn = None
        if query.selector.group_by_list and rate_uses_group_key(
                query.output_rate, windowed, agg_positions):
            # grouped queries get per-group limiter variants (reference
            # OutputParser picks the GroupBy limiter classes)
            gb_names = {v.attribute_name for v in query.selector.group_by_list}
            positions = tuple(i for i, (n, _t) in enumerate(runtime.output_attrs)
                              if n in gb_names)
            if positions:
                group_key_fn = lambda ev, _p=positions: tuple(  # noqa: E731
                    ev.data[i] for i in _p)
            else:
                # group key not projected (`select sum(calls) group by ip`):
                # ride the dense group-id column into Event.gk — the
                # reference keys its limiters on GroupedComplexEvent's
                # groupKey, which exists whether or not it is selected.
                # Inside partitions GK already folds the partition id in
                # (GroupKeyer keys on (pk, group)), so grouping stays
                # correct per partition instance.
                runtime.limiter_needs_gk = True
                group_key_fn = lambda ev: ev.gk  # noqa: E731
        # inside a partition each key is its OWN query instance in the
        # reference — wrap the limiter per partition key (events carry pk)
        limiter_partitioned = (partition_ctx is not None
                               and query.output_rate is not None)
        if limiter_partitioned:
            runtime.limiter_needs_pk = True
        runtime.rate_limiter = create_rate_limiter(
            query.output_rate, runtime.send_to_callbacks, group_key_fn,
            partitioned=limiter_partitioned,
            windowed=windowed,
            agg_positions=agg_positions,
            out_size=len(getattr(runtime, "output_attrs", ()) or ()),
            empty_send=getattr(runtime, "send_empty_to_query_callbacks", None))
        runtime.scheduler = self.app_context.scheduler

        if isinstance(query.input_stream, StateInputStream):
            # pattern/sequence: one proxy receiver per consumed stream
            for sid, proxy in runtime.make_proxies().items():
                self.junctions[sid].subscribe(proxy)
        elif isinstance(query.input_stream, JoinInputStream):
            # table sides have no proxy; named-window sides subscribe to the
            # window's emission junction, stream sides to their junction
            proxies = runtime.make_proxies()
            _left_sid = query.input_stream.left.unique_stream_id
            _right_sid = query.input_stream.right.unique_stream_id
            for side_key, s in (("left", query.input_stream.left),
                                ("right", query.input_stream.right)):
                if side_key not in proxies:
                    continue
                sid = s.unique_stream_id
                if sid in self.named_windows:
                    if _left_sid == _right_sid:
                        # a window joined with ITSELF processes each
                        # emission through ONE side chain only (reference
                        # MultiProcessStreamReceiver with processCount=1 —
                        # JoinInputStreamParser.java:129-135; both sides
                        # triggering would emit every match twice). Keep
                        # the TRIGGERING side (unidirectional joins pin it).
                        from siddhi_tpu.query_api.execution import EventTrigger

                        keep = ("right" if query.input_stream.trigger
                                == EventTrigger.RIGHT else "left")
                        if side_key != keep:
                            continue
                    self.named_windows[sid].out_junction.subscribe(proxies[side_key])
                elif (partition_ctx is not None and s.is_inner_stream):
                    if sid not in partition_ctx.inner_junctions:
                        raise SiddhiAppValidationException(
                            f"inner stream '{sid}' is consumed before any "
                            f"query in this partition produces it")
                    partition_ctx.inner_junctions[sid].subscribe(
                        proxies[side_key])
                else:
                    self.junctions[sid].subscribe(proxies[side_key])
        elif partition_ctx is not None and query.input_stream.is_inner_stream:
            input_stream_id = query.input_stream.unique_stream_id
            if input_stream_id not in partition_ctx.inner_junctions:
                raise SiddhiAppValidationException(
                    f"inner stream '{input_stream_id}' is consumed before any query "
                    f"in this partition produces it"
                )
            partition_ctx.inner_junctions[input_stream_id].subscribe(runtime)
        elif query.input_stream.unique_stream_id in self.named_windows:
            # `from W`: consume the named window's emissions
            self.named_windows[query.input_stream.unique_stream_id].out_junction.subscribe(runtime)
        else:
            self.junctions[query.input_stream.unique_stream_id].subscribe(runtime)
        self.query_runtimes[query_name] = runtime
        if partition_ctx is not None:
            partition_ctx.runtimes.append(runtime)

    # ------------------------------------------------------------- API

    def get_input_handler(self, stream_id: str) -> InputHandler:
        return self.input_manager.get_input_handler(stream_id)

    # Java-style alias
    getInputHandler = get_input_handler

    def add_callback(self, id_: str, callback):
        """addCallback(streamId, StreamCallback) or (queryName, QueryCallback)
        — reference SiddhiAppRuntimeImpl overloads."""
        if isinstance(callback, StreamCallback):
            if id_ not in self.junctions:
                raise SiddhiAppValidationException(f"stream '{id_}' is not defined")
            callback.stream_id = id_
            self.junctions[id_].subscribe(callback)
            self._stream_callback_adapters.append(callback)
        elif isinstance(callback, QueryCallback):
            if id_ not in self.query_runtimes:
                raise SiddhiAppValidationException(f"query '{id_}' not found")
            callback.query_name = id_
            self.query_runtimes[id_].query_callbacks.append(callback)
        else:
            raise TypeError(f"unsupported callback type {type(callback)}")

    addCallback = add_callback

    def remove_callback(self, callback):
        """Detach a previously added Stream/QueryCallback (reference
        SiddhiAppRuntimeImpl.removeCallback — CallbackTestCase: events
        sent after removal no longer reach it)."""
        if isinstance(callback, StreamCallback):
            j = self.junctions.get(getattr(callback, "stream_id", ""))
            if j is not None and callback in j.receivers:
                j.receivers.remove(callback)
            if callback in self._stream_callback_adapters:
                self._stream_callback_adapters.remove(callback)
        elif isinstance(callback, QueryCallback):
            for qr in self.query_runtimes.values():
                if callback in qr.query_callbacks:
                    qr.query_callbacks.remove(callback)

    removeCallback = remove_callback

    def start(self):
        with self._barrier:  # lazy start can race concurrent first sends
            if self._started:
                return
            self._started = True
            # critical-path profiler knobs: refcounted process-wide
            # enables, paired one-for-one with the disables in shutdown()
            if not self._profiling_on and (self.app_context.profile_journeys
                                           or self.app_context.profile_costs):
                from siddhi_tpu.observability import costmodel, journey

                if self.app_context.profile_journeys:
                    journey.enable()
                if self.app_context.profile_costs:
                    costmodel.enable()
                self._profiling_on = True
            # device telemetry plane: default-on per-app knob holds one
            # refcount on the process collector for the app's lifetime
            # (same discipline as profile_journeys)
            if (not self._instruments_on
                    and self.app_context.profile_device_instruments):
                from siddhi_tpu.observability import instruments

                instruments.enable()
                self._instruments_on = True
            # multicore ingest (core/stream/input/pack_pool.py): with
            # siddhi_tpu.ingest_pool > 0, pack/encode work shards across
            # that many supervised worker threads; every pack call site
            # reads the pool through core.event.pack_pool_of
            if (self.app_context.ingest_pool > 0
                    and self.app_context.ingest_pack_pool is None):
                from siddhi_tpu.core.stream.input.pack_pool import (
                    IngestPackPool,
                )

                self.app_context.ingest_pack_pool = IngestPackPool(
                    self.app_context,
                    workers=self.app_context.ingest_pool,
                    split_rows=self.app_context.ingest_split)
            # closed-loop controller (siddhi_tpu/autopilot/): register
            # with the per-process controller when the knob is armed —
            # 'off' (the default) keeps the engine free of any
            # controller thread, observation or actuation
            if getattr(self.app_context, "autopilot", "off") != "off":
                from siddhi_tpu.autopilot.controller import (
                    AutopilotController,
                )

                AutopilotController.instance().register(self)
            for j in self.junctions.values():
                j.start_processing()
            scheduler = self.app_context.scheduler
            for qr in self.query_runtimes.values():
                if qr.rate_limiter is not None:
                    qr.rate_limiter.start(scheduler)
                if hasattr(qr, "arm_initial"):
                    qr.arm_initial()  # head-absent patterns wait from start
            for sr in self.sink_runtimes:
                sr.connect()
            for sr in self.source_runtimes:
                # connect with retry/backoff off-thread (Source.java:155-185)
                t = threading.Thread(target=sr.connect_with_retry, daemon=True)
                t.start()
            for agg in self.aggregations.values():
                if agg.purge_enabled and scheduler is not None:
                    scheduler.schedule_periodic(
                        agg.purge_interval_ms,
                        lambda ts, a=agg: a.purge(ts))
            # cache-table retention sweeps (reference CacheExpirer: a
            # periodic task deletes cache rows older than retention.period)
            for t in self.tables.values():
                cache = getattr(t, "cache", None)
                if (cache is not None and cache.retention_ms is not None
                        and scheduler is not None):
                    scheduler.schedule_periodic(
                        cache.purge_interval_ms,
                        lambda _ts, c=cache: c.expire())
            if self.app_context.statistics_manager is not None:
                self.app_context.statistics_manager.start_reporting(scheduler)
                self._register_statistic_probes()
            for pctx in self.partition_contexts:
                if pctx.purge_interval_ms is not None and scheduler is not None:
                    scheduler.schedule_periodic(
                        pctx.purge_interval_ms,
                        lambda _ts, p=pctx: p.purge())  # wall clock, not event time
            for tr in self.trigger_runtimes:
                tr.start()

    def debug(self):
        """Attach a SiddhiDebugger (reference SiddhiAppRuntime.debug)."""
        from siddhi_tpu.core.debugger import SiddhiDebugger

        if getattr(self, "_debugger", None) is None:
            # breakpoints instrument per-runtime delivery methods, which a
            # fused group bypasses — debugging runs unfused
            for g in list(self.fused_fanout_groups):
                g.dissolve()
            self.fused_fanout_groups = []
            self._debugger = SiddhiDebugger(self)
        return self._debugger

    def _register_statistic_probes(self):
        """DETAIL memory + buffered-events probes for every stateful
        element — the analog of ``SiddhiAppRuntimeImpl.
        monitorQueryMemoryUsage:757-782`` (reflective deep size there;
        exact pytree/array nbytes here) and ``monitorBufferedEvents:
        784-821`` (@Async ring fill there; junction queue depth + deferred
        device outputs here). Idempotent — probes are keyed by name."""
        from siddhi_tpu.core.util.statistics import pytree_nbytes

        sm = self.app_context.statistics_manager
        if sm is None:
            return
        # dirty-guard: probe sets only change when runtimes are built, so
        # a statistics() polling loop must not rebuild closures per poll
        sig = (len(self.query_runtimes), len(self.tables),
               len(self.named_windows), len(self.aggregations),
               len(self.junctions))
        if getattr(self, "_probe_sig", None) == sig:
            return
        self._probe_sig = sig
        for name, qr in self.query_runtimes.items():
            sm.register_memory_probe(
                f"query.{name}", lambda q=qr: pytree_nbytes(q._state))
            sm.register_buffer_probe(
                f"query.{name}.deferred_outputs",
                lambda q=qr: len(q._deferred))
        for name, t in self.tables.items():
            sm.register_memory_probe(
                f"table.{name}", lambda tb=t: _element_state_bytes(tb))
        for name, w in self.named_windows.items():
            sm.register_memory_probe(
                f"window.{name}", lambda win=w: _element_state_bytes(win))
        for name, agg in self.aggregations.items():
            sm.register_memory_probe(
                f"aggregation.{name}", lambda a=agg: _agg_store_bytes(a))
        for sid, j in self.junctions.items():
            if getattr(j, "_queue", None) is not None:
                sm.register_buffer_probe(
                    f"junction.{sid}", lambda jn=j: jn._queue.qsize())

    def statistics(self) -> dict:
        """Metrics snapshot (reference SiddhiAppRuntime.getStatistics)."""
        sm = self.app_context.statistics_manager
        if sm is None:
            return {"level": "off"}
        self._register_statistic_probes()   # cover late-built runtimes
        return sm.report()

    def set_statistics_level(self, level: str):
        """'off' | 'basic' | 'detail' (reference setStatisticsLevel)."""
        from siddhi_tpu.core.util.statistics import StatisticsManager, parse_level

        if self.app_context.statistics_manager is None:
            self.app_context.statistics_manager = StatisticsManager()
        self.app_context.statistics_manager.set_level(parse_level(level))
        self._register_statistic_probes()

    setStatisticsLevel = set_statistics_level

    def start_trace(self, log_dir: str):
        """Start a device-level profiler trace (XLA/TPU timeline) into
        ``log_dir`` — the TPU-native answer to the reference's latency
        tracker detail level: per-op device timings come from the XLA
        profiler rather than per-processor stopwatches. View with
        TensorBoard or xprof. For its duration the engine's spans and
        batch journeys are on (``journey.enable``: the one switch; the
        stage histograms on ``/metrics`` and the journey ring fill as a
        side effect), so the
        trace holds the host stages (``siddhi.pack``, ``siddhi.query.step``,
        ``siddhi.meta_pull``, ``siddhi.emit``, ``siddhi.pull``, ...) on
        ``/host:CPU`` beside the device's timeline."""
        import jax

        from siddhi_tpu.observability import journey

        if getattr(self, "_tracing", False):
            raise RuntimeError("a trace is already running")
        jax.profiler.start_trace(log_dir)
        journey.enable()
        self._tracing = True
        return log_dir

    def stop_trace(self):
        import jax

        from siddhi_tpu.observability import journey

        if not getattr(self, "_tracing", False):
            raise RuntimeError("no trace is running")
        # this trace's hold is released exactly once, whatever the
        # profiler does: a retry after a failed stop must not give back
        # somebody else's (profile_journeys, /profile/journeys)
        self._tracing = False
        try:
            jax.profiler.stop_trace()
        finally:
            journey.disable()

    def shutdown(self):
        self.app_context.stopped = True
        if getattr(self.app_context, "autopilot", "off") != "off":
            # detach FIRST: no actuation may land on a tearing-down app
            # (identity-pinned — an old runtime never strips a newer
            # same-named app's controller registration)
            from siddhi_tpu.autopilot.controller import AutopilotController

            AutopilotController.instance().unregister(
                self.app_context.name, app_runtime=self)
        if self.app_context.supervisor is not None:
            self.app_context.supervisor.stop()
        if getattr(self.app_context, "overload", None) is not None:
            # drop the process-global registration (fair-scheduler slot,
            # per-app control); identity-pinned so shutting down an OLD
            # runtime never strips a newer same-named app's quotas
            from siddhi_tpu.resilience.overload import OverloadManager

            OverloadManager.instance().unregister(
                self.app_context.name, ctl=self.app_context.overload)
            self.app_context.overload = None
        self.app_context.timestamp_generator.stop_heartbeat()
        pump = getattr(self.app_context, "completion_pump", None)
        if pump is not None and pump.has_pending:
            # batches still riding the dispatch pipeline emit before
            # teardown (async tails are additionally flushed by each
            # worker as its last act on the stop sentinel)
            try:
                pump.flush()
            except RuntimeError:
                import logging

                logging.getLogger(__name__).exception(
                    "pipeline flush failed during shutdown")
        for qr in self.query_runtimes.values():
            if getattr(qr, "_deferred", None):
                try:
                    qr.flush_deferred()
                except RuntimeError:
                    # deferred overflow error must not abort teardown —
                    # outputs were drained before the raise
                    import logging

                    logging.getLogger(__name__).exception(
                        "deferred flush failed during shutdown")
        if self.app_context.statistics_manager is not None:
            self.app_context.statistics_manager.stop_reporting(
                self.app_context.scheduler)
        for sr in self.source_runtimes:
            sr.shutdown()
        for tr in self.trigger_runtimes:
            tr.stop()
        for qr in self.query_runtimes.values():
            if qr.rate_limiter is not None:
                qr.rate_limiter.stop()
        for j in self.junctions.values():
            j.stop_processing()
        for sr in self.sink_runtimes:
            sr.shutdown()
        if self.app_context.ingest_pack_pool is not None:
            # after junction workers stopped: no pack can be in flight
            self.app_context.ingest_pack_pool.shutdown()
            self.app_context.ingest_pack_pool = None
        if self.app_context.scheduler is not None:
            self.app_context.scheduler.shutdown()
        from siddhi_tpu.core.util import program_cache

        # release this app's refs on the process-global compiled-program
        # cache; entries reaching refcount zero evict (free) here. The
        # owner token is this runtime's telemetry-registry INSTANCE
        # (identity-pinned, the blue/green convention): an OLD runtime's
        # shutdown can never strip the programs a newer same-named app
        # acquired through ITS registry.
        program_cache.cache().release_owner(self.app_context.telemetry)
        from siddhi_tpu.observability import journey

        # this app's wall-tracking must die with it (a redeployed
        # same-named app starts a fresh observation window)
        journey.forget_app(self.app_context.name)
        if getattr(self, "_tracing", False):
            try:
                self.stop_trace()     # and with it the trace's hold
            except Exception:  # noqa: BLE001 — the profiler's own fault
                import logging

                logging.getLogger(__name__).exception(
                    "stopping the device trace at shutdown")
        if self._profiling_on:
            # release this runtime's refcount on the process collectors
            from siddhi_tpu.observability import costmodel

            if self.app_context.profile_journeys:
                journey.disable()
            if self.app_context.profile_costs:
                costmodel.disable()
            self._profiling_on = False
        if self._instruments_on:
            from siddhi_tpu.observability import instruments

            instruments.disable()
            self._instruments_on = False
        self._started = False

    # ----------------------------------------------------- resilience API

    def enable_autopilot(self, mode: str = "on",
                         interval_s: Optional[float] = None,
                         cooldown_s: Optional[float] = None):
        """Arm the closed-loop controller (``siddhi_tpu/autopilot/``)
        programmatically — the API spelling of the
        ``siddhi_tpu.autopilot`` config knob. ``mode`` is ``'on'`` or
        ``'dry_run'`` (decide + log, never actuate). Idempotent;
        registration with the per-process controller happens here when
        the app already started, else at ``start()``. Returns the
        controller."""
        from siddhi_tpu.autopilot.controller import AutopilotController
        from siddhi_tpu.core.util.knobs import KNOBS

        self.app_context.autopilot = KNOBS["autopilot"].parse(mode)
        if self.app_context.autopilot == "off":
            raise ValueError("enable_autopilot with mode 'off' — use the "
                             "config knob to keep the controller out")
        if interval_s is not None:
            self.app_context.autopilot_interval_s = float(interval_s)
        if cooldown_s is not None:
            self.app_context.autopilot_cooldown_s = float(cooldown_s)
        ctl = AutopilotController.instance()
        if self._started:
            ctl.register(self)
        return ctl

    def enable_wal(self, max_batches: int = 4096,
                   max_events: Optional[int] = None):
        """Attach a bounded ingest WAL (``resilience/replay.py``): every
        accepted batch is recorded until the next checkpoint barrier trims
        it; ``restore_revision`` replays the retained suffix, turning
        checkpoint recovery from at-most-once into effectively-once.
        Idempotent; returns the WAL."""
        from siddhi_tpu.resilience.replay import IngestWAL, register_wal_gauges

        if self.app_context.ingest_wal is None:
            self.app_context.ingest_wal = IngestWAL(
                max_batches=max_batches, max_events=max_events,
                app_context=self.app_context)
        # scrapeable WAL size/loss gauges (GET /metrics): a log that
        # keeps dropping batches means checkpoints are too far apart for
        # the configured bound
        register_wal_gauges(self.app_context)
        return self.app_context.ingest_wal

    def supervise(self, interval_s: float = 0.25,
                  wedge_timeout_s: float = 5.0, peer_recovery=None,
                  peer_monitor=None):
        """Start an ``AppSupervisor`` (``resilience/supervisor.py``) that
        heartbeats this app's @Async junction workers — restarting dead or
        wedged ones with their queues intact — and, when ``peer_recovery``
        is given, runs the cluster-peer recovery protocol on a peer
        failure (a ``ClusterPeerError`` from the bounded pull, or a lost
        ``peer_monitor`` heartbeat). Idempotent; returns the supervisor."""
        from siddhi_tpu.resilience.supervisor import AppSupervisor

        if self.app_context.supervisor is None:
            AppSupervisor(self, interval_s=interval_s,
                          wedge_timeout_s=wedge_timeout_s,
                          peer_recovery=peer_recovery,
                          peer_monitor=peer_monitor).start()
        return self.app_context.supervisor

    # ---------------------------------------------------- persistence API

    @property
    def persistence(self):
        from siddhi_tpu.core.util.snapshot import PersistenceManager

        if getattr(self, "_persistence", None) is None:
            self._persistence = PersistenceManager(self)
        return self._persistence

    def persist(self) -> str:
        """Checkpoint all state to the configured persistence store;
        returns the revision id (reference SiddhiAppRuntimeImpl.persist:677).
        Sources are paused around the snapshot so no events race the
        checkpoint (reference pauses source handlers during persist)."""
        for sr in self.source_runtimes:
            sr.pause()
        try:
            return self.persistence.persist()
        finally:
            for sr in self.source_runtimes:
                sr.resume()

    def persist_incremental(self) -> str:
        """Op-log checkpoint chained to the last revision (reference
        incremental snapshots); falls back to full when none exists."""
        for sr in self.source_runtimes:
            sr.pause()
        try:
            return self.persistence.persist_incremental()
        finally:
            for sr in self.source_runtimes:
                sr.resume()

    def restore_revision(self, revision: str):
        self.persistence.restore_revision(revision)

    restoreRevision = restore_revision

    def restore_last_revision(self):
        return self.persistence.restore_last_revision()

    restoreLastRevision = restore_last_revision

    def clear_all_revisions(self):
        self.persistence.clear_all_revisions()

    def snapshot(self) -> bytes:
        """Raw state snapshot bytes (reference SiddhiAppRuntime.snapshot)."""
        from siddhi_tpu.core.util.snapshot import SnapshotService

        with self._barrier:
            return SnapshotService(self).full_snapshot()

    def restore(self, snapshot: bytes):
        from siddhi_tpu.core.util.snapshot import SnapshotService

        with self._barrier:
            SnapshotService(self).restore(snapshot)

    # ------------------------------------------------------ on-demand API

    def query(self, on_demand_query: str) -> List[Event]:
        """Run an ad-hoc (store) query against a table or named window —
        reference ``SiddhiAppRuntimeImpl.query`` +
        ``util/parser/OnDemandQueryParser.java``."""
        from siddhi_tpu.core.query.on_demand import run_on_demand_query
        from siddhi_tpu.ops import expressions as _expr_mod

        # lazy compiles resolve against THIS app's registry (manager
        # extensions + script functions)
        _expr_mod.set_active_extensions(self._extensions)

        # barrier management lives in run_on_demand_query: mutations and
        # table/window finds serialize on the app barrier as before, but
        # aggregation store-queries read epoch-pinned per-shard snapshots
        # and must NOT hold it — the serving tier's whole point is that a
        # dashboard query storm never stalls ingest (which takes the same
        # barrier on every send)
        return run_on_demand_query(on_demand_query, self)

    @property
    def query_names(self) -> List[str]:
        return list(self.query_runtimes)

    def get_queries(self) -> List:
        """Query runtimes in declaration order (reference
        ``SiddhiAppRuntime.getQueries``)."""
        return list(self.query_runtimes.values())


def _element_state_bytes(el) -> int:
    """State footprint of a table or named window, whatever its backing:
    dense arrays (``state``), a store-backed adapter (row count x columnar
    row width, incl. its cache rows), or a host-mode window's columnar
    probe surface."""
    from siddhi_tpu.core.util.statistics import pytree_nbytes

    st = getattr(el, "state", None)
    if st is not None:
        return pytree_nbytes(st)
    if hasattr(el, "count") and hasattr(el, "col_specs"):
        # RecordTableAdapter: rows live behind the SPI; size them by the
        # columnar row width this adapter would encode them at
        import numpy as np

        row = sum(np.dtype(d).itemsize + 1 for d in el.col_specs.values())
        n = int(el.count)
        cache = getattr(el, "cache", None)
        return n * row + (len(cache) * row if cache is not None else 0)
    if hasattr(el, "contents"):
        c = el.contents()   # host-mode named window
        return pytree_nbytes(c[0] if isinstance(c, tuple) else c)
    return 0


def _agg_store_bytes(agg) -> int:
    """State footprint of an incremental aggregation: the host cube's
    stored base values (8 bytes each — floats/longs in per-group lists)
    plus any array-valued running state. The reference sizes this with a
    reflective object walk (ObjectSizeCalculator.java:66); the dense cube
    makes it a direct count."""
    total = 0
    # sharded serving runtimes hold their cube in per-shard stores
    for holder in (getattr(agg, "shards", None) or [agg]):
        for dstore in getattr(holder, "store", {}).values():
            for groups in dstore.values():
                for vals in groups.values():
                    total += 8 * len(vals)
    for v in vars(agg).values():
        if hasattr(v, "nbytes"):
            total += int(v.nbytes)
    return total
