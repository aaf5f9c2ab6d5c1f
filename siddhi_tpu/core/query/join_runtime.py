"""Host driver for two-stream window joins.

The counterpart of reference ``query/input/stream/join/JoinProcessor.java``
+ ``JoinInputStreamParser.java``: each side owns a window stage; an arriving
chunk is inserted into its own window first (pre-join forwards, trigger
false — ``JoinInputStreamParser.java:344``), then every row the window
emits (CURRENT and EXPIRED) probes the other side's buffer with the
compiled `on` condition (post-join trigger — ``:348``,
``JoinProcessor.execute:107-170``) as one masked [N, W] broadcast compare.
Outer sides emit a null-padded row when nothing matches.

Extensions beyond the basic stream-stream shape:
- group-by selectors (host keyer over the joined columns — split pipeline)
- joins inside partitions: keyed window sides, per-row probes gathered from
  the other side's ``[K, W]`` ring by partition key
- host-mode window sides (sort/frequent/session): the window runs host-side
  and exposes its ``contents()`` as the probe surface
- aggregation joins (``join AggName within ... per ...``): the aggregation's
  stitched buckets are the probe store (``AggregationRuntime.java:331-357``)
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.eligibility import ReasonCode as _RC
from siddhi_tpu.core.eligibility import reason as _reason
from siddhi_tpu.core.event import (Event, HostBatch, LazyColumns, _pad_len,
                                   launch_step, pack_pool_of)
from siddhi_tpu.core.plan.selector_plan import FLUSH_KEY, GK_KEY
from siddhi_tpu.core.query.runtime import QueryRuntime, pack_meta
from siddhi_tpu.core.stream.junction import FatalQueryError, Receiver
from siddhi_tpu.observability import instruments, journey
from siddhi_tpu.ops.expressions import (
    OKEY_KEY,
    PK_KEY,
    TS_KEY,
    TYPE_KEY,
    VALID_KEY,
    ColumnRef,
    CompileError,
    Resolver,
)
from siddhi_tpu.ops.windows import conform_cols
from siddhi_tpu.ops.types import dtype_of
from siddhi_tpu.query_api.definitions import AttrType, StreamDefinition
from siddhi_tpu.query_api.expressions import Variable

_LOG = logging.getLogger("siddhi_tpu.join")

CURRENT, EXPIRED, TIMER, RESET = 0, 1, 2, 3


@dataclass
class JoinSide:
    key: str                     # 'left' | 'right'
    stream_id: str
    ref_id: Optional[str]
    definition: StreamDefinition
    window_stage: object         # None for shared-store (table/window) sides
    filters: List[Callable]
    triggers: bool               # unidirectional: does this side emit?
    outer: bool                  # emit null-padded row when no match
    # shared probe-only store with a contents() -> (cols, valid) surface
    # (InMemoryTable / NamedWindowRuntime / AggregationJoinStore)
    store: object = None
    # host-mode window (sort/frequent/...): processed host-side; its
    # contents() is the probe surface, its emissions trigger the join
    host_window: object = None
    keyer: object = None         # partition keyer (partitioned joins)
    # stream-function column transforms applied before filters/window
    transforms: List = field(default_factory=list)
    # when transforms append attributes, `definition` is the extended
    # (post-transform) shape; ingest packing uses the declared one
    input_definition: Optional[StreamDefinition] = None
    # filters after the window: mask this side's emitted (trigger) rows
    post_filters: List = field(default_factory=list)
    # inside a partition: a NON-partitioned stream side — one shared
    # (unkeyed) window, events visible to every partition instance
    # (reference: non-partitioned streams reach all instances)
    global_side: bool = False
    # inner '#stream' / partition-local side: rows carry their pk
    carried_pk: bool = False

    @property
    def pack_definition(self) -> StreamDefinition:
        return self.input_definition or self.definition

    @property
    def prefix(self) -> str:
        return "l__" if self.key == "left" else "r__"

    @property
    def probe_external(self) -> bool:
        """Probe columns come from outside the jitted state."""
        return self.store is not None or self.host_window is not None


class AggregationJoinStore:
    """Probe adapter over an incremental aggregation's stitched buckets
    (reference ``join AggName within <start>, <end> per '<duration>'``)."""

    def __init__(self, agg, duration, within: Optional[tuple]):
        self.agg = agg
        self.duration = duration
        self.within = within
        self.definition = agg.output_definition()
        self.dynamic = None      # (per_of, within_of) raw-value closures
        self.dynamic_raw = None  # uncompiled expressions (set by the planner)

    def contents(self):
        _defn, cols, valid = self.agg.contents(self.duration, self.within)
        return cols, valid

    def resolve_groups(self, cols, ctx):
        """Group trigger rows by their per-event (duration, within) values
        (``within i.startTime, i.endTime per i.perValue``); each group
        probes its own stitched-bucket surface. Timer rows and rows whose
        values don't parse ride the first group (they only advance window
        clocks — no probe of their own)."""
        from siddhi_tpu.core.aggregation.incremental import parse_duration_name
        from siddhi_tpu.core.aggregation.within_time import (
            bound_ms, single_within_range)
        from siddhi_tpu.ops.expressions import TYPE_KEY, VALID_KEY

        per_of, within_of = self.dynamic
        valid = np.asarray(cols[VALID_KEY])
        is_timer = np.asarray(cols[TYPE_KEY]) == TIMER
        n = len(valid)
        pers = per_of(cols, ctx) if per_of is not None else None
        wins = within_of(cols, ctx) if within_of is not None else None
        groups: dict = {}
        carry = []
        for i in range(n):
            if not valid[i]:
                continue
            if is_timer[i]:
                carry.append(i)
                continue
            try:
                dur = parse_duration_name(pers[i]) if pers is not None \
                    else self.duration
                if wins is not None:
                    w = wins[i]
                    if isinstance(w, tuple):
                        win = (bound_ms(w[0]), bound_ms(w[1]))
                        if not win[0] < win[1]:
                            raise ValueError("within start must be < end")
                    elif isinstance(w, str):
                        win = single_within_range(w)
                    else:
                        win = (int(w), 2 ** 62)
                else:
                    win = self.within
            except Exception as e:
                # reference logs at the processor and drops the event
                _LOG.warning("aggregation join: dropping trigger row with "
                             "unresolvable within/per: %s", e)
                continue
            groups.setdefault((dur, win), []).append(i)
        if not groups:
            groups[(self.duration or parse_duration_name("seconds"),
                    self.within)] = []
        out = []
        for gi, ((dur, win), idx) in enumerate(groups.items()):
            mask = np.zeros(n, bool)
            mask[idx] = True
            if gi == 0:
                mask[carry] = True
            out.append((mask, dur, win))
        return out


class JoinResolver(Resolver):
    """Resolve selector/on-condition variables to prefixed joined columns."""

    def __init__(self, left: JoinSide, right: JoinSide, dictionary):
        self.sides = [left, right]
        self.dictionary = dictionary
        self.synthetic: Dict[str, AttrType] = {}

    def resolve(self, var: Variable) -> ColumnRef:
        if var.attribute_name in self.synthetic and var.stream_id is None:
            return ColumnRef(var.attribute_name, self.synthetic[var.attribute_name])
        sid = var.stream_id
        matches = []
        for side in self.sides:
            if sid is not None and sid not in (side.ref_id, side.stream_id):
                continue
            try:
                attr = side.definition.attribute(var.attribute_name)
            except Exception:
                continue
            matches.append((side, attr))
        if not matches:
            raise CompileError(
                f"cannot resolve '{(sid + '.') if sid else ''}{var.attribute_name}' "
                f"in join query"
            )
        if len(matches) > 1:
            # self-joins: the raw stream id matches both sides too
            raise CompileError(
                f"'{(sid + '.') if sid else ''}{var.attribute_name}' is ambiguous "
                f"between the join sides — qualify it with the `as` reference"
            )
        side, attr = matches[0]
        return ColumnRef(side.prefix + attr.name, attr.type)

    def encode_string(self, s: str) -> int:
        return self.dictionary.encode(s)


def _side_batch(batch: HostBatch, definition) -> HostBatch:
    """What ``receive(junction.decode_events(batch))`` packs, straight from
    the columns: the valid rows in order, each attribute in the
    definition's dtype (zero under its null mask), CURRENT or EXPIRED,
    padded as a pack pads. The junction's batch is shared by its
    receivers and may be another query's output; this one is the side's
    own."""
    cols = batch.cols
    idx = np.flatnonzero(np.asarray(cols[VALID_KEY], bool))
    n, b = len(idx), _pad_len(len(idx))
    out = {TS_KEY: np.zeros(b, np.int64), VALID_KEY: np.zeros(b, bool),
           TYPE_KEY: np.full(b, CURRENT, np.int8)}
    out[VALID_KEY][:n] = True
    out[TS_KEY][:n] = np.asarray(cols[TS_KEY])[idx]
    out[TYPE_KEY][:n][np.asarray(cols[TYPE_KEY])[idx] == EXPIRED] = EXPIRED
    for a in definition.attributes:
        mask = cols.get(a.name + "?")
        null = (np.asarray(mask, bool)[idx] if mask is not None
                else np.zeros(n, bool))
        vals = np.zeros(b, dtype_of(a.type))
        vals[:n] = np.where(null, 0, np.asarray(cols[a.name])[idx])
        out[a.name] = vals
        out[a.name + "?"] = np.zeros(b, bool)
        out[a.name + "?"][:n] = null
    packed = HostBatch(out, size=n)
    packed.journey = batch.journey
    return packed


def _inert_probe(step):
    """A device-engine side step as a plain ``(state, cols, now)`` step:
    both probe surfaces live inside the state, so the probe arguments of
    the side-step signature are inert."""
    placeholder = jnp.zeros((1,), bool)

    def fn(state, cols, now):
        return step(state, {}, placeholder, cols, now)

    return fn


class JoinSideProxy(Receiver):
    """Per-side receiver of a join runtime. Beyond plain delivery it
    implements the fused fan-out MEMBER protocol
    (``core/query/fused_fanout.py``): an engine-attached join side can
    fuse with sibling single-stream queries on a shared junction — the
    side's insert+probe folds into the junction's ONE jitted step and its
    meta rides the group's combined pull (the engine's in-state probe
    surfaces are what make the side step a pure ``(state, cols, now)``
    function like any other member's)."""

    _fanout_group = None
    _own_keyer = None

    def __init__(self, runtime: "JoinQueryRuntime", side_key: str):
        self.runtime = runtime
        self.side_key = side_key

    # ------------------------------------------------ fused member protocol

    def fusion_ineligibility(self) -> Optional[str]:
        """Why this join side cannot join a fused fan-out group (None =
        eligible) — consulted by ``fanout_plan.fusion_ineligibility``."""
        rt = self.runtime
        if rt.engine is None:
            return _reason(
                _RC.NO_DEVICE_ENGINE,
                f"join side without device engine ({rt.engine_reason})")
        if rt.keyer is not None:
            return _reason(_RC.GROUPED_SELECT,
                           "grouped join selector (split host-keyed "
                           "pipeline)")
        if rt._shard_mesh is not None or rt._route_layout is not None:
            return _reason(_RC.SHARDED, "mesh-sharded join")
        for side in rt.sides.values():
            st = side.window_stage
            if st is not None and getattr(st, "needs_scheduler", False):
                return _reason(_RC.SCHEDULER_WINDOW,
                               "scheduler-driven join window")
        if rt.sides["left"].stream_id == rt.sides["right"].stream_id:
            # both proxies would fuse onto ONE junction sharing one state
            # pytree — the fused step would donate it twice per dispatch
            return _reason(_RC.SELF_JOIN,
                           "self-join (both sides share the junction batch)")
        return None

    @property
    def name(self) -> str:
        return f"{self.runtime.name}.{self.side_key}"

    @property
    def app_context(self):
        return self.runtime.app_context

    @property
    def input_definition(self):
        return self.runtime.sides[self.side_key].pack_definition

    @property
    def dictionary(self):
        return self.runtime.dictionary

    @property
    def selector_plan(self):
        return self.runtime.selector_plan

    @property
    def keyer(self):
        return self.runtime.keyer

    @keyer.setter
    def keyer(self, value):
        self.runtime.keyer = value

    @property
    def _win_keys(self):
        return self.runtime._win_keys

    @property
    def _lock(self):
        return self.runtime._lock

    @property
    def _state(self):
        return self.runtime._state

    @_state.setter
    def _state(self, value):
        self.runtime._state = value

    @property
    def scheduler(self):
        return self.runtime.scheduler

    def process_timer(self, ts: int):
        # per-side notify attribution: a fused side's wake time re-enters
        # through ITS OWN timer callback (defensive — eligible sides carry
        # no scheduler-driven window)
        self.runtime._timer(self.side_key, ts)

    def _ensure_capacity(self):
        self.runtime._ensure_capacity()

    def _init_state(self):
        return self.runtime._init_state()

    def prepare_cols(self, cols) -> bool:
        """Fused-group pre-dispatch hook: adaptive sub-window growth for
        this side's batch (mirrors ``process_side_batch``'s call). True =
        state shapes changed, the group must re-jit its fused step."""
        eng = self.runtime.engine
        if eng is None:
            return False
        if self.runtime._state is None:
            self.runtime._state = self.runtime._init_state()
        return eng.prepare_batch(self.side_key, cols)

    def overflow_knob_msg(self, code: Optional[int] = None):
        # forward the overflow bitmask: the fused drain must name the
        # selector knob, not default to window capacity
        return self.runtime.overflow_knob_msg(code)

    def decode_meta_suffix(self, meta):
        """Fused-member drain hook: this side's padded meta row decodes
        by the RUNTIME's spec (seq + matches — the unrouted runtime's
        instrument_slots), into the runtime's telemetry."""
        self.runtime.decode_meta_suffix(meta)

    def _emit(self, out: HostBatch):
        self.runtime._emit(out)

    def build_step_fn(self):
        """The side's fused-member step: the engine's, as a
        ``(state, cols, now)`` step (``_inert_probe``)."""
        return _inert_probe(self.runtime.build_side_step_fn(self.side_key))

    # ---------------------------------------------------------- delivery

    def receive_batch(self, batch: HostBatch, junction=None):
        """Columnar fast path: the batch the event path would pack from
        the junction's, with no Event objects (``_side_batch``). A
        partition-local side keeps the event path (its rows carry their
        producer's partition key), as does a set-valued attribute."""
        side = self.runtime.sides[self.side_key]
        defn = side.pack_definition
        if side.carried_pk or any(a.type == AttrType.OBJECT
                                  for a in defn.attributes):
            super().receive_batch(batch, junction)
            return
        self.runtime.process_side_batch(self.side_key,
                                        _side_batch(batch, defn))

    def receive(self, events: List[Event]):
        side = self.runtime.sides[self.side_key]
        if side.carried_pk:
            # inner-'#stream' / partition-local side: rows keep the
            # producing instance's pk. Events WITHOUT a pk (the stream is
            # a global junction anyone can feed) are broadcast to every
            # active instance like a global side — attributing them to
            # instance 0 would corrupt key 0's join state.
            keyed = [e for e in events if e.pk is not None]
            bare = [e for e in events if e.pk is None]
            if keyed:
                batch = HostBatch.from_events(
                    keyed, side.pack_definition, self.runtime.dictionary)
                pk = np.zeros(batch.capacity, np.int32)
                for i, e in enumerate(keyed):
                    pk[i] = e.pk
                batch.cols[PK_KEY] = pk
                self.runtime.process_side_batch(self.side_key, batch)
            if bare:
                n = self.runtime.partition_ctx.active_keys() \
                    if self.runtime.partition_ctx is not None else 0
                if n > 0:
                    rep = [Event(timestamp=e.timestamp, data=e.data,
                                 is_expired=e.is_expired, pk=k)
                           for e in bare for k in range(n)]
                    batch = HostBatch.from_events(
                        rep, side.pack_definition, self.runtime.dictionary)
                    pk = np.zeros(batch.capacity, np.int32)
                    for i, e in enumerate(rep):
                        pk[i] = e.pk
                    batch.cols[PK_KEY] = pk
                    self.runtime.process_side_batch(self.side_key, batch)
            return
        batch = HostBatch.from_events(
            events, side.pack_definition, self.runtime.dictionary,
            pool=pack_pool_of(self.runtime.app_context))
        self.runtime.process_side_batch(self.side_key, batch)


class JoinQueryRuntime(QueryRuntime):
    def is_stateful(self) -> bool:
        # window/NFA state is always snapshot-relevant
        return True

    def __init__(self, name, app_context, left: JoinSide, right: JoinSide,
                 on_cond: Optional[Callable], selector_plan, dictionary,
                 partition_ctx=None, group_keyer=None):
        super().__init__(
            name=name,
            app_context=app_context,
            input_definition=None,
            filters=[],
            window_stage=None,
            selector_plan=selector_plan,
            keyer=group_keyer,
            dictionary=dictionary,
            partition_ctx=partition_ctx,
        )
        self.sides = {"left": left, "right": right}
        self.on_cond = on_cond
        # @index equality probe spec from the planner (None = broadcast
        # compare): {"store_side", "attr", "val_fn", "residual_fn"}
        self.index_probe = None
        self._steps: Dict[str, object] = {}
        # device join engine (core/join/): attached by the planner for
        # eligible stream-stream shapes; None keeps the legacy probe path
        self.engine = None
        self.engine_reason: Optional[str] = _reason(
            _RC.NOT_ATTACHED, "engine not attached")
        self.pipeline_reason: Optional[str] = _reason(
            _RC.NOT_ATTACHED, "engine not attached")
        # does anything read the joined EXPIRED rows (an expired-events
        # output, an aggregate that retracts)? The planner says; False
        # lets the device engine probe with CURRENT rows alone
        self.expired_read = True
        self._in_timer = False       # timer sweeps run synchronously
        self._drain_seq = None       # last cross-stream seq seen at drain
        self._cur_timer_cb = None    # per-side notify attribution (pump)
        # stable per-side timer callbacks so the scheduler's
        # (id(target), ts) dedup holds across batches
        self._timer_cbs = {
            k: (lambda ts, sk=k: self._timer(sk, ts)) for k in ("left", "right")
        }

    def make_proxies(self) -> Dict[str, JoinSideProxy]:
        # store sides produce no events — no proxy; named-window sides get
        # one (subscribed to the window's emission junction). The proxies
        # are retained: fan-out fusion subscribes THEM as group members
        # (fanout_plan), and the seq check consults their group state.
        self._proxies = {
            k: JoinSideProxy(self, k)
            for k in ("left", "right")
            if self.sides[k].window_stage is not None
        }
        return self._proxies

    def _init_state(self) -> dict:
        state = {"sel": self.selector_plan.init_state()}
        partitioned = self.partition_ctx is not None
        for k, wk in (("left", "lwin"), ("right", "rwin")):
            side = self.sides[k]
            if side.window_stage is not None and side.host_window is None:
                state[wk] = (side.window_stage.init_state(self._win_keys)
                             if partitioned else side.window_stage.init_state())
        if self.engine is not None:
            state.update(self.engine.init_state())
        return state

    def strip_engine_state(self, state):
        """Snapshot canonicalization: the cross-stream sequence is engine
        state — captures store only the legacy ``[W]`` ring layout, so
        revisions cross-restore between the device engine and the legacy
        path bit-identically."""
        if state is None or self.engine is None:
            return state
        from siddhi_tpu.core.join import ENGINE_STATE_KEYS

        return {k: v for k, v in state.items()
                if k not in ENGINE_STATE_KEYS}

    def adopt_restored_state(self):
        """Snapshot-restore hook: the restored state is canonical (no
        engine keys) — rebuild the engine's row mirrors from the rings
        and reset the drain-sequence expectation."""
        self._drain_seq = None
        if self._state is None:
            return
        self._adopt_ring_capacities()
        if self.engine is None:
            return
        from siddhi_tpu.core.join import SEQ_KEY

        state = dict(self._state)
        if SEQ_KEY not in state:
            import jax.numpy as _jnp

            state[SEQ_KEY] = _jnp.int64(0)
        self._state = state
        self.engine.rebuild_probe_state()

    def _adopt_ring_capacities(self) -> None:
        """A restored time-window ring keeps the size it was captured at
        (the device engine grows rings with their content): the stage
        follows its leaves, and the side steps re-jit."""
        from siddhi_tpu.ops.windows import TimeWindowStage

        for side_key, wk in (("left", "lwin"), ("right", "rwin")):
            stage = self.sides[side_key].window_stage
            win = (self._state or {}).get(wk)
            if isinstance(stage, TimeWindowStage) and win is not None:
                size = int(next(iter(win["buf"].values())).shape[0])
                if size != stage.capacity:
                    stage.capacity = size
                    self._steps.clear()

    def _seq_check(self, seq: int) -> None:
        """Drain-side verification of the engine's explicit cross-stream
        sequence: the pump's per-owner FIFO must hand batches back in
        dispatch order — a gap means an ordering bug, which must be loud
        (the outputs would silently interleave wrong). Skipped when a
        side rides a fused fan-out group: its seqs drain through the
        GROUP's entries, so this runtime's own FIFO legitimately sees
        gaps (cross-owner order was never promised)."""
        if any(getattr(p, "_fanout_group", None) is not None
               for p in getattr(self, "_proxies", {}).values()):
            self._drain_seq = None
            return
        exp = self._drain_seq
        self._drain_seq = seq
        if exp is not None and seq != exp + 1:
            _LOG.error(
                "query '%s': join drain sequence break (expected %d, "
                "got %d) — cross-stream emission order violated",
                self.name, exp + 1, seq)
            tel = getattr(self.app_context, "telemetry", None)
            if tel is not None:
                tel.count("join.seq_breaks")

    def _ensure_capacity(self):
        before = (self.selector_plan.num_keys, self._win_keys)
        super()._ensure_capacity()
        if (self.selector_plan.num_keys, self._win_keys) != before:
            self._steps.clear()

    def overflow_knob_msg(self, code: Optional[int] = None) -> str:
        """Join overflow naming the exact knob per the
        ``QueryRuntime.overflow_knob_msg`` convention. ``code`` is the
        step's overflow bitmask: 1 = window ring capacity, 2 = indexed
        probe candidate window, 8 = selector value table
        (distinctCount), 16 = the keyed probe's output width, 32 = a
        growing time-window ring."""
        if code is None:
            code = 1
        code = int(code)
        parts = []
        if code & 1:
            knob = ("app_context.partition_window_capacity"
                    if self.partition_ctx is not None
                    else "app_context.window_capacity")
            parts.append(f"join window capacity exceeded — raise {knob}")
        if code & 2:
            parts.append("indexed join probe candidate window saturated — "
                         "raise app_context.index_probe_width")
        if code & 8:
            parts.append("join selector aggregation overflow — raise "
                         "app_context.distinct_values_capacity")
        if code & 16:
            parts.append("join probe output narrower than the batch's "
                         "pairs: the host's count of the probed window "
                         "was short (an engine bug; no knob)")
        if code & 32:
            parts.append("join time-window ring overflowed although it "
                         "grows with its content: the host's count of its "
                         "live rows was short (an engine bug; no knob)")
        if not parts:
            parts.append("join window capacity exceeded — raise "
                         "app_context.window_capacity")
        return "; ".join(parts)

    def _step_instrument_slots(self):
        """Spec of the engine side step's meta suffix (must mirror
        ``DeviceJoinEngine.build_side_step`` exactly): the structural
        cross-stream sequence, then — instruments on — the pairs the
        step matched. Routed
        (mesh-sharded) joins run the LEGACY side step (engine None),
        whose meta carries no inner suffix; their route slots come from
        the base ``instrument_slots``."""
        from siddhi_tpu.observability.instruments import Slot

        if self.engine is None:
            return []
        slots = [Slot("seq", kind="check")]
        if self._instruments_on():
            slots.append(Slot("join_matches"))
        return slots

    def _record_instrument(self, slot, vals, telemetry: bool,
                           jr=None) -> None:
        """The pairs a step matched land on the batch's journey
        (``join_matches``) and in the counter ``join.<query>.matches``;
        then as any lane."""
        if slot.name == "join_matches":
            self.app_context.telemetry.count(f"join.{self.name}.matches",
                                             int(vals[0]))
            if jr is not None:
                jr.join_matches = int(vals[0])
        super()._record_instrument(slot, vals, telemetry, jr)

    def _consume_check_slot(self, name, vals) -> None:
        if name == "seq":
            self._seq_check(int(vals[0]))
            return
        super()._consume_check_slot(name, vals)

    def build_side_step_fn(self, side_key: str):
        if self.engine is not None:
            return self.engine.build_side_step(side_key)
        side = self.sides[side_key]
        other = self.sides["right" if side_key == "left" else "left"]
        win_key = "lwin" if side_key == "left" else "rwin"
        other_key = "rwin" if side_key == "left" else "lwin"
        sel = self.selector_plan
        on_cond = self.on_cond
        # host-window sides run their transforms + filters + window host-side
        host_pre = side.host_window is not None
        filters = [] if host_pre else side.filters
        transforms = [] if host_pre else side.transforms
        partitioned = self.partition_ctx is not None
        split = self.keyer is not None
        other_external = other.probe_external
        # indexed probe: only when THIS side triggers against the indexed
        # store side (the store never triggers)
        iprobe = self.index_probe
        use_index = (iprobe is not None and side.triggers
                     and iprobe["store_side"] == other.key
                     and other.store is not None and not partitioned)
        probe_width = int(getattr(self.app_context, "index_probe_width", 64))

        def step(state, probe_cols, probe_valid, cols, current_time):
            from siddhi_tpu.core.plan.selector_plan import STR_RANK

            ctx = {"xp": jnp, "current_time": current_time}
            cols = dict(cols)
            # the rank table rides to the SELECTOR only — window stages
            # must not see the non-row-shaped extra column
            strrank = cols.pop(STR_RANK, None)
            for t in transforms:
                cols = t.apply(cols, ctx)
            valid = cols[VALID_KEY]
            timer = cols[TYPE_KEY] == TIMER
            for f in filters:
                valid = valid & (f(cols, ctx) | timer)
            cols[VALID_KEY] = valid
            new_state = dict(state)
            with jax.named_scope(instruments.STATE_SCOPE):
                new_win, wout = side.window_stage.apply(
                    state.get(win_key),
                    conform_cols(side.window_stage, cols), ctx)
            if win_key in state:
                new_state[win_key] = new_win
            wout = dict(wout)
            notify = wout.pop("__notify__", None)
            overflow = wout.pop("__overflow__", None)
            wout.pop("__flush__", None)
            # device-routed dispatch: the keyed window emits a global
            # emission-order key per trigger row (RIDX-derived); the join
            # carries it to the joined rows below for the cross-shard
            # ordered re-merge
            okey_w = wout.pop(OKEY_KEY, None)
            # post-window filters mask emitted rows (probe/trigger side
            # only — the window's retained contents are unaffected)
            pvalid = wout[VALID_KEY]
            ptimer = wout[TYPE_KEY] == TIMER
            for f in side.post_filters:
                pvalid = pvalid & (f(wout, ctx) | ptimer)
            wout[VALID_KEY] = pvalid

            with jax.named_scope(instruments.STATE_SCOPE):   # the probe
                N = wout[VALID_KEY].shape[0]
                if not other_external:
                    # unpartitioned: the window oldest first, so a trigger
                    # meets its matches in their arrival order (a
                    # partitioned ring is read so below, key by key)
                    contents = other.window_stage.contents
                    if not partitioned:
                        contents = getattr(other.window_stage,
                                           "arrival_contents", contents)
                    probe_cols, probe_valid = contents(state[other_key])

                # joined eval dict: this side [N,1]; other side [1,W]
                # (or, partitioned, this row's key's ring gathered to [N,W];
                # or, INDEXED, per-row candidate windows gathered to [N,G])
                ev: Dict[str, jnp.ndarray] = {}
                idx_overflow = None
                if use_index:
                    # sort the probe column once (invalid/null rows to the
                    # end), then per-event searchsorted gives a contiguous
                    # candidate range — O(W log W + N log W + N*G) instead of
                    # the O(N*W) broadcast compare, and the join materializes
                    # [N, G+1] instead of [N, W+1]
                    attr = iprobe["attr"]
                    ev0 = {TS_KEY: wout[TS_KEY][:, None]}
                    for a in side.definition.attributes:
                        ev0[side.prefix + a.name] = wout[a.name][:, None]
                        ev0[side.prefix + a.name + "?"] = wout[a.name + "?"][:, None]
                    v, vmask = iprobe["val_fn"](ev0, ctx)
                    pvals = probe_cols[attr]
                    pnull = probe_cols.get(attr + "?")
                    ok = probe_valid
                    if pnull is not None:
                        ok = ok & ~pnull
                    if jnp.issubdtype(pvals.dtype, jnp.floating):
                        big = jnp.asarray(jnp.inf, pvals.dtype)
                    else:
                        big = jnp.asarray(jnp.iinfo(pvals.dtype).max, pvals.dtype)
                    sortkey = jnp.where(ok, pvals, big)
                    order = jnp.argsort(sortkey)
                    sk = sortkey[order]
                    Wfull = sk.shape[0]
                    vv = jnp.broadcast_to(jnp.asarray(v), (N, 1))[:, 0] \
                        .astype(pvals.dtype)
                    lo = jnp.searchsorted(sk, vv, side="left")
                    hi = jnp.searchsorted(sk, vv, side="right")
                    G = min(probe_width, Wfull)
                    grid = lo[:, None] + jnp.arange(G)[None, :]
                    cmask = grid < hi[:, None]
                    if vmask is not None:
                        cmask = cmask & ~jnp.broadcast_to(
                            jnp.asarray(vmask), (N, 1))
                    idx_overflow = jnp.any((hi - lo) > G).astype(jnp.int32)
                    cand = order[jnp.clip(grid, 0, Wfull - 1)]        # [N, G]
                    W = G
                    for a in other.definition.attributes:
                        ev[other.prefix + a.name] = probe_cols[a.name][cand]
                        ev[other.prefix + a.name + "?"] = \
                            probe_cols[a.name + "?"][cand]
                    # belt-and-braces equality re-check on the gathered rows:
                    # guards the dtype-max/inf sentinel (a probe value equal
                    # to it would otherwise sweep deleted/null rows in) and
                    # any residual dtype edge case
                    pv = (cmask & ok[cand]
                          & (pvals[cand] == vv[:, None]))
                elif partitioned and not other_external:
                    pk_rows = jnp.clip(wout[PK_KEY].astype(jnp.int32), 0,
                                       probe_valid.shape[0] - 1)
                    probe_cols = {a: v[pk_rows] for a, v in probe_cols.items()}
                    probe_valid = probe_valid[pk_rows]          # [N, W]
                    W = probe_valid.shape[1]
                    start = getattr(other.window_stage, "arrival_start", None)
                    if start is not None:
                        # each row's key ring read oldest first, so a
                        # trigger meets its matches in their arrival order
                        at = ((start(state[other_key])[pk_rows][:, None]
                               + jnp.arange(W)) % W).astype(jnp.int32)
                        probe_cols = {a: jnp.take_along_axis(v, at, axis=1)
                                      for a, v in probe_cols.items()}
                        probe_valid = jnp.take_along_axis(probe_valid, at,
                                                          axis=1)
                    for a in other.definition.attributes:
                        ev[other.prefix + a.name] = probe_cols[a.name]
                        ev[other.prefix + a.name + "?"] = probe_cols[a.name + "?"]
                    pv = probe_valid
                else:
                    W = probe_valid.shape[0]
                    for a in other.definition.attributes:
                        ev[other.prefix + a.name] = probe_cols[a.name][None, :]
                        ev[other.prefix + a.name + "?"] = probe_cols[a.name + "?"][None, :]
                    pv = probe_valid[None, :]
                for a in side.definition.attributes:
                    ev[side.prefix + a.name] = wout[a.name][:, None]
                    ev[side.prefix + a.name + "?"] = wout[a.name + "?"][:, None]
                ev[TS_KEY] = wout[TS_KEY][:, None]

                row_live = wout[VALID_KEY] & ((wout[TYPE_KEY] == CURRENT) | (wout[TYPE_KEY] == EXPIRED))
                if use_index:
                    # the probed equality holds by construction; only the
                    # residual conjuncts (if any) still need evaluating
                    rfn = iprobe["residual_fn"]
                    cond = rfn(ev, ctx) if rfn is not None else jnp.ones((N, W), bool)
                    cond = jnp.broadcast_to(cond, (N, W))
                    match = row_live[:, None] & jnp.broadcast_to(pv, (N, W)) & cond
                elif side.triggers:
                    cond = on_cond(ev, ctx) if on_cond is not None else jnp.ones((N, W), bool)
                    cond = jnp.broadcast_to(cond, (N, W))
                    match = row_live[:, None] & jnp.broadcast_to(pv, (N, W)) & cond
                else:
                    match = jnp.zeros((N, W), bool)

                # column W carries the one-sided row: outer no-match + RESET
                no_match = row_live & ~jnp.any(match, axis=1) & side.outer & side.triggers
                one_sided = no_match | (wout[VALID_KEY] & (wout[TYPE_KEY] == RESET))

                NW = N * (W + 1)
                joined: Dict[str, jnp.ndarray] = {}
                for a in side.definition.attributes:
                    v = jnp.broadcast_to(wout[a.name][:, None], (N, W + 1))
                    mk = jnp.broadcast_to(wout[a.name + "?"][:, None], (N, W + 1))
                    joined[side.prefix + a.name] = v.reshape(NW)
                    joined[side.prefix + a.name + "?"] = mk.reshape(NW)
                for a in other.definition.attributes:
                    pc = ev[other.prefix + a.name]
                    pm = ev[other.prefix + a.name + "?"]
                    v = jnp.concatenate(
                        [jnp.broadcast_to(pc, (N, W)),
                         jnp.zeros((N, 1), pc.dtype)], axis=1)
                    mk = jnp.concatenate(
                        [jnp.broadcast_to(pm, (N, W)),
                         jnp.ones((N, 1), bool)], axis=1)
                    joined[other.prefix + a.name] = v.reshape(NW)
                    joined[other.prefix + a.name + "?"] = mk.reshape(NW)
                joined[VALID_KEY] = jnp.concatenate(
                    [match, one_sided[:, None]], axis=1).reshape(NW)
                joined[TS_KEY] = jnp.repeat(wout[TS_KEY], W + 1)
                joined[TYPE_KEY] = jnp.repeat(wout[TYPE_KEY], W + 1)
                if partitioned:
                    pk_out = jnp.repeat(wout[PK_KEY].astype(jnp.int32), W + 1)
                    joined[PK_KEY] = pk_out
                    joined[GK_KEY] = pk_out
                else:
                    joined[GK_KEY] = jnp.zeros(NW, jnp.int32)
                # one reference chunk per trigger event (JoinProcessor.execute):
                # the selector's batch collapse keys on (trigger row, group)
                joined[FLUSH_KEY] = jnp.repeat(
                    jnp.arange(N, dtype=jnp.int32), W + 1)
                if okey_w is not None:
                    # joined emission-order key: trigger okey stridden by the
                    # probe width reproduces the legacy [N, W+1] row-major
                    # order ACROSS shards (one-sided rows at column W); the
                    # invalid-row _BIG sentinel is zeroed before the multiply
                    # (the route wrapper re-masks invalid rows itself)
                    okw = jnp.asarray(okey_w, jnp.int64)
                    okw = jnp.where(okw >= jnp.int64(2 ** 61), jnp.int64(0), okw)
                    joined[OKEY_KEY] = (
                        okw[:, None] * jnp.int64(W + 1)
                        + jnp.arange(W + 1, dtype=jnp.int64)[None, :]
                    ).reshape(NW)

            if idx_overflow is not None:
                # candidate window saturated: surfacing it beats silently
                # dropping matches. Bit 2 of the overflow mask — the host
                # decodes it to app_context.index_probe_width, distinct
                # from the window-capacity knob (overflow_knob_msg)
                base = (jnp.int32(0) if overflow is None else jnp.where(
                    jnp.asarray(overflow).astype(jnp.int32) > 0, 1, 0))
                overflow = base | (idx_overflow * 2)

            if strrank is not None:   # string order-by: rank table -> selector
                joined[STR_RANK] = strrank

            if split:
                # host keyer computes GK from joined columns; the selector
                # runs as a separate jitted step (_host_keyed_select)
                if notify is not None:
                    joined["__notify__"] = notify
                if overflow is not None:
                    joined["__overflow__"] = overflow
                with jax.named_scope(instruments.META_SCOPE):
                    return new_state, pack_meta(joined)

            with jax.named_scope(instruments.SELECT_SCOPE):
                new_state["sel"], out = sel.apply(state["sel"], joined, ctx)
            if notify is not None:
                out["__notify__"] = notify
            if overflow is not None:
                out["__overflow__"] = overflow
            with jax.named_scope(instruments.META_SCOPE):
                return new_state, pack_meta(out)

        return step

    def build_step_fn(self):
        key = "left" if self.sides["left"].window_stage is not None else "right"
        return self.build_side_step_fn(key)

    def process_side_batch(self, side_key: str, batch: HostBatch):
        import time as _time

        from siddhi_tpu.core.stream.junction import \
            current_delivering_junction
        from siddhi_tpu.observability.tracing import span

        t_host0 = _time.perf_counter()
        with span("query.step", batch=journey.batch_of(batch),
                  query=self.name, side=side_key), self._lock:
            # pipelined completions need the delivering junction (error
            # attribution + latency feedback) and the SIDE's own timer
            # callback (per-side notify attribution at drain)
            j = current_delivering_junction()
            self._cur_junction = j
            self._cur_fault_batch = batch if (
                j is not None and j.on_error_action == "STREAM"
                and j.fault_junction is not None) else None
            self._cur_timer_cb = self._timer_cbs[side_key]
            # batch-journey (PR-11 coverage gap): join side batches get
            # the same stage attribution as single-stream ones — the
            # shared _finish_device_batch tail consumes the context.
            # The split (host-keyed) tail is synchronous and does not
            # thread the journey, so grouped joins skip the allocation.
            jr = self._batch_journey = self._cur_journey = \
                journey.begin(batch) \
                if journey.enabled() and self.keyer is None else None
            if jr is not None and self._in_timer:
                jr.timer_steps = 1
            side = self.sides[side_key]
            cols = batch.cols
            partitioned = self.partition_ctx is not None
            notify_host = None
            with journey.keying(jr, self.name, batch.capacity,
                                self._needed_sel_keys):
                if partitioned:
                    if side.keyer is not None:
                        cols, pk = side.keyer.apply(cols)
                        batch = HostBatch(cols)
                        cols[PK_KEY] = np.asarray(pk, np.int32)
                    elif side.global_side:
                        # non-partitioned stream inside a partition: the
                        # reference hands the event to every EXISTING
                        # instance (each holds its own window copy), so
                        # broadcast each row across the key axis, valid
                        # only for keys active at arrival — a later-created
                        # instance must NOT see earlier global events
                        # (JoinPartitionTestCase test10). _ensure_capacity
                        # runs before K is read so growth precedes the
                        # tile.
                        self._ensure_capacity()
                        n_active = self.partition_ctx.active_keys()
                        K = self._win_keys
                        B = batch.capacity
                        rep = {}
                        for name, v in cols.items():
                            rep[name] = np.repeat(np.asarray(v), K, axis=0)
                        pk_tile = np.tile(np.arange(K, dtype=np.int32), B)
                        rep[PK_KEY] = pk_tile
                        rep[VALID_KEY] = rep[VALID_KEY] & (
                            pk_tile < n_active)
                        cols = rep
                        batch = HostBatch(cols)
                    elif PK_KEY not in cols:
                        cols[PK_KEY] = np.zeros(batch.capacity, np.int32)
                    if not side.global_side:   # global branch ensured already
                        self._ensure_capacity()
            if side.host_window is not None:
                now_h = int(self.app_context.timestamp_generator.current_time())
                hctx = {"xp": np, "current_time": now_h}
                for t in side.transforms:
                    cols = t.apply(cols, hctx)
                valid = cols[VALID_KEY]
                timer = cols[TYPE_KEY] == TIMER
                for f in side.filters:
                    valid = valid & (np.asarray(f(cols, hctx)) | timer)
                cols[VALID_KEY] = valid
                batch = HostBatch(cols)
                batch, notify_host = side.host_window.process(batch, now_h)
                cols = batch.cols
            cols[GK_KEY] = np.zeros(batch.capacity, np.int32)
            if self._state is None:
                self._state = self._init_state()
            if self.engine is not None:
                # adaptive sub-window capacity: mirror this batch's ring
                # occupancy and grow the partition directory BEFORE the
                # step could overflow it (clears _steps when it grows)
                self.engine.prepare_batch(side_key, cols)
            routed = self._route_layout is not None
            jitted = self._steps.get(side_key)
            if jitted is None:
                if routed:
                    # mesh-sharded partitioned join: the side step runs
                    # inside the device-router's shard_map (exchange by
                    # pk, partition-local probe, okey re-merge)
                    from siddhi_tpu.parallel.mesh import routed_step_for

                    jitted = routed_step_for(self, side_key=side_key)
                else:
                    fn = self.build_side_step_fn(side_key)
                    if self.engine is not None:
                        fn = _inert_probe(fn)
                    jitted = self.app_context.telemetry.instrument_jit(
                        jax.jit(instruments.named_step(
                            fn, f"device_join.{side_key}"), donate_argnums=0),
                        f"query.{self.name}.join.{side_key}",
                        family=f"device_join.{side_key}")
                self._steps[side_key] = jitted
            else:
                self.app_context.telemetry.record_jit(
                    getattr(jitted, "_key",
                            f"query.{self.name}.join.{side_key}"), hit=True)
            other = self.sides["right" if side_key == "left" else "left"]
            # callable: the step's overflow bitmask decodes to the exact
            # knob (window / index-probe / partition sub-window / selector)
            _ovf_msg = self.overflow_knob_msg
            tel = self.app_context.telemetry
            tel.histogram(f"join.insert_ms.{self.name}").record(
                (_time.perf_counter() - t_host0) * 1000.0)
            t_probe0 = _time.perf_counter()
            if (other.store is not None
                    and getattr(other.store, "dynamic", None) is not None):
                # per-event within/per: group trigger rows by their resolved
                # (duration, within) and probe each group's stitched surface
                now_h = int(self.app_context.timestamp_generator.current_time())
                groups = other.store.resolve_groups(
                    cols, {"xp": np, "current_time": now_h})
                notify = None
                base_valid = np.asarray(cols[VALID_KEY])
                saved = (other.store.duration, other.store.within)
                try:
                    for mask, dur, win in groups:
                        other.store.duration = dur
                        other.store.within = win
                        try:
                            probe_cols, probe_valid = other.store.contents()
                        except CompileError as e:
                            _LOG.error("query '%s': %s — dropping trigger "
                                       "events", self.name, e)
                            continue
                        sub = dict(cols)
                        sub[VALID_KEY] = base_valid & mask

                        def call(st, c, now, _pc=probe_cols, _pv=probe_valid):
                            return jitted(st, _pc, _pv, c, now)

                        call.bound = (probe_cols, probe_valid)
                        n = self._finish_device_batch(call, sub, _ovf_msg)
                        if n is not None:
                            notify = n if notify is None else min(notify, n)
                finally:
                    # leave the planner-assigned static view on the shared
                    # store — the per-event values must not outlive the batch
                    other.store.duration, other.store.within = saved
            else:
                probe_ok = True
                if other.store is not None:
                    try:
                        probe_cols, probe_valid = other.store.contents()
                    except CompileError as e:
                        # e.g. `per "days"` against a sec...hour aggregation:
                        # the reference logs at the stream processor and
                        # drops the event (Aggregation1TestCase test22) —
                        # notify_host below must still be honored
                        _LOG.error("query '%s': %s — dropping trigger "
                                   "events", self.name, e)
                        probe_ok = False
                elif other.host_window is not None:
                    probe_cols, probe_valid = other.host_window.contents()
                else:  # placeholders; the step reads its own state instead
                    probe_cols, probe_valid = {}, jnp.zeros((1,), bool)

                notify = None
                if probe_ok:
                    if routed:
                        # pad/precheck host-side, splitting oversized
                        # batches, then run each piece through the routed
                        # side step in order (mirrors process_batch)
                        from siddhi_tpu.parallel.mesh import \
                            prepare_routed_batches

                        for piece in prepare_routed_batches(self, cols):
                            nt = self._finish_device_batch(
                                jitted, piece, _ovf_msg)
                            if nt is not None:
                                notify = (nt if notify is None
                                          else min(notify, nt))
                    elif self.engine is not None:
                        notify = self._finish_device_batch(
                            jitted, cols, _ovf_msg)
                    else:
                        def call(st, cols, now):
                            return jitted(st, probe_cols, probe_valid,
                                          cols, now)

                        call.bound = (probe_cols, probe_valid)
                        notify = self._finish_device_batch(
                            call, cols, _ovf_msg)
            tel.histogram(f"join.probe_ms.{self.name}").record(
                (_time.perf_counter() - t_probe0) * 1000.0)
        if notify_host is not None:
            notify = notify_host if notify is None else min(notify, notify_host)
        if notify is not None and self.scheduler is not None:
            self.scheduler.notify_at(notify, self._timer_cbs[side_key])

    @property
    def _defer_ok(self) -> bool:
        # per-side scheduler windows need their __notify__ promptly, and
        # notify values are per SIDE — never defer join metas
        return False

    @property
    def _pipeline_ok(self) -> bool:
        # Eligible joins ride the CompletionPump (core/join/ decides —
        # ``pipeline_reason`` is None when both probe surfaces live
        # inside the jitted state): probe-vs-insert coupling is resolved
        # at DISPATCH (state updates happen synchronously under the
        # runtime lock; only the meta pull + emission ride), both sides
        # share one owner FIFO so cross-stream emission order equals
        # dispatch order (the engine's explicit sequence number verifies
        # it at drain), and the per-side __notify__ is attributed to the
        # side's own timer callback captured on the entry. Timer sweeps
        # stay synchronous (flush-then-run, like process_timer).
        return self.pipeline_reason is None and not self._in_timer

    def _finish_device_batch(self, step, cols, overflow_msg):
        if self.keyer is None:
            return super()._finish_device_batch(step, cols, overflow_msg)
        # split (host-keyed) path: synchronous by construction; the
        # journey context is not threaded through the two-stage tail
        self._cur_journey = None
        from siddhi_tpu.core.util.statistics import latency_t0, record_elapsed_ms

        sm = self.app_context.statistics_manager
        t0 = latency_t0(sm)
        now = np.int64(self.app_context.timestamp_generator.current_time())
        if self.selector_plan.needs_str_rank:
            from siddhi_tpu.core.plan.selector_plan import STR_RANK

            cols[STR_RANK] = self.dictionary.rank_table()
        self._state, out = launch_step(step, self._state, cols, now,
                                       query=self.name)
        out_host = LazyColumns(out)
        meta = out_host.pop("__meta__", None)
        if meta is not None:
            meta = np.asarray(meta)
            overflow, notify = int(meta[0]), int(meta[1])
            self.decode_meta_suffix(meta)
        else:
            ovf = out_host.pop("__overflow__", None)
            overflow = int(ovf) if ovf is not None else 0
            nt = out_host.pop("__notify__", None)
            notify = int(nt) if nt is not None else -1
        if overflow > 0:
            msg = (overflow_msg(overflow) if callable(overflow_msg)
                   else overflow_msg)
            raise FatalQueryError(f"query '{self.name}': {msg}")
        record_elapsed_ms(sm, self.name, t0)
        out_host = self._host_keyed_select(out_host)
        self._emit(HostBatch(out_host))
        if notify >= 0:
            return notify
        return None

    def _timer(self, side_key: str, ts: int):
        side = self.sides[side_key]
        from siddhi_tpu.core.event import TIMER as TIMER_TYPE
        from siddhi_tpu.core.query.runtime import _zero_value

        batch = HostBatch.from_events(
            [Event(timestamp=int(ts),
                   data=[_zero_value(a.type) for a in side.pack_definition.attributes])],
            side.pack_definition,
            self.dictionary,
        )
        batch.cols[TYPE_KEY][...] = TIMER_TYPE
        # fired by a send's clock advance (playback), the sweep is that
        # send's work: its journey takes the send's batch id
        self.app_context.telemetry.count(f"window.{self.name}.timer_steps")
        sender = journey.sending_batch()
        if sender is not None and getattr(batch, "journey", None) is not None:
            batch.journey.batch = sender
        # timer sweeps run synchronously over a drained timeline, exactly
        # like process_timer: in-flight pipelined batches were dispatched
        # BEFORE this timer fired, and the sweep's own notify must re-arm
        # promptly (no producer will drain it later)
        with self._lock:
            pump = getattr(self.app_context, "completion_pump", None)
            if pump is not None and pump.has_pending:
                pump.flush_owner(self)
            self._in_timer = True
            try:
                self.process_side_batch(side_key, batch)
            finally:
                self._in_timer = False

    def receive(self, events: List[Event]):  # pragma: no cover — proxies only
        raise RuntimeError("join queries receive through per-side proxies")
