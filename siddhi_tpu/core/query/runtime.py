"""QueryRuntime: host driver for one compiled query.

The counterpart of the reference's receiver->processor-chain->selector
->rate-limiter->callback assembly (``QueryParser.java:90-283``,
``ProcessStreamReceiver.java:74-184``), inverted for TPU: the junction hands
the runtime a chunk of events, the runtime packs them into a padded columnar
batch, computes group-key ids host-side (dense dictionary — the analog of
``GroupByKeyGenerator.java:37`` string keys), runs the jitted device step
(filters + windows + selector fused by XLA), and decodes valid output rows
back to Events for rate limiting and callbacks.
"""

from __future__ import annotations

import logging
import threading
import uuid
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.analysis.locks import make_lock
from siddhi_tpu.core.event import CURRENT, EXPIRED, TIMER as TIMER_TYPE, Event, HostBatch, LazyColumns, StringDictionary, launch_step, pack_pool_of
from siddhi_tpu.observability import instruments, journey
from siddhi_tpu.observability.tracing import span
from siddhi_tpu.observability.instruments import Slot
from siddhi_tpu.core.plan.selector_plan import GK_KEY, SelectorPlan
from siddhi_tpu.core.query.ratelimit import OutputRateLimiter
from siddhi_tpu.core.stream.junction import FatalQueryError, Receiver, StreamJunction
from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY
from siddhi_tpu.ops.windows import conform_cols
from siddhi_tpu.query_api.definitions import AttrType, StreamDefinition

_LOG = logging.getLogger("siddhi_tpu.query.runtime")


class GroupKeyer:
    """Host-side (group-by or partition) key dictionary: maps tuples of
    key-expression values to dense ids used to index ``[K, ...]`` state."""

    def __init__(self, fns: List[Tuple[Callable, AttrType]]):
        self._fns = fns
        self._map: Dict[tuple, int] = {}
        self._next = 0   # ids are NEVER reused (purged entries leave holes)
        # fast path: single string attribute -> LUT from dict id to key id
        self._single_string = len(fns) == 1 and fns[0][1] == AttrType.STRING
        self._lut = np.full(64, -1, np.int32)

    def _alloc(self, key: tuple) -> int:
        i = self._map.get(key)
        if i is None:
            i = self._map[key] = self._next
            self._next += 1
        return i

    def __len__(self):
        # dense capacity: holes from purged entries still occupy the range
        return self._next

    def __call__(self, cols: Dict[str, np.ndarray], pk: Optional[np.ndarray] = None) -> np.ndarray:
        """Group ids for a batch; when ``pk`` is given the dictionary key is
        (partition key, group-by values) — reference state addressing is
        ``[partitionFlowId][groupByFlowId]`` (PartitionStateHolder.java:43-48)."""
        ctx = {"xp": np}
        valid = cols[VALID_KEY]
        B = valid.shape[0]
        gk = np.zeros(B, np.int32)
        if pk is None and self._single_string:
            v, m = self._fns[0][0](cols, ctx)
            # LUT slots are dict ids shifted +1: slot 0 is the NULL group
            # (the reference's "null" string key, GroupByKeyGenerator
            # String.valueOf) — a null-masked key must not share the group
            # of whatever string holds the 0 placeholder, and the shift
            # also keeps NULL_ID(-1) from wrapping to lut[-1]
            ids = np.asarray(v, np.int64) + 1
            if m is not None:
                m = np.asarray(m, bool)
                if m.any():
                    ids = np.where(m, 0, ids)
            lut = self._lut
            if ids.size and ids.max() >= lut.shape[0]:
                top = int(ids.max()) + 1
                grown = np.full(max(top, 2 * lut.shape[0]), -1, np.int32)
                grown[: lut.shape[0]] = lut
                self._lut = lut = grown
            np.take(lut, ids, out=gk)
            # steady state: every dict id already has a key id — one take +
            # one reduction, no per-batch sort (np.unique costs ~5 ms at
            # 65k rows). Misses (NEW dict ids) take the unique path once.
            missed = (gk < 0) & valid
            if missed.any():
                for sid in np.unique(ids[missed]):
                    if lut[sid] < 0:
                        lut[sid] = self._alloc((int(sid) - 1,))
                np.take(lut, ids, out=gk)
            gk[~valid] = 0
            return gk
        # general path: vectorized dictionary encoding (shared helper —
        # unique the key tuples once per batch, probe the dict per NEW
        # unique only)
        from siddhi_tpu.core.event import encode_key_tuples

        arrays = []
        if pk is not None:
            arrays.append(np.asarray(pk))
        for fn, _t in self._fns:
            v, m = fn(cols, ctx)
            arrays.append(np.broadcast_to(np.asarray(v), (B,)))
            # the null mask joins the key tuple: a null key (placeholder
            # value 0) must form its own group, distinct from a real 0 /
            # the dict-id-0 string (reference nulls key as "null")
            arrays.append(np.zeros(B, bool) if m is None
                          else np.broadcast_to(np.asarray(m, bool), (B,)))
        vidx = np.nonzero(valid)[0]
        if vidx.size == 0:
            return gk
        gk[vidx] = encode_key_tuples(arrays, vidx, self._alloc)
        return gk


class QueryRuntime(Receiver):
    def __init__(
        self,
        name: str,
        app_context,
        input_definition: StreamDefinition,
        filters: List[Callable],
        window_stage,               # ops stage or None (M2)
        selector_plan: SelectorPlan,
        keyer: Optional[GroupKeyer],
        dictionary: StringDictionary,
        partition_ctx=None,
        partition_keyer=None,
        carried_pk: bool = False,
        transforms=None,
        log_stages=None,
        post_filters=None,
        post_pipeline=None,
    ):
        self.name = name
        self.app_context = app_context
        self.input_definition = input_definition
        self.filters = filters
        self.transforms = transforms or []   # ops/stream_functions stages
        self.log_stages = log_stages or []   # host #log() taps
        self.post_filters = post_filters or []  # masks on window-emitted rows
        # ordered post-window stages ("f", cond) | ("t", transform); falls
        # back to post_filters when only filters exist
        self.post_pipeline = post_pipeline if post_pipeline is not None else [
            ("f", f) for f in (post_filters or [])]
        self.host_transforms = False         # run transforms host-side (keyer needs them)
        self.window_stage = window_stage
        self.selector_plan = selector_plan
        self.keyer = keyer
        self.dictionary = dictionary
        # partition support (reference partition/PartitionRuntimeImpl.java)
        self.partition_ctx = partition_ctx
        self.partition_keyer = partition_keyer
        self.carried_pk = carried_pk      # input is an inner '#stream': rows carry pk
        self.attach_pk = False            # output goes to an inner '#stream'
        self.limiter_needs_pk = False     # partitioned rate limiter routing
        self.limiter_needs_gk = False     # grouped limiter, key not projected
        self._win_keys = 1
        if partition_ctx is not None:
            self._win_keys = max(_pow2(partition_ctx.num_keys()), 16)
        self.host_window = None   # map/comparator windows (ops/host_windows)
        self.rate_limiter: Optional[OutputRateLimiter] = None
        self.query_callbacks: List = []
        self.output_junction: Optional[StreamJunction] = None
        self.output_action: Optional[Callable] = None  # table ops etc.
        self.scheduler = None  # set by the app runtime when timers are needed
        self._state: Optional[dict] = None
        self._step = None
        self._sel_step = None  # split pipelines (host keyer between stages)
        self._shard_mesh = None  # set by parallel.mesh.shard_query_step
        self._route_layout = None  # parallel.mesh.device_route_query_step
        self._lock = make_lock("owner")  # per-query lock (QueryParser.java:159-215)
        self._deferred: List = []   # queued outputs when defer_meta > 1
        self._cur_junction = None   # delivering junction of the batch in
        #                             process (completion-latency feedback)
        self._cur_fault_batch = None  # input batch retained for drain-time
        #                               fault-stream routing (@OnError)
        self._cur_journey = None    # batch-journey context of the batch in
        #                             process (observability/journey.py)
        self._batch_journey = None  # the same, kept for the whole batch:
        #                             every piece's launch is stamped on it
        # device-instrument plumbing (observability/instruments.py):
        # last drained raw lanes per slot (zero-pull scrape surface),
        # host-known capacity denominators, and the lazily-registered
        # device.<q>.<slot> gauge set
        self._instr_last: Dict[str, np.ndarray] = {}
        self._instr_caps: Dict[str, float] = {}
        self._instr_gauged: set = set()
        self._instr_spec = None     # cached instrument_slots() result
        self.on_error: Optional[Callable] = None
        # what the dense state holds, on /metrics beside
        # state.<query>.grows (note_growth); sampled from shapes, no pull
        tel = getattr(app_context, "telemetry", None)
        if tel is not None:
            tel.gauge(f"state.{name}.key_capacity", self.key_capacity)
            tel.gauge(f"state.{name}.bytes", self.state_bytes)

    # ---------------------------------------------------------------- state

    @property
    def output_attrs(self) -> List[Tuple[str, AttrType]]:
        return self.selector_plan.output_attrs

    def is_stateful(self) -> bool:
        """Does this query hold state a snapshot must capture? — a window,
        an aggregator/group-by, or a non-passthrough rate limiter
        (reference ``QueryRuntimeImpl.isStateful``, StateTestCase)."""
        from siddhi_tpu.core.query.ratelimit import PassThroughRateLimiter

        if (self.window_stage is not None
                or getattr(self, "host_window", None) is not None):
            return True
        if (self.selector_plan.contains_aggregator
                or self.selector_plan.group_by):
            return True
        rl = self.rate_limiter
        return rl is not None and not isinstance(rl, PassThroughRateLimiter)

    def _init_state(self) -> dict:
        state = {"sel": self.selector_plan.init_state()}
        if self.window_stage is not None:
            state["win"] = self.window_stage.init_state(self._win_keys)
        return state

    def key_capacity(self) -> int:
        """Keys the dense state has rows for (``state.<query>.key_capacity``):
        the larger of the selector's and the window's key axes, over
        every shard of a device-routed query."""
        rl = self._route_layout
        if rl is not None:
            return rl.n * max(rl.localK, rl.local_win)
        return max(self.selector_plan.num_keys, self._win_keys)

    def state_bytes(self) -> int:
        """Bytes of the query's state on the device
        (``state.<query>.bytes``), from the leaves' shapes: no pull."""
        from siddhi_tpu.core.util.statistics import pytree_nbytes

        return pytree_nbytes(self._state)

    def state_slots(self) -> Optional[int]:
        """Ring slots of a keyed window's state, key capacity x the ring
        each key owns; None where the query keeps no keyed ring."""
        ring = getattr(self.window_stage, "ring_capacity", None)
        if ring is None or not getattr(self.window_stage, "keyed", False):
            return None
        rl = self._route_layout
        keys = rl.n * rl.local_win if rl is not None else self._win_keys
        return keys * ring

    def note_growth(self, ms: Optional[float]) -> None:
        """One key-capacity growth happened, under a ``siddhi.grow`` span
        of ``ms``: counted, and charged to the batch that forced it."""
        self.app_context.telemetry.count(f"state.{self.name}.grows")
        if self._cur_journey is not None:
            self._cur_journey.grown(ms)

    def _needed_sel_keys(self) -> int:
        if self.keyer is not None:
            return max(len(self.keyer), 1)
        if self.partition_ctx is not None:
            return self.partition_ctx.num_keys()
        return 1

    def _ensure_capacity(self):
        """Grow dense key capacity (pow2) when a key dictionary outgrows
        it; state rows are preserved (keyed buffers are laid out so prefix
        copy keeps per-key alignment), step re-jitted on the new shapes."""
        if self._route_layout is not None:
            # device-routed runtimes hold PER-SHARD capacities: growth
            # compares the GLOBAL key population against n * localK and
            # re-lays the state out through its canonical form
            from siddhi_tpu.parallel.mesh import ensure_routed_capacity

            ensure_routed_capacity(self)
            return
        needed = self._needed_sel_keys()
        k = self.selector_plan.num_keys
        new_k = _pow2(needed, start=k) if needed > k else k
        new_w = self._win_keys
        if self.partition_ctx is not None:
            needed_w = self.partition_ctx.num_keys()
            if needed_w > self._win_keys:
                new_w = _pow2(needed_w, start=self._win_keys)
        if new_k == k and new_w == self._win_keys:
            return
        if (getattr(self.app_context, "overload", None) is not None
                and self._state is not None):
            # device-memory budget gate (resilience/overload.py): deny
            # the growth BEFORE allocating — dense state scales with the
            # grown key capacity, so project from the current footprint
            from siddhi_tpu.resilience.overload import ensure_memory_budget

            ratio = max(new_k / max(k, 1), new_w / max(self._win_keys, 1))
            ensure_memory_budget(
                self.app_context, f"query.{self.name}",
                int(self.state_bytes() * ratio),
                what=f"query '{self.name}' key-capacity growth "
                     f"({k}->{new_k} keys)")
        from_keys = self.key_capacity()
        self.selector_plan.num_keys = new_k
        self._win_keys = new_w
        self._sel_step = None
        if self._state is None:
            self._state = self._init_state()
        else:
            grown = jax.tree_util.tree_leaves(
                jax.eval_shape(self._init_state))
            with span("grow", query=self.name, from_keys=from_keys,
                      to_keys=self.key_capacity(),
                      bytes_before=self.state_bytes(),
                      bytes_after=sum(g.size * g.dtype.itemsize
                                      for g in grown)) as sp:
                # the runtime lets go of the old state before the first
                # leaf moves: grow_state owns its only reference
                old_leaves, treedef = jax.tree_util.tree_flatten(self._state)
                self._state = None
                try:
                    self._state = jax.tree_util.tree_unflatten(
                        treedef,
                        grow_state(self._init_state, grown, old_leaves))
                except Exception as e:
                    # leaves that had moved are gone with their old
                    # copies: nothing to fall back to
                    raise FatalQueryError(
                        f"query '{self.name}': key-capacity growth "
                        f"({from_keys}->{self.key_capacity()} keys) failed "
                        f"with its state half moved: {e}") from e
            self.note_growth(sp.ms)
        self._step = None  # re-jit
        if self._shard_mesh is not None:
            # re-establish key-axis sharding on the grown state
            from siddhi_tpu.parallel.mesh import shard_query_step

            shard_query_step(self, self._shard_mesh)
        if getattr(self.app_context, "overload", None) is not None:
            from siddhi_tpu.resilience.overload import charge_memory

            charge_memory(self.app_context, f"query.{self.name}",
                          self.state_bytes())

    def reset_partition_keys(self, ids):
        """Zero the dense state rows of purged partition keys so their ids
        can be reused by new keys (@purge — PartitionRuntimeImpl purge)."""
        if self.rate_limiter is not None and hasattr(
                self.rate_limiter, "reset_keys"):
            # per-key limiter instances of retired keys must not leak
            # their counters/pending into a recycled pk
            self.rate_limiter.reset_keys(ids)
        with self._lock:
            if self._state is None:
                return
            rl = self._route_layout
            ids_np = np.asarray(ids, np.int64)
            if rl is not None:
                # routed state is shard-major: global pk id g lives at row
                # (g % n) * local + g // n of each keyed buffer
                idx = jnp.asarray(
                    ((ids_np % rl.n) * rl.local_win
                     + ids_np // rl.n).astype(np.int32))
            else:
                idx = jnp.asarray(ids_np.astype(np.int32))
            state = dict(self._state)
            if "win" in state and hasattr(self.window_stage, "reset_keys"):
                state["win"] = self.window_stage.reset_keys(state["win"], idx)
            for wk in ("lwin", "rwin"):     # partitioned join sides
                side = getattr(self, "sides", {}).get(
                    "left" if wk == "lwin" else "right") if hasattr(self, "sides") else None
                if wk in state and side is not None and hasattr(
                        side.window_stage, "reset_keys"):
                    state[wk] = side.window_stage.reset_keys(state[wk], idx)
            if "nfa" in state:
                nfa = dict(state["nfa"])
                for k in ("active", "consumed", "armed"):
                    nfa[k] = nfa[k].at[idx].set(False)
                state["nfa"] = nfa
            if self.keyer is None:
                # gk == pk: selector rows are addressed by partition id.
                # Rows reset to the aggregator INIT values (min/max keep
                # their +/-inf sentinels), gathered from a fresh state.
                # Key axis = first axis sized num_keys (the same heuristic
                # parallel/mesh.py shards by).
                K = self.selector_plan.num_keys
                init = self.selector_plan.init_state()
                sel_idx, init_idx = idx, idx
                if rl is not None:
                    # sel space is gk == pk here; init rows are identical
                    # per key, so gather them at the LOCAL id
                    K = K * rl.n
                    sel_idx = jnp.asarray(
                        ((ids_np % rl.n) * rl.localK
                         + ids_np // rl.n).astype(np.int32))
                    init_idx = jnp.asarray((ids_np // rl.n).astype(np.int32))

                def reset_key_rows(x, x0):
                    if not hasattr(x, "shape"):
                        return x
                    for ax, s in enumerate(x.shape):
                        if s == K:
                            sl = [slice(None)] * x.ndim
                            sl[ax] = sel_idx
                            sl0 = [slice(None)] * x.ndim
                            sl0[ax] = init_idx
                            return x.at[tuple(sl)].set(
                                jnp.asarray(x0)[tuple(sl0)])
                    return x

                state["sel"] = jax.tree_util.tree_map(
                    reset_key_rows, state["sel"], init)
            else:
                # composite (pk, group) keys: drop the purged pks' entries
                # so a reused id cannot alias old groups (their gk rows
                # become unreachable, not recycled)
                dead = set(int(i) for i in np.asarray(ids))
                self.keyer._map = {k: v for k, v in self.keyer._map.items()
                                   if int(k[0]) not in dead}
                self.keyer._lut = np.full(64, -1, np.int32)
                # _next is untouched: gk ids are never reused, so a fresh
                # (pk, group) key can never alias a surviving group's row
            self._state = state

    def _make_step(self):
        # first-call compile timing rides a telemetry proxy: jit-compile
        # count/wall-ms per query (and a span("jit")) with one attribute
        # check per call afterwards — re-jits on capacity growth show up
        # as fresh compile events
        if self._route_layout is not None:
            # a cleared step on a device-routed runtime (restore, growth)
            # must come back ROUTED, not as the plain unsharded jit
            from siddhi_tpu.parallel.mesh import routed_step_for

            return routed_step_for(self)
        jitted = jax.jit(
            instruments.named_step(self.build_step_fn(), "query_step"),
            donate_argnums=0)
        return self.app_context.telemetry.instrument_jit(
            jitted, f"query.{self.name}.step", family="query_step")

    # ------------------------------------------------- device instruments

    def _instruments_on(self) -> bool:
        """Gate of the telemetry instrument slots — the per-app typed
        knob ``siddhi_tpu.profile_device_instruments`` (default on; off
        keeps today's meta layouts bit-for-bit). Consulted at step BUILD
        and at drain, so layout and decoder always agree."""
        return instruments.app_instruments_on(self.app_context)

    def instrument_slots(self) -> List[Slot]:
        """Ordered spec of everything this runtime's meta carries BEHIND
        the standard ``[overflow, notify, count]`` prefix — the single
        declaration the step builder, the CompletionPump drain and
        graftlint R6 all read. Route-structural slots first (their lanes
        predate the registry and are knob-independent), then the inner
        step's slots (``_step_instrument_slots``). Cached per runtime —
        the drain runs per batch; the spec only changes when the layout
        does (route install / engine attach invalidate ``_instr_spec``)."""
        if self._instr_spec is not None:
            return self._instr_spec
        spec: List[Slot] = []
        rl = self._route_layout
        if rl is not None:
            spec.append(Slot("route_overflow", kind="check"))
            spec.append(Slot("shard_rows", width=rl.n))
            if self._instruments_on():
                spec.append(Slot("route_residual"))
        spec.extend(self._step_instrument_slots())
        self._instr_spec = spec
        return spec

    def _step_instrument_slots(self) -> List[Slot]:
        """Slots the INNER (per-shard) step appends — overridden by the
        join/NFA runtimes to match their own step builders exactly."""
        if not self._instruments_on():
            return []
        slots: List[Slot] = []
        if (self.window_stage is not None
                and hasattr(self.window_stage, "live_fill")):
            slots.append(Slot("win_fill", reduce="max"))
        if self.keyer is not None or self.partition_ctx is not None:
            slots.append(Slot("groups"))
        return slots

    def _instrument_values(self, slots: List[Slot], new_state, cols) -> List:
        """Device-side slot computation (runs INSIDE the jitted step,
        from state/columns the step already holds — zero extra work
        beyond a couple of reductions)."""
        vals = []
        for slot in slots:
            if slot.name == "win_fill":
                vals.append(jnp.asarray(
                    self.window_stage.live_fill(new_state["win"]),
                    jnp.int64).reshape(1))
            elif slot.name == "groups":
                K = self.selector_plan.num_keys
                valid = cols[VALID_KEY]
                gk = jnp.clip(cols[GK_KEY].astype(jnp.int64), 0, K - 1)
                idx = jnp.where(valid, gk, jnp.int64(K))
                seen = jnp.zeros(K + 1, bool).at[idx].set(True, mode="drop")
                vals.append(jnp.sum(seen[:K], dtype=jnp.int64).reshape(1))
        return vals

    def _instrument_capacity(self, name: str) -> Optional[float]:
        """Host-known denominator of one data slot (the report quotes
        saturation against it); None = not a saturation-style signal."""
        if name == "win_fill":
            return getattr(self.window_stage, "ring_capacity", None)
        if name == "groups":
            k = self.selector_plan.num_keys
            rl = self._route_layout
            return float(k * rl.n) if rl is not None else float(k)
        if name in ("shard_rows", "route_residual"):
            rl = self._route_layout
            return float(rl.n * rl.quota) if rl is not None else None
        return None

    def decode_meta_suffix(self, meta, jr=None) -> None:
        """Drain-side decoder of the meta suffix, shared by the
        synchronous tail, the CompletionPump drain, the deferred flush
        and the fused fan-out per-member path: walk the spec, record
        data slots into ``device.<query>.<slot>`` telemetry (and the
        zero-pull ``_instr_last`` cache), then run the structural check
        slots (route-overflow raise, join seq verification). Data lands
        BEFORE checks so a fatal overflow still leaves the skew gauges
        pointing at the culprit."""
        spec = self.instrument_slots()
        meta = np.asarray(meta)
        if not spec or meta.shape[0] <= 3:
            return
        ins_on = self._instruments_on()
        checks = []
        i = 3
        for slot in spec:
            if i + slot.width > meta.shape[0]:
                # a meta SHORTER than the spec means a builder/spec
                # layout drift — the bug class this registry exists to
                # prevent. It must be loud, not a silent skip of the
                # pending check slots (join seq, route overflow).
                if "decode_short" not in self._instr_gauged:
                    self._instr_gauged.add("decode_short")
                    _LOG.error(
                        "query '%s': meta suffix (%d lanes) shorter than "
                        "the declared instrument spec %s — step builder "
                        "and instrument_slots() drifted apart; remaining "
                        "slots (incl. checks) not decoded",
                        self.name, meta.shape[0] - 3,
                        [s.name for s in spec])
                tel = getattr(self.app_context, "telemetry", None)
                if tel is not None:
                    tel.count("device.decode_short")
                break
            vals = np.asarray(meta[i:i + slot.width], np.int64)
            i += slot.width
            if slot.kind == "check":
                checks.append((slot, vals))
            else:
                self._record_instrument(slot, vals, telemetry=ins_on, jr=jr)
        for slot, vals in checks:
            self._consume_check_slot(slot.name, vals)

    def _record_instrument(self, slot: Slot, vals, telemetry: bool,
                           jr=None) -> None:
        self._instr_last[slot.name] = vals
        if slot.name == "shard_rows" and self._route_layout is not None:
            # back-compat mirror (skew debugging reads it directly)
            self._route_layout.last_shard_rows = vals
            if jr is not None:
                jr.shards_filled(int(vals.max(initial=0)),
                                 self._instrument_capacity(slot.name))
        if telemetry:
            instruments.record(self, slot, vals,
                               capacity=self._instrument_capacity(slot.name))

    def _consume_check_slot(self, name: str, vals) -> None:
        """Structural (kind='check') slot consumers; the join runtime
        adds 'seq'. graftlint R6 pairs every check slot with a literal
        handled here or in an override."""
        if name == "route_overflow" and int(vals[0]) > 0:
            raise FatalQueryError(
                f"query '{self.name}': {self.route_overflow_msg()}")

    def build_step_fn(self):
        """The pure (state, cols, now) -> (state', out) device function for
        this query — jit-compiled by `_make_step`, also exported raw for
        sharded execution (siddhi_tpu.parallel) and the driver's
        compile-check (`__graft_entry__.entry`)."""
        # host windows already applied the filters (and transforms) before
        # their stage, host-side; host_transforms likewise pre-applies the
        # transforms so the group keyer can read synthetic columns
        host_pre = self.host_window is not None
        filters = [] if host_pre else list(self.filters)
        transforms = [] if (host_pre or self.host_transforms) else list(self.transforms)
        post_pipeline = [] if host_pre else list(self.post_pipeline)
        sel = self.selector_plan
        win = self.window_stage
        islots = self._step_instrument_slots()

        def step(state, cols, current_time):
            from siddhi_tpu.core.plan.selector_plan import STR_RANK

            ctx = {"xp": jnp, "current_time": current_time}
            cols = dict(cols)
            strrank = cols.pop(STR_RANK, None)  # window stages rebuild cols
            for t in transforms:
                cols = t.apply(cols, ctx)
            valid = cols[VALID_KEY]
            timer = cols[TYPE_KEY] == 2
            for f in filters:
                valid = valid & (f(cols, ctx) | timer)
            cols[VALID_KEY] = valid
            new_state = dict(state)
            notify = None
            overflow = None
            if win is not None:
                with jax.named_scope(instruments.STATE_SCOPE):
                    new_state["win"], cols = win.apply(
                        state["win"], conform_cols(win, cols), ctx)
                cols = dict(cols)
                notify = cols.pop("__notify__", None)
                overflow = cols.pop("__overflow__", None)
                # post-window stages transform/mask emitted rows (window
                # retention is unaffected — they sit downstream of it)
                ptimer = cols[TYPE_KEY] == 2
                for kind, obj in post_pipeline:
                    if kind == "t":
                        cols = obj.apply(cols, ctx)
                    else:
                        cols[VALID_KEY] = cols[VALID_KEY] & (
                            obj(cols, ctx) | ptimer)
            if strrank is not None:
                cols[STR_RANK] = strrank
            with jax.named_scope(instruments.SELECT_SCOPE):
                new_state["sel"], out = sel.apply(state["sel"], cols, ctx)
            with jax.named_scope(instruments.META_SCOPE):
                if notify is not None:
                    out["__notify__"] = notify
                if overflow is not None:
                    sel_ov = out.get("__overflow__")
                    out["__overflow__"] = overflow if sel_ov is None else jnp.maximum(
                        jnp.asarray(overflow).astype(jnp.int32),
                        jnp.asarray(sel_ov).astype(jnp.int32))
                out = pack_meta(out)
                if islots:
                    # device instruments ride behind the [ov, notify,
                    # count] prefix — decoded by spec at drain
                    # (decode_meta_suffix)
                    out["__meta__"] = jnp.concatenate(
                        [out["__meta__"]]
                        + self._instrument_values(islots, new_state, cols))
            return new_state, out

        return step

    # ----------------------------------------------------------- processing

    def receive(self, events: List[Event]):
        batch = HostBatch.from_events(events, self.input_definition,
                                      self.dictionary,
                                      pool=pack_pool_of(self.app_context))
        if self.carried_pk:
            pk = np.zeros(batch.capacity, np.int32)
            for i, e in enumerate(events):
                pk[i] = e.pk or 0
            batch.cols[PK_KEY] = pk
        self.process_batch(batch)

    def receive_batch(self, batch: HostBatch, junction=None):
        """Columnar fast path from StreamJunction.send_batch — no Event
        objects on ingest."""
        if self.carried_pk and PK_KEY not in batch.cols:
            batch.cols[PK_KEY] = np.zeros(batch.capacity, np.int32)
        backfill_null_masks(batch, self.input_definition)
        self.process_batch(batch, junction=junction)

    _now_override = None   # timer chunks sweep at their scheduled time

    def _now(self) -> int:
        """Current time for window expiry/stamping: the TIMER chunk's
        scheduled timestamp while one is being processed (the playback
        clock has already jumped ahead of queued timers — reference
        ``Scheduler.sendTimerEvents`` fires each timer AT its time), else
        the app clock."""
        if self._now_override is not None:
            return self._now_override
        return int(self.app_context.timestamp_generator.current_time())

    def process_timer(self, ts: int):
        """Inject a TIMER chunk (the role of Scheduler.sendTimerEvents +
        EntryValveProcessor in the reference), under the ``siddhi.timer``
        span. Fired by a send's clock advance (playback), the chunk is
        that send's work: span and journey take its batch id."""
        self.app_context.telemetry.count(f"window.{self.name}.timer_steps")
        sender = journey.sending_batch()
        with span("timer", query=self.name, batch=sender, ts=int(ts)):
            batch = HostBatch.from_events(
                [Event(timestamp=int(ts), data=[_zero_value(a.type) for a in self.input_definition.attributes])],
                self.input_definition,
                self.dictionary,
            )
            batch.cols[TYPE_KEY][...] = TIMER_TYPE
            if sender is not None \
                    and getattr(batch, "journey", None) is not None:
                batch.journey.batch = sender
            # take the per-query lock BEFORE setting the override: a
            # live-mode event batch on another thread must never observe
            # the timer's ts as its clock (the RLock nests with
            # process_batch's own acquire)
            with self._lock:
                # in-flight pipelined batches were dispatched BEFORE this
                # timer fired: drain them first so the timer sweep
                # observes a fully-emitted timeline (and the timer batch
                # itself runs synchronously — _now_override gates the
                # pipeline branch)
                pump = getattr(self.app_context, "completion_pump", None)
                if pump is not None and pump.has_pending:
                    pump.flush_owner(self)
                self._now_override = int(ts)
                try:
                    self.process_batch(batch)
                finally:
                    self._now_override = None

    def _apply_host_transforms(self, cols, ctx):
        for t in self.transforms:
            cols = t.apply(cols, ctx)
        return cols

    def _run_log_taps(self, batch: HostBatch):
        """Host side of ``#log()`` taps: replay each tap's slice of the
        pre-window pipeline with numpy and log the rows flowing at its
        position in the handler chain (LogStreamProcessor.java:219-277)."""
        base_valid = np.asarray(batch.cols[VALID_KEY]) & (
            np.asarray(batch.cols[TYPE_KEY]) == CURRENT)
        if not base_valid.any():
            return
        ctx = {
            "xp": np,
            "current_time": self._now(),
        }
        # only replay the transform prefix some tap actually reads
        depth = min(max(t.n_transforms for t in self.log_stages),
                    len(self.transforms))
        stages = [batch.cols]
        for t in self.transforms[:depth]:
            stages.append(t.apply(stages[-1], ctx))
        for tap in self.log_stages:
            cols = stages[min(tap.n_transforms, len(stages) - 1)]
            valid = np.asarray(cols[VALID_KEY]) & (
                np.asarray(cols[TYPE_KEY]) == CURRENT)
            for f in self.filters[: tap.n_filters]:
                valid = valid & np.asarray(f(cols, ctx))
            idx = np.nonzero(valid)[0]
            if idx.size == 0:
                continue
            attrs = list(self.input_definition.attributes)
            for t in self.transforms[: tap.n_transforms]:
                attrs.extend(t.out_attrs)
            rows, timestamps = [], []
            ts_col = cols[TS_KEY]
            for i in idx:
                row = []
                for a in attrs:
                    mcol = cols.get(a.name + "?")
                    if mcol is not None and bool(mcol[i]):
                        row.append(None)
                    elif a.type == AttrType.STRING:
                        row.append(self.dictionary.decode(int(cols[a.name][i])))
                    else:
                        row.append(cols[a.name][i].item())
                rows.append(tuple(row))
                timestamps.append(int(ts_col[i]))
            tap.emit(rows, timestamps)

    def process_batch(self, batch: HostBatch, junction=None):
        from siddhi_tpu.core.stream.junction import current_delivering_junction

        with span("query.step", batch=journey.batch_of(batch),
                  query=self.name), self._lock:
            # Event-path deliveries (Receiver.receive) carry no junction
            # parameter — fall back to the delivery-loop thread-local so
            # pipelined completions keep their error attribution and
            # latency feedback; direct receiver feeds see None
            j = junction or current_delivering_junction()
            self._cur_junction = j
            # fault-stream routing of drain-time errors needs the input
            # events; retain the batch only under @OnError(action=stream)
            self._cur_fault_batch = batch if (
                j is not None and j.on_error_action == "STREAM"
                and j.fault_junction is not None) else None
            # batch-journey: fork the pack stamp, open the dispatch
            # stage (host prep + step dispatch); _finish_device_batch
            # consumes it (one journey per delivered batch — routed
            # splits ride the first piece)
            jr = self._batch_journey = self._cur_journey = \
                journey.begin(batch) if journey.enabled() else None
            if jr is not None and self._now_override is not None:
                jr.timer_steps = 1
            notify_host = None
            if self.log_stages:
                self._run_log_taps(batch)
            partitioned = self.partition_ctx is not None
            pk_done = False
            if partitioned and self.host_window is not None:
                # per-key host stages route rows by the pk column, so the
                # partition key must be attached before the window runs
                with journey.keying(jr, self.name, batch.capacity,
                                    self._needed_sel_keys):
                    cols = batch.cols
                    if self.carried_pk:
                        pk0 = cols.get(PK_KEY)
                        if pk0 is None:
                            pk0 = np.zeros(batch.capacity, np.int32)
                    elif self.partition_keyer is not None:
                        cols, pk0 = self.partition_keyer.apply(cols)
                        batch = HostBatch(cols)
                    else:
                        pk0 = np.zeros(batch.capacity, np.int32)
                    batch.cols[PK_KEY] = np.asarray(pk0, np.int32)
                pk_done = True
            if self.host_window is not None:
                now_h = self._now()
                ctx = {"xp": np, "current_time": now_h}
                cols = batch.cols
                for t in self.transforms:
                    cols = t.apply(cols, ctx)
                valid = cols[VALID_KEY]
                timer = cols[TYPE_KEY] == TIMER_TYPE
                for f in self.filters:
                    valid = valid & (np.asarray(f(cols, ctx)) | timer)
                cols[VALID_KEY] = valid
                batch = HostBatch(cols)
                batch, notify_host = self.host_window.process(batch, now_h)
                if self.post_pipeline:
                    cols = dict(batch.cols)
                    ptimer = cols[TYPE_KEY] == TIMER_TYPE
                    for kind, obj in self.post_pipeline:
                        if kind == "t":
                            cols = obj.apply(cols, ctx)
                        else:
                            cols[VALID_KEY] = cols[VALID_KEY] & (
                                np.asarray(obj(cols, ctx)) | ptimer)
                    batch = HostBatch(cols)
            elif self.host_transforms:
                now_h = self._now()
                batch = HostBatch(self._apply_host_transforms(
                    batch.cols, {"xp": np, "current_time": now_h}))
            cols = batch.cols
            pk = None
            with journey.keying(jr, self.name, batch.capacity,
                                self._needed_sel_keys):
                if partitioned:
                    if pk_done:
                        # already attached (and carried through the host
                        # window's emitted rows)
                        pk = cols.get(PK_KEY)
                        if pk is None:
                            pk = np.zeros(batch.capacity, np.int32)
                    elif self.carried_pk:
                        pk = cols.get(PK_KEY)
                        if pk is None:
                            pk = np.zeros(batch.capacity, np.int32)
                    elif self.partition_keyer is not None:
                        cols, pk = self.partition_keyer.apply(cols)
                        batch = HostBatch(cols)
                    cols[PK_KEY] = np.asarray(pk, np.int32)
                if self.keyer is not None:
                    cols[GK_KEY] = self.keyer(
                        cols, pk=pk if partitioned else None)
                elif partitioned:
                    cols[GK_KEY] = cols[PK_KEY]
                else:
                    cols[GK_KEY] = np.zeros(batch.capacity, np.int32)
                if partitioned or self.keyer is not None:
                    self._ensure_capacity()
            if self._state is None:
                self._state = self._init_state()
            if self._step is None:
                self._step = self._make_step()
            else:
                # hit key follows the wrapper's own key: a sharded step
                # (mesh.shard_query_step) compiles under ".sharded_step",
                # and its hits must land on the SAME series or cache-hit
                # dashboards read garbage for sharded apps
                self.app_context.telemetry.record_jit(
                    getattr(self._step, "_key", f"query.{self.name}.step"),
                    hit=True)
            if self._route_layout is not None:
                # device-routed dispatch: pad/precheck host-side (splitting
                # oversized batches instead of overflowing) and run each
                # piece through the routed step in order
                from siddhi_tpu.parallel.mesh import prepare_routed_batches

                notify = None
                with span("route.prepare", query=self.name,
                          batch=jr.batch if jr is not None else None) as sp:
                    pieces = prepare_routed_batches(self, cols)
                if jr is not None:
                    jr.route_prepared(sp.ms, len(pieces))
                for piece in pieces:
                    nt = self._finish_device_batch(
                        self._step, piece, self.overflow_knob_msg())
                    if nt is not None:
                        notify = nt if notify is None else min(notify, nt)
            else:
                notify = self._finish_device_batch(
                    self._step, cols, self.overflow_knob_msg())
        if notify_host is not None:
            notify = notify_host if notify is None else min(notify, notify_host)
        if (notify is not None and self._now_override is not None
                and notify <= self._now_override):
            # a TIMER step that asks to be woken at or before its own
            # time has not moved its boundary: waking it again would spin
            # the playback sweep at one instant forever
            notify = None
        if notify is not None and self.scheduler is not None:
            self.scheduler.notify_at(notify, self.process_timer)

    def overflow_knob_msg(self, code: Optional[int] = None) -> str:
        """Capacity-overflow message naming THIS query's knob — shared by
        the unfused path and the fused fan-out group
        (``core/query/fused_fanout.py``) so attribution cannot drift.
        ``code`` is the step's overflow value; join runtimes decode it
        as a bitmask into the exact knob (single-stream steps carry a
        single overflow cause, so it is ignored here)."""
        knob = (
            "app_context.partition_window_capacity"
            if self.partition_ctx is not None
            else "app_context.window_capacity"
        )
        if any(s.kind == "distinctcount"
               for s in self.selector_plan.specs or []):
            knob += " (or app_context.distinct_values_capacity)"
        return f"window buffer capacity exceeded — raise {knob}"

    def route_overflow_msg(self) -> str:
        """Device-router exchange overflow naming its knob, in the same
        convention as ``overflow_knob_msg`` (the host precheck splits
        oversized batches, so this only fires on direct step callers that
        bypass ``prepare_routed_batches``)."""
        rl = self._route_layout
        rps = rl.rows_per_shard if rl is not None else 0
        return (f"shard exchange overflow — more rows bound for one shard "
                f"pair than its quota; raise rows_per_shard={rps} "
                f"(device_route_query_step) or split the batch")

    def _routed_meta_check(self, meta) -> None:
        """Back-compat alias: the route-overflow/rows suffix is now one
        case of the declarative instrument spec — see
        ``decode_meta_suffix`` / ``instrument_slots``."""
        self.decode_meta_suffix(meta)

    def _host_keyed_select(self, out_host: Dict[str, np.ndarray],
                           jr=None) -> Dict[str, np.ndarray]:
        """Split-pipeline tail: when the group key is computed from a device
        stage's OUTPUT columns (pattern captures, joined rows), the keyer
        runs host-side between the stage and a separately-jitted selector
        step (GroupByKeyGenerator.java:37 over intermediate events)."""
        with journey.keying(jr, self.name,
                            dict.__getitem__(out_host, VALID_KEY).shape[0],
                            self._needed_sel_keys):
            pk = (out_host.get(PK_KEY) if self.partition_ctx is not None
                  else None)
            out_host[GK_KEY] = self.keyer(out_host, pk=pk)
            self._ensure_capacity()
        if self._sel_step is None:
            sel = self.selector_plan

            def fn(sel_state, cols, now):
                with jax.named_scope(instruments.SELECT_SCOPE):
                    st2, out2 = sel.apply(
                        sel_state, cols, {"xp": jnp, "current_time": now})
                with jax.named_scope(instruments.META_SCOPE):
                    return st2, pack_meta(out2)

            self._sel_step = self.app_context.telemetry.instrument_jit(
                jax.jit(instruments.named_step(fn, "selector"),
                        donate_argnums=0),
                f"query.{self.name}.selector", family="selector")
        else:
            self.app_context.telemetry.record_jit(
                f"query.{self.name}.selector", hit=True)
        now = np.int64(self._now())
        new_sel, sel_out = launch_step(
            self._sel_step, self._state["sel"], dict(out_host), now,
            query=self.name, jr=jr)
        self._state["sel"] = new_sel
        out = LazyColumns(sel_out)
        meta = out.pop("__meta__", None)
        out.pop("__notify__", None)
        out.pop("__overflow__", None)
        if meta is not None and int(np.asarray(meta)[0]) != 0:
            # the selector step's own overflow (distinctCount value-table
            # saturation) must not be silently clamped on the split path
            raise FatalQueryError(
                "selector aggregation overflow — raise "
                "app_context.distinct_values_capacity")
        return out

    def _finish_device_batch(self, step, cols, overflow_msg: str) -> Optional[int]:
        """Run the jitted step, raise on overflow, emit outputs; returns the
        wanted timer wake time (or None). Shared tail of every query
        runtime's batch processing (single-stream, NFA, join)."""
        from siddhi_tpu.core.util.statistics import latency_t0, record_elapsed_ms

        sm = self.app_context.statistics_manager
        t0 = latency_t0(sm)
        jr = self._cur_journey
        self._cur_journey = None
        now = np.int64(self._now())
        if isinstance(cols, LazyColumns):
            cols = dict(cols)   # jit boundary: raw (possibly device) arrays
        if self.selector_plan.needs_str_rank:
            # string order-by keys sort by lexicographic rank, not id
            from siddhi_tpu.core.plan.selector_plan import STR_RANK

            cols[STR_RANK] = self.dictionary.rank_table()
        # the batch's journey, not ``jr``: a later piece of a split batch
        # has none of its own, and its launch belongs to the batch's sum
        self._state, out = launch_step(step, self._state, cols, now,
                                       query=self.name,
                                       jr=self._batch_journey)
        if jr is not None:
            jr.state_sized(self.state_bytes(), self.state_slots())
        # lazy pull: only columns a consumer actually reads cross the
        # device->host link; overflow/notify/size travel as ONE packed
        # array — a single device->host round trip per batch
        out_host = LazyColumns(out)
        size_hint = None
        meta = (dict.__getitem__(out_host, "__meta__")
                if "__meta__" in out_host else None)   # raw — no pull yet
        if meta is not None:
            pump = getattr(self.app_context, "completion_pump", None)
            if (pump is not None and pump.depth > 1 and self._pipeline_ok
                    and self._now_override is None):
                # pipelined dispatch: the batch rides in flight while the
                # producer packs the next one; the pump emits in dispatch
                # order, delivers __notify__ at drain, and surfaces
                # overflow on the producer's next send (completion.py)
                from siddhi_tpu.core.query.completion import QueryCompletion

                record_elapsed_ms(sm, self.name, t0)
                if jr is not None:
                    jr.end_dispatch()   # device/emit stages close at drain
                pump.submit(QueryCompletion(
                    self, out_host, overflow_msg,
                    junction=self._cur_junction,
                    batch=getattr(self, "_cur_fault_batch", None),
                    journey=jr))
                return None
            defer = getattr(self.app_context, "defer_meta", 1)
            if defer > 1 and self._defer_ok:
                # batch N metas into ONE round trip: queue the (device)
                # output; emission + overflow surfacing lag <= N batches
                # (dispatch-side latency only — emission is deferred)
                record_elapsed_ms(sm, self.name, t0)
                if jr is not None:
                    # legacy hold-N path: the deferred drain is not
                    # instrumented — finish with the stages observed so
                    # far (pack/queue/dispatch) rather than vanishing
                    jr.end_dispatch()
                    jr.finish(self.app_context, (self.name,))
                self._deferred.append((out_host, overflow_msg))
                if len(self._deferred) < defer:
                    return None
                return self.flush_deferred()
            dict.pop(out_host, "__meta__")
            if jr is not None:
                # synchronous device stage: the ride is ~0 (we pull
                # immediately), so device service is the blocking pull
                jr.end_dispatch()
                jr.pre_drain(journey.ready_of(meta))
            meta = self._pull_meta(meta, jr)
            self.decode_meta_suffix(meta, jr)
            overflow = int(meta[0])
            notify = int(meta[1])
            size_hint = int(meta[2])
            if overflow > 0:
                # joins pass a CALLABLE that decodes the step's overflow
                # bitmask into the exact knob (overflow_knob_msg)
                msg = (overflow_msg(overflow) if callable(overflow_msg)
                       else overflow_msg)
                raise FatalQueryError(
                    f"query '{self.name}': {msg} before creating the runtime")
            record_elapsed_ms(sm, self.name, t0)
            self._timed_emit(HostBatch(out_host, size=size_hint), jr,
                             rows_out=size_hint)
            if notify >= 0:
                return notify
            return None
        overflow = out_host.pop("__overflow__", None)
        if overflow is not None and int(overflow) > 0:
            msg = (overflow_msg(int(overflow)) if callable(overflow_msg)
                   else overflow_msg)
            raise FatalQueryError(
                f"query '{self.name}': {msg} before creating the runtime"
            )
        notify = out_host.pop("__notify__", None)
        record_elapsed_ms(sm, self.name, t0)
        if jr is not None:
            jr.end_dispatch()   # host-window path: no device meta stage
        self._timed_emit(HostBatch(out_host), jr)
        if notify is not None and int(notify) >= 0:
            return int(notify)
        return None

    def _host_batch(self, out_host: LazyColumns,
                    size: Optional[int]) -> HostBatch:
        """The batch to emit from a step's output, once its meta has said
        how many rows are valid: the one place that settles, before
        anything touches a column, whether a pull moves the compacted
        columns or the padded ones (``LazyColumns.choose``: an NFA stream
        step's output; a no-op for every other step's), and counts the
        choice (``pull.<query>.compacted`` / ``.padded``; an output with
        no valid row is never pulled and counts as neither). ``size``
        None (no meta said): the padded columns."""
        compacted = out_host.choose(size)
        if compacted is not None and size:
            self.app_context.telemetry.count(
                f"pull.{self.name}."
                + ("compacted" if compacted else "padded"))
        return HostBatch(out_host, size=size)

    def _timed_emit(self, out: HostBatch, jr, rows_out=None) -> None:
        """``_emit`` inside the journey's emit stage (``siddhi.emit``
        span; at its close the journey is finished: histograms + ring) —
        the synchronous tail; pipelined batches run the same accounting
        at drain (completion.py)."""
        if rows_out and getattr(self.window_stage, "counts_flushes", False):
            # a folded tumbling window answers only when it closes
            self.app_context.telemetry.count(f"window.{self.name}.flushes")
            if jr is not None:
                jr.flushed(rows_out)
        if jr is None:
            self._emit(out)
            return
        with jr.emitting(self.app_context, (self.name,), rows_out):
            self._emit(out)

    def _pull_meta(self, meta, jr=None):
        """Pull the packed meta array, under the ``siddhi.meta_pull``
        span whose duration the batch's journey ``jr`` keeps; on a
        multi-process mesh with ``siddhi_tpu.cluster_step_timeout`` set,
        bound the wait so a dead peer surfaces as a labeled
        ClusterPeerError through the fault machinery instead of hanging
        the coordinator (SURVEY.md §5.3)."""
        with span("meta_pull", query=self.name,
                  batch=jr.batch if jr is not None else None) as sp:
            timeout = getattr(self.app_context, "cluster_step_timeout", None)
            if timeout is not None and self._shard_mesh is not None:
                from siddhi_tpu.parallel.distributed import guarded_pull

                meta = guarded_pull(meta, timeout,
                                    what=f"query '{self.name}' step")
            else:
                # explicit pull: this is THE sanctioned per-batch round
                # trip — the sanitizer's transfer guard rejects implicit
                # d2h transfers
                meta = np.asarray(jax.device_get(meta))
        if jr is not None:
            jr.meta_pulled(sp.ms)
        return meta

    @property
    def _defer_ok(self) -> bool:
        # scheduler-driven windows need their per-batch __notify__ promptly
        return (self.host_window is None
                and (self.window_stage is None
                     or not getattr(self.window_stage, "needs_scheduler", False)))

    @property
    def _pipeline_ok(self) -> bool:
        """May this runtime's batches ride the CompletionPump? Unlike
        ``_defer_ok``, scheduler-driven and host windows are ELIGIBLE —
        the pump delivers their ``__notify__`` wake times promptly at
        drain (sync sends flush before returning; @Async workers flush at
        queue-idle) instead of holding them a full defer window. Joins
        override this to False (``join_runtime._pipeline_ok``)."""
        return True

    def flush_deferred(self) -> Optional[int]:
        """Drain queued outputs: pull ALL their metas in one batched round
        trip, then emit in order (called when the defer window fills, at
        checkpoints, and at shutdown)."""
        with self._lock:
            if not self._deferred:
                return None
            pending, self._deferred = self._deferred, []
            raw = [dict.__getitem__(o, "__meta__") for o, _m in pending]
            timeout = getattr(self.app_context, "cluster_step_timeout", None)
            if timeout is not None and self._shard_mesh is not None:
                # the deferred drain is a device pull too: bound it the
                # same way as _pull_meta, or a dead peer hangs it forever
                from siddhi_tpu.parallel.distributed import guarded_pull

                metas = guarded_pull(raw, timeout,
                                     what=f"query '{self.name}' drain")
            else:
                metas = jax.device_get(raw)
            notify_min: Optional[int] = None
            overflow_errs: List[str] = []
            for (out_host, overflow_msg), meta in zip(pending, metas):
                dict.pop(out_host, "__meta__")
                try:
                    # instrument/structural suffix (drain-then-raise:
                    # a route overflow joins the collected errors)
                    self.decode_meta_suffix(meta)
                except FatalQueryError as suffix_err:
                    msg = str(suffix_err)
                    if msg not in overflow_errs:
                        overflow_errs.append(msg)
                overflow, notify, size = int(meta[0]), int(meta[1]), int(meta[2])
                if overflow > 0 and overflow_msg not in overflow_errs:
                    # every DISTINCT knob text of an overflowed batch is
                    # reported (first-error-wins dropped the later
                    # members' knobs); still drain-then-raise
                    overflow_errs.append(overflow_msg)
                self._emit(self._host_batch(out_host, size))
                if notify >= 0:
                    notify_min = notify if notify_min is None else min(notify_min, notify)
            if overflow_errs:
                raise FatalQueryError(
                    f"query '{self.name}': {'; '.join(overflow_errs)} "
                    f"before creating the runtime")
            return notify_min

    def _emit(self, out: HostBatch):
        if out.size == 0:
            return
        uuid_cols = self.selector_plan.uuid_cols
        if uuid_cols:
            # uuid(): fresh per-row UUID strings, filled host-side (the
            # jitted step emitted placeholders — see ops/expressions.py).
            # The whole batch of UUIDs — every column — is generated up
            # front and dictionary-encoded in ONE encode_array pass; the
            # fused fan-out path shares this call site via m._emit
            idx = np.nonzero(np.asarray(out.cols[VALID_KEY]))[0]
            if idx.size:
                fresh = np.array(
                    [str(uuid.uuid4())
                     for _ in range(idx.size * len(uuid_cols))],
                    dtype=object)
                ids = self.dictionary.encode_array(fresh)
                for ci, col in enumerate(uuid_cols):
                    vals = np.asarray(out.cols[col]).copy()
                    vals[idx] = ids[ci * idx.size:(ci + 1) * idx.size]
                    out.cols[col] = vals
        from siddhi_tpu.core.query.ratelimit import PassThroughRateLimiter

        if (
            (self.rate_limiter is None
             or type(self.rate_limiter) is PassThroughRateLimiter)
            and self.output_action is None
            and not self.query_callbacks
            and self.output_junction is not None
            and not self.attach_pk
            and hasattr(self.output_junction, "send_batch")
        ):
            # columnar re-publish: no Event materialization between queries
            cols = LazyColumns(out.cols)
            if self.selector_plan.expired_on:
                # EXPIRED -> CURRENT on re-publish
                # (InsertIntoStreamCallback.java:52-55); CURRENT-only
                # selectors skip the flip — touching TYPE would pull every
                # device column to the host
                t = cols[TYPE_KEY]
                cols[TYPE_KEY] = np.where(t == EXPIRED, CURRENT, t).astype(np.int8)
            # more matches than an NFA step's compacted width holds (a
            # fall-back to the padded columns): downstream has compiled for
            # that width, so the rows go on in pieces of it and it never
            # sees the padded one
            width = getattr(out.cols, "fell_back_from", None)
            for piece in ([HostBatch(cols, size=out._size)] if width is None
                          else _valid_rows_in_pieces(cols, width)):
                self.output_junction.send_batch(piece)
            return
        want_pk = self.attach_pk or self.limiter_needs_pk
        events = out.to_events(
            self.output_attrs, self.dictionary,
            pk_key=PK_KEY if want_pk else None,
            gk_key=GK_KEY if self.limiter_needs_gk else None,
            object_meta=self.selector_plan.object_meta or None,
            object_multi=set(self.selector_plan.object_multi) or None,
        )
        if self.rate_limiter is not None:
            self.rate_limiter.process(events)
        else:
            self.send_to_callbacks(events)

    def send_empty_to_query_callbacks(self):
        """Snapshot limiters deliver EMPTY flushes to QueryCallbacks as
        (null, null) — SnapshotOutputRateLimitTestCase q21 counts them —
        while stream junctions/actions see nothing."""
        ts = self.app_context.timestamp_generator.current_time()
        for cb in self.query_callbacks:
            cb.receive(ts, None, None)

    def send_to_callbacks(self, events: List[Event]):
        if not events:
            return
        if self.output_action is not None:
            self.output_action(events)
        elif self.output_junction is not None:
            # EXPIRED -> CURRENT on re-publish (InsertIntoStreamCallback.java:52-55)
            repub = [
                Event(timestamp=e.timestamp, data=e.data, pk=e.pk) if e.is_expired else e
                for e in events
            ]
            self.output_junction.send_events(repub)
        for cb in self.query_callbacks:
            in_events = [e for e in events if not e.is_expired] or None
            remove_events = [e for e in events if e.is_expired] or None
            cb.receive(events[0].timestamp, in_events, remove_events)


def _valid_rows_in_pieces(cols: LazyColumns, width: int):
    """The valid rows of ``cols``, in order, as batches ``width`` wide
    (host side: this pulls the columns)."""
    rows = np.nonzero(cols[VALID_KEY])[0]
    for at in range(0, rows.size, width):
        take = rows[at:at + width]
        piece = {}
        for k in cols:
            col = cols[k]
            piece[k] = np.zeros((width,) + col.shape[1:], col.dtype)
            piece[k][:take.size] = col[take]
        yield HostBatch(piece, size=int(take.size))


def backfill_null_masks(batch: HostBatch, definition) -> None:
    """A re-published batch omits '?' masks for never-null outputs;
    window buffers key off the full col-spec set, so backfill. Shared by
    the unfused and fused receive_batch paths — the capacity read skips
    ``__getitem__`` so device-held columns stay unpulled."""
    cap = dict.__getitem__(batch.cols, VALID_KEY).shape[0]
    for a in definition.attributes:
        if a.name in batch.cols and a.name + "?" not in batch.cols:
            batch.cols[a.name + "?"] = np.zeros(cap, bool)


def pack_meta(out: dict) -> dict:
    """Fold __overflow__/__notify__/valid-count into ONE device array so
    the host pays a single D2H round trip per batch (each pull is a
    synchronization point whatever its size)."""
    ov = out.pop("__overflow__", None)
    nt = out.pop("__notify__", None)
    ov = jnp.int64(0) if ov is None else jnp.asarray(ov).astype(jnp.int64).reshape(())
    nt = jnp.int64(-1) if nt is None else jnp.asarray(nt).astype(jnp.int64).reshape(())
    n = jnp.sum(out[VALID_KEY], dtype=jnp.int64)
    out["__meta__"] = jnp.stack([ov, nt, n])
    return out


def _zero_value(attr_type: AttrType):
    if attr_type == AttrType.STRING:
        return ""
    if attr_type == AttrType.BOOL:
        return False
    return 0


def _pow2(needed: int, start: int = 16) -> int:
    k = max(start, 1)
    while k < needed:
        k *= 2
    return k


def grow_leaf(init_state, index: int, old):
    """Leaf ``index`` of ``init_state()`` with ``old`` laid over its
    prefix along every axis (keyed buffers are laid out so that a prefix
    copy keeps per-key alignment). One small program: the other leaves
    of ``init_state()`` are dead code in it, and the rows beyond ``old``
    are written straight into the result, so the device holds ``old``
    and the grown leaf and no third buffer. Waits for the result: the
    caller lets ``old`` go only when its successor exists."""
    def build(old):
        fresh = jax.tree_util.tree_leaves(init_state())[index]
        return fresh.at[tuple(slice(0, s) for s in old.shape)].set(old)

    return jax.block_until_ready(jax.jit(build)(old))


def grow_state(init_state, grown: list, old_leaves: list) -> list:
    """The leaves of the state ``init_state()`` describes (``grown``:
    their shapes, at the grown capacity) with the old state's rows in
    place, built LEAF BY LEAF so that the grown state is never held
    twice: ``old_leaves`` (the caller's ONLY reference to the old state,
    flattened) gives each leaf up as its successor is made. The peak is
    the old state plus the new one less what has already moved. A leaf
    whose shape did not change is passed through, the same buffer."""
    new_leaves = []
    for i, shape in enumerate(grown):
        old, old_leaves[i] = old_leaves[i], None
        new_leaves.append(old if shape.shape == old.shape
                          else grow_leaf(init_state, i, old))
    return new_leaves
