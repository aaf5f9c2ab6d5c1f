"""Host driver for pattern/sequence (NFA) queries.

The counterpart of the reference's pattern receivers + state runtime
(``query/input/stream/state/receiver/*.java``, ``StateStreamRuntime.java``):
one runtime subscribes to every junction the pattern consumes (via
``StreamProxy`` receivers); each arriving chunk runs that stream's jitted
NFA transition (``ops/nfa.py``) fused with the query's selector stage.

Absent (`not ... for t`) deadlines additionally drive a scheduler loop:
every device step reports the earliest pending deadline (``__notify__``),
the scheduler wakes the runtime at that time, and ``process_timer`` runs a
jitted all-keys deadline sweep (``NFAStage.apply_timer``) — the role of the
reference's ``Scheduler`` + ``AbsentStreamPreStateProcessor`` timer chain.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.event import Event, HostBatch, LazyColumns, launch_step, pack_pool_of
from siddhi_tpu.core.plan.selector_plan import GK_KEY
from siddhi_tpu.core.query.runtime import QueryRuntime, pack_meta
from siddhi_tpu.core.stream.junction import FatalQueryError, Receiver
from siddhi_tpu.observability import journey
from siddhi_tpu.observability.instruments import (COMPACT_SCOPE, META_SCOPE,
                                                  SELECT_SCOPE, STATE_SCOPE,
                                                  named_step)
from siddhi_tpu.observability.tracing import span
from siddhi_tpu.ops.compact import compact_columns, compact_width
from siddhi_tpu.ops.expressions import (PADDED_KEY, PK_KEY, TS_KEY, TYPE_KEY,
                                        VALID_KEY)
from siddhi_tpu.ops.nfa import NFAStage
from siddhi_tpu.query_api.definitions import StreamDefinition


def _nfa_meta(out: dict, new_nfa: dict, ins_on: bool) -> dict:
    """Append the ``nfa_runs`` instrument lane (live partial-match
    slots) behind the packed meta prefix — computed from state the step
    already holds (``observability/instruments.py``); inert when the
    ``profile_device_instruments`` knob is off."""
    if ins_on:
        out["__meta__"] = jnp.concatenate(
            [out["__meta__"],
             jnp.sum(new_nfa["active"], dtype=jnp.int64).reshape(1)])
    return out


class StreamProxy(Receiver):
    """Per-input-stream junction subscriber for one NFA query (the role of
    PatternSingle/SequenceSingleProcessStreamReceiver)."""

    def __init__(self, runtime: "NFAQueryRuntime", stream_id: str,
                 definition: StreamDefinition):
        self.runtime = runtime
        self.stream_id = stream_id
        self.definition = definition

    def receive(self, events: List[Event]):
        batch = HostBatch.from_events(
            events, self.definition, self.runtime.dictionary,
            pool=pack_pool_of(self.runtime.app_context))
        self.runtime.process_stream_batch(self.stream_id, batch)

    def receive_batch(self, batch: HostBatch, junction=None):
        self.runtime.process_stream_batch(self.stream_id, batch,
                                          junction=junction)


class NFAQueryRuntime(QueryRuntime):
    def is_stateful(self) -> bool:
        # window/NFA state is always snapshot-relevant
        return True

    def __init__(
        self,
        name: str,
        app_context,
        stage: NFAStage,
        input_defs: Dict[str, StreamDefinition],
        stream_keyers: Dict[str, object],
        selector_plan,
        dictionary,
        partition_ctx=None,
        out_keyer=None,
    ):
        super().__init__(
            name=name,
            app_context=app_context,
            input_definition=None,
            filters=[],
            window_stage=None,
            selector_plan=selector_plan,
            keyer=out_keyer,          # group-by over capture columns
            dictionary=dictionary,
            partition_ctx=partition_ctx,
        )
        self.stage = stage
        self.input_defs = input_defs
        self.stream_keyers = stream_keyers  # stream id -> partition keyer|None
        self._steps: Dict[object, object] = {}
        self._timer_step = None
        self._sel_step = None
        # host mirror of the PER-KEY event-time high-water marks (fast
        # two-step kernel dispatch — see _host_hard_batch; per-key because
        # the generic engine's `_expire` only advances the clock of each
        # row's own key); persisted with snapshots so restored state
        # cannot be resurrected by replays
        self._nfa_hwm_arr = None
        self._expire_step = None
        # one stable callback object: Scheduler dedups on (id(target), ts),
        # a fresh bound method per notify_at would defeat it
        self._timer_cb = self.process_timer

    # -------------------------------------------------------------- wiring

    def make_proxies(self) -> Dict[str, StreamProxy]:
        return {
            sid: StreamProxy(self, sid, self.input_defs[sid])
            for sid in self.stage.plan.stream_ids
        }

    # --------------------------------------------------------------- state

    def _init_state(self) -> dict:
        return {
            "sel": self.selector_plan.init_state(),
            "nfa": self.stage.init_state(self._win_keys),
        }

    def _ensure_capacity(self):
        before = (self.selector_plan.num_keys, self._win_keys)
        super()._ensure_capacity()
        if (self.selector_plan.num_keys, self._win_keys) != before:
            self._steps.clear()
            self._timer_step = None

    def _step_instrument_slots(self):
        """Every NFA step (per-stream and timer sweep) appends the live
        active-run count — see ``_nfa_meta``."""
        from siddhi_tpu.observability.instruments import Slot

        if not self._instruments_on():
            return []
        return [Slot("nfa_runs")]

    def _instrument_capacity(self, name):
        if name == "nfa_runs":
            return float(self._win_keys * self.stage.plan.slots)
        return super()._instrument_capacity(name)

    def arm_initial(self):
        """Arm key 0's head wait at app start (reference: absent pre-state
        processors schedule their first deadline when the runtime starts —
        ``AbsentStreamPreStateProcessor.java`` partitionCreated/start).

        Playback timelines have no wall origin, so the wait is anchored at
        the app clock's FIRST value instead (the playback analog of
        runtime-start wall time), via a one-shot time-change listener —
        the first event on ANY stream starts the quiet window. Anchoring
        at t=0 would let a successor at any realistic epoch timestamp sail
        past the deadline without any quiet period elapsing
        (AbsentPatternTestCase q7/q27)."""
        plan = self.stage.plan
        arm_j = plan.arm_step()
        if arm_j is None or self.partition_ctx is not None:
            return
        if self.app_context.playback:
            tsg = self.app_context.timestamp_generator
            tsg.once_first_time(lambda ts: self._arm_at(int(ts)))
            return
        self._arm_at(int(self.app_context.timestamp_generator.current_time()))

    def _arm_at(self, now: int):
        plan = self.stage.plan
        arm_j = plan.arm_step()
        with self._lock:
            if self._state is None:
                self._state = self._init_state()
            nfa = {k: np.asarray(v) for k, v in self._state["nfa"].items()}
            if nfa["armed"][0]:
                return
            nfa["armed"] = nfa["armed"].copy()
            nfa["armed"][0] = True
            nfa["active"] = nfa["active"].copy()
            nfa["active"][0, 0] = True
            nfa["stepi"] = nfa["stepi"].copy()
            nfa["stepi"][0, 0] = arm_j
            nfa["sts"] = nfa["sts"].copy()
            st = plan.steps[arm_j]
            # capture-less armed head: `within` anchors at the first
            # CAPTURE (T0 sentinel min()ed down there — ops/nfa._T0_FAR)
            from siddhi_tpu.ops.nfa import _T0_FAR

            capless = all(s.capture is None for s in st.sides)
            nfa["sts"][0, 0] = int(_T0_FAR) if capless else now
            next_dl = None
            if st.kind == "absent":
                nfa["adl"] = nfa["adl"].copy()
                nfa["adl"][0, 0] = now + st.wait_ms
                next_dl = now + st.wait_ms
            else:
                for side in st.sides:
                    if side.absent and side.wait_ms is not None:
                        key = "adl" if side.bit == 1 else "adl2"
                        nfa[key] = nfa[key].copy()
                        nfa[key][0, 0] = now + side.wait_ms
                        dl = now + side.wait_ms
                        next_dl = dl if next_dl is None else min(next_dl, dl)
            # scopes starting at the armed (capture-less) wait do NOT start
            # counting here — `within` measures across captured events
            # (see NFAStage._start_capture_scopes)
            self._state["nfa"] = {k: jnp.asarray(v) for k, v in nfa.items()}
        if next_dl is not None and self.scheduler is not None:
            self.scheduler.notify_at(int(next_dl), self._timer_cb)

    # ---------------------------------------------------------- step builds

    def build_stream_step_fn(self, stream_id: str, force_generic: bool = False,
                             compact: bool = True):
        """Pure (state, cols, now) -> (state', out) for one input stream —
        the NFA transition fused with the selector stage (unless a host
        group-by keyer has to run between them). ``force_generic`` builds
        the serial-engine variant the host dispatches to when a batch's
        timestamps are hostile to the fast kernel (see
        ``process_stream_batch``); an in-graph ``lax.cond`` would instead
        break buffer donation (XLA copies the whole [K, S] state through
        conditionals — measured 11 big copies/step). ``compact``: the
        output's columns are its valid rows compacted (see
        ``_select_and_meta_fn``)."""
        stage = self.stage
        _select_and_meta = self._select_and_meta_fn()
        # a GSPMD-sharded step keeps the padded pull: a compaction across a
        # sharded axis would bring collectives nobody has measured
        compact = compact and self._shard_mesh is None

        def step(state, cols, current_time):
            from siddhi_tpu.core.plan.selector_plan import STR_RANK

            ctx = {"xp": jnp, "current_time": current_time}
            cols = dict(cols)
            strrank = cols.pop(STR_RANK, None)   # selector-only side input
            with jax.named_scope(STATE_SCOPE):
                if force_generic:
                    new_nfa, out_cols = stage._apply_stream_generic(
                        stream_id, state["nfa"], cols, ctx)
                else:
                    new_nfa, out_cols = stage.apply_stream(
                        stream_id, state["nfa"], cols, ctx)
            out_cols = dict(out_cols)
            overflow = out_cols.pop("__overflow__", None)
            notify = out_cols.pop("__notify__", None)
            if strrank is not None:
                out_cols[STR_RANK] = strrank
            return _select_and_meta(
                state, new_nfa, out_cols, overflow, notify, ctx,
                batch_rows=cols[VALID_KEY].shape[0] if compact else None)

        return step

    def _select_and_meta_fn(self):
        """The tail every NFA step shares (stream and timer): the
        selector over the stage's emissions, unless a host group-by keyer
        has to run between them, then the packed meta with the
        ``nfa_runs`` lane.

        Given the input batch's ``batch_rows``, the output's columns are
        its valid rows compacted to a width that follows from the batch's
        (``ops/compact.py``): the same rows in the same order (event order,
        then slot order), ``__valid__`` the first ``count`` positions. The
        columns at their padded width ride beside them under
        ``PADDED_KEY``, and the host swaps those in whenever the meta's
        count does not fit (``LazyColumns.choose``). Not engaged, on
        purpose: the timer step (no batch), the split-keyer path (the host
        keyer reads the NFA's output at once), a GSPMD-sharded step
        (``build_stream_step_fn``), and wherever the width would not be
        well under the padded one (small ``nfa_slots``). Their output is
        the padded columns alone."""
        sel = self.selector_plan
        split = self.keyer is not None
        ins_on = self._instruments_on()

        def tail(state, new_nfa, out_cols, overflow, notify, ctx,
                 batch_rows=None):
            padded = None
            if split:
                out, new_sel = out_cols, state["sel"]
                out["__overflow__"] = overflow
                out["__notify__"] = notify
            else:
                with jax.named_scope(SELECT_SCOPE):
                    new_sel, out = sel.apply(state["sel"], out_cols, ctx)
                rows = out[VALID_KEY].shape[0]
                width = (compact_width(batch_rows, rows)
                         if batch_rows is not None else None)
                if width is not None:
                    # the columns: what is as long as the valid mask; the
                    # rest (the selector's 0-d overflow flag) is the meta's
                    padded = {k: v for k, v in out.items()
                              if jnp.ndim(v) >= 1 and v.shape[0] == rows}
                    with jax.named_scope(COMPACT_SCOPE):
                        twins = compact_columns(padded, width)
                if overflow is not None:
                    out["__overflow__"] = overflow
                if notify is not None:
                    out["__notify__"] = notify
            with jax.named_scope(META_SCOPE):
                out = _nfa_meta(pack_meta(out), new_nfa, ins_on)
            if padded is not None:
                # the meta counted the padded mask: it says more than the
                # width where the rows do not fit
                out = {**{k: v for k, v in out.items() if k not in padded},
                       **twins, PADDED_KEY: padded}
            return {"nfa": new_nfa, "sel": new_sel}, out

        return tail

    def build_timer_step_fn(self):
        stage = self.stage
        _select_and_meta = self._select_and_meta_fn()

        def step(state, now):
            ctx = {"xp": jnp, "current_time": now}
            with jax.named_scope(STATE_SCOPE):
                new_nfa, out_cols = stage.apply_timer(state["nfa"], now, ctx)
            out_cols = dict(out_cols)
            overflow = out_cols.pop("__overflow__", None)
            notify = out_cols.pop("__notify__", None)
            return _select_and_meta(state, new_nfa, out_cols, overflow,
                                    notify, ctx)

        return step

    def build_step_fn(self):
        # single-step export (driver compile checks, and what
        # shard_query_step jits with the mesh's shardings): first stream's
        # step, its output padded only
        return self.build_stream_step_fn(self.stage.plan.stream_ids[0],
                                         compact=False)

    # ----------------------------------------------------------- processing

    def process_stream_batch(self, stream_id: str, batch: HostBatch,
                             junction=None):
        with span("query.step", batch=journey.batch_of(batch),
                  query=self.name, stream=stream_id), self._lock:
            from siddhi_tpu.core.stream.junction import \
                current_delivering_junction

            j = junction or current_delivering_junction()
            self._cur_junction = j
            self._cur_fault_batch = batch if (
                j is not None and j.on_error_action == "STREAM"
                and j.fault_junction is not None) else None
            # batch-journey, as QueryRuntime.process_batch: fork the pack
            # stamp, open the dispatch stage; _run_nfa_step consumes it
            jr = self._cur_journey = journey.begin(batch) \
                if journey.enabled() else None
            cols = batch.cols
            partitioned = self.partition_ctx is not None
            with journey.keying(jr, self.name, batch.capacity,
                                self._needed_sel_keys):
                if partitioned:
                    keyer = self.stream_keyers.get(stream_id)
                    if keyer is not None:
                        cols, pk = keyer.apply(cols)
                        cols[PK_KEY] = np.asarray(pk, np.int32)
                    else:
                        cols[PK_KEY] = np.zeros(batch.capacity, np.int32)
                    cols[GK_KEY] = cols[PK_KEY]
                    self._ensure_capacity()
                else:
                    cols[GK_KEY] = np.zeros(cols[VALID_KEY].shape[0],
                                            np.int32)
            if self._state is None:
                self._state = self._init_state()
            force_generic = self._host_hard_batch(stream_id, cols)
            jit_key = (f"query.{self.name}.nfa.{stream_id}"
                       + (".generic" if force_generic else ""))
            step = self._steps.get((stream_id, force_generic))
            if step is None:
                fn = named_step(
                    self.build_stream_step_fn(stream_id,
                                              force_generic=force_generic),
                    f"nfa_step_{stream_id}"
                    + ("_generic" if force_generic else ""))
                if self._shard_mesh is not None:
                    from siddhi_tpu.parallel.mesh import sharded_jit_for

                    step = sharded_jit_for(self, fn, n_plain_args=2)
                else:
                    step = jax.jit(fn, donate_argnums=0)
                # cache_extra: wrapper shardings are invisible in the
                # traced program — a mesh-sharded NFA step must never
                # alias an unsharded one with an equal jaxpr
                step = self.app_context.telemetry.instrument_jit(
                    step, jit_key, family="nfa_step",
                    cache_extra=str(self._shard_mesh or ""))
                self._steps[(stream_id, force_generic)] = step
            else:
                self.app_context.telemetry.record_jit(jit_key, hit=True)
            jcols = dict(cols) if isinstance(cols, LazyColumns) else cols
            if self.selector_plan.needs_str_rank:
                from siddhi_tpu.core.plan.selector_plan import STR_RANK

                jcols[STR_RANK] = self.dictionary.rank_table()
            notify = self._run_nfa_step(lambda: launch_step(
                step, self._state, jcols,
                np.int64(self.app_context.timestamp_generator.current_time()),
                query=self.name, jr=jr))
        if notify is not None and self.scheduler is not None:
            self.scheduler.notify_at(notify, self._timer_cb)

    def _host_hard_batch(self, stream_id: str, cols) -> bool:
        """Host-side dispatch between the fast two-step kernel and the
        serial engine, decided from timestamps alone (VERDICT r05: an
        in-graph lax.cond breaks state donation — 11 full-state copies
        per step). Hard conditions, each a conservative
        over-approximation:
        - out-of-order timestamps (below the row's key's high-water mark,
          or decreasing in-batch): the fast kernel's lazy `within` expiry
          is exact only for monotone feeds;
        - head batches where one key's rows span several timestamps: a
          `within` deadline could cross inside the batch and re-order the
          free-slot list between same-key arming rows.
        When a batch is hard, the PER-KEY physical expiry clears the
        generic engine would already have made are applied first
        (`expire_to` — per key because `_expire` only advances each row's
        own key's clock)."""
        stage = self.stage
        side_kind = (stage._fast_side(stream_id)
                     if stage.fast_enabled else None)
        if side_kind is None or stage.plan.within is None:
            return side_kind is None  # ineligible plans: generic always
        raw_ts = dict.__getitem__(cols, TS_KEY) if TS_KEY in cols else None
        if not isinstance(raw_ts, np.ndarray):
            # device-resident (chained-query) batch: reading timestamps
            # here would force a device->host pull per batch, and
            # without host timestamps the high-water
            # marks cannot be maintained soundly — retire the fast path
            # for this runtime
            stage.fast_enabled = False
            self._steps.clear()
            return True
        ts = raw_ts
        valid = np.asarray(cols[VALID_KEY]) & (
            np.asarray(cols[TYPE_KEY]) == 0)
        tsv = ts[valid]
        if tsv.size == 0:
            return False
        K = self._win_keys
        arr = self._nfa_hwm_arr
        if arr is None or arr.shape[0] < K:
            grown = np.full(K, -(2 ** 62), np.int64)
            if arr is not None:
                grown[: arr.shape[0]] = arr
            self._nfa_hwm_arr = arr = grown
        pk = (np.asarray(cols[PK_KEY], np.int64) if PK_KEY in cols
              else np.zeros(ts.shape[0], np.int64))
        pkv = np.clip(pk[valid], 0, K - 1)
        hard = bool(np.any(tsv < arr[pkv])) or bool(
            np.any(np.diff(tsv) < 0))
        if not hard and side_kind == "head" and tsv.min() != tsv.max():
            order = np.argsort(pkv, kind="stable")
            same = pkv[order][1:] == pkv[order][:-1]
            hard = bool(np.any(same & (np.diff(tsv[order]) != 0)))
        if hard:
            # apply the generic engine's per-key physical expiry clears
            # before falling back (donation-safe: state replaced wholesale)
            if self._expire_step is None:
                self._expire_step = jax.jit(self.stage.expire_to,
                                            donate_argnums=0)
            self._state = dict(self._state)
            self._state["nfa"] = self._expire_step(
                self._state["nfa"], arr)
        if tsv[0] == tsv[-1] and tsv.min() == tsv.max():
            # single-timestamp batch (the steady-state shape): duplicate
            # keys all write the same value, so plain fancy assignment
            # replaces the much slower unbuffered np.maximum.at
            arr[pkv] = np.maximum(arr[pkv], tsv[0])
        else:
            np.maximum.at(arr, pkv, tsv)
        return hard

    def process_timer(self, ts: int):
        with self._lock:
            # drain in-flight pipelined batches first: the deadline sweep
            # must observe a fully-emitted timeline (and runs sync itself)
            pump = getattr(self.app_context, "completion_pump", None)
            if pump is not None and pump.has_pending:
                pump.flush_owner(self)
            if self._state is None:
                self._state = self._init_state()
            jr = self._cur_journey = journey.begin() \
                if journey.enabled() else None
            if self._timer_step is None:
                fn = named_step(self.build_timer_step_fn(), "nfa_timer")
                if self._shard_mesh is not None:
                    from siddhi_tpu.parallel.mesh import sharded_jit_for

                    self._timer_step = sharded_jit_for(self, fn, n_plain_args=1)
                else:
                    self._timer_step = jax.jit(fn, donate_argnums=0)
                self._timer_step = self.app_context.telemetry.instrument_jit(
                    self._timer_step, f"query.{self.name}.nfa.timer",
                    family="nfa_timer",
                    cache_extra=str(self._shard_mesh or ""))
            notify = self._run_nfa_step(
                lambda: launch_step(self._timer_step, self._state,
                                    np.int64(ts), query=self.name, jr=jr),
                allow_pipeline=False)
        if notify is not None and self.scheduler is not None:
            self.scheduler.notify_at(notify, self._timer_cb)

    def _run_nfa_step(self, run, allow_pipeline: bool = True) -> int | None:
        """Run a jitted NFA step; when a group-by keyer splits the pipeline,
        key the NFA emissions host-side and run the selector step after.
        Overflow/notify/size arrive packed in __meta__ — one pull."""
        from siddhi_tpu.core.util.statistics import latency_t0, record_elapsed_ms

        sm = self.app_context.statistics_manager
        t0 = latency_t0(sm)
        jr = self._cur_journey
        self._cur_journey = None
        self._state, out = run()
        if jr is not None:
            jr.end_dispatch()
        out_host = LazyColumns(out)
        size_hint = None
        # raw device ref — LazyColumns.pop would PULL it (one ~70ms round
        # trip), defeating the defer batching below
        meta = (dict.__getitem__(out_host, "__meta__")
                if "__meta__" in out_host else None)
        if meta is not None:
            pump = getattr(self.app_context, "completion_pump", None)
            if (allow_pipeline and pump is not None and pump.depth > 1
                    and self.keyer is None):
                # pipelined dispatch (completion.py). Unlike defer_meta,
                # waitish (absent-deadline) plans are ELIGIBLE: the pump
                # delivers __notify__ promptly at drain (sync sends flush
                # before returning). The split-keyer path stays sync —
                # it needs the NFA outputs host-side immediately.
                from siddhi_tpu.core.query.completion import QueryCompletion

                record_elapsed_ms(sm, self.name, t0)
                pump.submit(QueryCompletion(
                    self, out_host,
                    "pattern match-slot capacity exceeded — raise "
                    "app_context.nfa_slots",
                    junction=self._cur_junction,
                    batch=getattr(self, "_cur_fault_batch", None),
                    journey=jr))
                return None
            defer = getattr(self.app_context, "defer_meta", 1)
            if defer > 1 and self.keyer is None and not any(
                    st.waitish for st in self.stage.plan.steps):
                # batch N step metas into ONE round trip; absent
                # deadlines need prompt notifies, so
                # only wait-free plans defer (dispatch-side latency only —
                # emission is deferred)
                record_elapsed_ms(sm, self.name, t0)
                if jr is not None:
                    # legacy hold-N path, as in _finish_device_batch: the
                    # deferred drain is not instrumented
                    jr.finish(self.app_context, (self.name,))
                self._deferred.append((
                    out_host,
                    "pattern match-slot capacity exceeded — raise "
                    "app_context.nfa_slots"))
                if len(self._deferred) < defer:
                    return None
                return self.flush_deferred()
            dict.pop(out_host, "__meta__")
            if jr is not None:
                jr.pre_drain(journey.ready_of(meta))
            meta = self._pull_meta(meta, jr)
            self.decode_meta_suffix(meta)
            overflow, notify, size_hint = int(meta[0]), int(meta[1]), int(meta[2])
        else:
            ovf = out_host.pop("__overflow__", None)
            overflow = int(ovf) if ovf is not None else 0
            nt = out_host.pop("__notify__", None)
            notify = int(nt) if nt is not None else -1
        if overflow > 0:
            raise FatalQueryError(
                f"query '{self.name}': pattern match-slot capacity exceeded — "
                f"raise app_context.nfa_slots before creating the runtime"
            )
        record_elapsed_ms(sm, self.name, t0)
        if self.keyer is not None:
            out_host.pop("__overflow__", None)
            out_host.pop("__notify__", None)
            out_host = self._host_keyed_select(out_host, jr)
            size_hint = None
        self._timed_emit(self._host_batch(out_host, size_hint), jr,
                         rows_out=size_hint)
        if notify >= 0:
            return notify
        return None

    def receive(self, events: List[Event]):  # pragma: no cover — proxies only
        raise RuntimeError("NFA queries receive through per-stream proxies")
