"""FusedFanoutRuntime: one device dispatch per junction batch, not one
per query.

The junction delivers each batch to its receivers sequentially; before
this layer every subscribed ``QueryRuntime`` ran its own host pack, its
own group keyer, its own jitted step and its own ``__meta__`` pull — N
queries on one stream paid N device dispatches and N device->host round
trips per batch (the cost of a pull on a co-located chip: not measured). A
fused group subscribes ONE receiver in the members' place: the shared
packed batch feeds a single ``jax.jit`` step whose state is the tuple of
the members' state pytrees and whose output packs every member's columns
plus one combined ``[N, 3]`` ``__meta__`` — one dispatch and one meta
round trip per batch regardless of N.

Reference semantics are preserved per member:

- **subscription-order emission** — members emit in the order they
  subscribed (the group occupies the first member's receiver slot, so
  ordering against callbacks/sinks is unchanged);
- **state identity** — each member keeps its own ``_state`` pytree under
  its own name/lock, so snapshot capture/restore keys are exactly the
  unfused layout (pre-fusion revisions restore into a fused runtime and
  vice versa);
- **per-member error attribution** — a member's capacity overflow raises
  a ``FatalQueryError`` naming that query and its knob
  (``QueryRuntime.overflow_knob_msg``); under ``@OnError(action=
  'stream')`` only that member's failure is routed to the fault stream
  and the other members' outputs for the same batch are emitted
  normally (an upgrade over the unfused path, where the first fatal
  receiver starves the rest of the delivery loop);
- **group-key dedup** — members whose group-by expressions match share
  one ``GroupKeyer`` object (``group by symbol`` runs once per batch for
  the whole group); the member's own keyer is stashed so a restore that
  brings divergent per-member maps un-shares them
  (``fanout_plan.keyer_signature``);
- **identical-program dedup** — members whose step PROGRAMS are provably
  identical (equal jaxpr text, equal embedded constants, equal output
  tree, same group-key slot) AND whose current states are bit-equal run
  as ONE computation in the fused module; every member of the cluster is
  handed the (immutable) result arrays. This is sound because an
  identical program over the identical junction history produces an
  identical state trajectory — the common multi-tenant fan-out (the
  same analytics per consumer) collapses from N× compute to 1×, which
  is the semantic-overlap sharing PAPERS.md describes, not just
  dispatch amortization. Members whose programs differ keep their own
  sub-computation inside the same module (one dispatch either way).

Telemetry: the fused step compiles under jit key
``fanout.<stream>.step`` with one cache hit recorded PER MEMBER per
dispatch (hits/compiles = query-batches amortized per compile), plus
``fanout.<stream>.dispatches`` / ``fanout.<stream>.meta_pulls``
counters and ``fanout.<stream>.group_size`` /
``fanout.<stream>.unique_programs`` gauges — exported as
``siddhi_fanout_*`` on ``GET /metrics``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.analysis.locks import make_lock
from siddhi_tpu.core.event import Event, HostBatch, LazyColumns, launch_step, pack_pool_of
from siddhi_tpu.observability import instruments, journey
from siddhi_tpu.observability.tracing import span
from siddhi_tpu.core.plan.selector_plan import GK_KEY, STR_RANK
from siddhi_tpu.core.stream.junction import FatalQueryError, Receiver
from siddhi_tpu.ops.expressions import VALID_KEY

_FGK = "__fgk{}__"   # per-slot shared group-key columns in the fused step


def _groups_of(junction) -> List["FusedFanoutRuntime"]:
    """Live fused groups subscribed to ``junction`` (an ineligible
    receiver mid-run can split one stream into two groups)."""
    return [r for r in junction.receivers
            if isinstance(r, FusedFanoutRuntime)]


def _same_program(a, b) -> bool:
    """Provably identical step programs: equal jaxpr text (deterministic
    variable naming, scalar literals inline), pairwise-equal embedded
    constants (closure-captured arrays are NOT in the text), and equal
    output tree/avals (catches output-name-only differences)."""
    a_str, a_consts, a_shape = a
    b_str, b_consts, b_shape = b
    if a_str != b_str:
        return False
    if len(a_consts) != len(b_consts):
        return False
    for x, y in zip(a_consts, b_consts):
        if not _values_equal(x, y):
            return False
    try:
        return (jax.tree_util.tree_structure(a_shape)
                == jax.tree_util.tree_structure(b_shape)
                and jax.tree_util.tree_leaves(a_shape)
                == jax.tree_util.tree_leaves(b_shape))
    except Exception:  # noqa: BLE001 — unequal on any doubt
        return False


def _values_equal(x, y) -> bool:
    try:
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        if np.issubdtype(x.dtype, np.floating):
            return bool(np.array_equal(x, y, equal_nan=True))
        return bool(np.array_equal(x, y))
    except Exception:  # noqa: BLE001 — unequal on any doubt
        return False


def _states_equal(sa, sb) -> bool:
    """Bit-equality of two state pytrees (same junction history + same
    program means same trajectory; this check makes the sharing
    assumption verified, not assumed — e.g. against states hand-mutated
    by tooling)."""
    if sa is sb:
        return True
    la, ta = jax.tree_util.tree_flatten(sa)
    lb, tb = jax.tree_util.tree_flatten(sb)
    if ta != tb or len(la) != len(lb):
        return False
    return all(_values_equal(x, y) for x, y in zip(la, lb))


class FusedFanoutRuntime(Receiver):
    def __init__(self, junction, members: List):
        self.junction = junction
        self.members = list(members)
        self.app_context = members[0].app_context
        self.stream_id = junction.definition.id
        self.input_definition = members[0].input_definition
        self.dictionary = members[0].dictionary
        self._needs_rank = any(m.selector_plan.needs_str_rank
                               for m in self.members)
        self._step = None
        self._sig = None          # (slots, per-member key capacities)
        self._clusters: List[List[int]] = []   # member idxs per computation
        self._cluster_of: List[int] = []       # member idx -> cluster idx
        self._lock = make_lock("owner")
        for m in self.members:
            m._fanout_group = self
        junction.replace_receivers(self.members, self)
        self.alias_keyers()
        # per-STREAM gauges aggregated over every live group on the
        # junction (a junction can host two groups when an ineligible
        # receiver splits the run): registration is idempotent and the
        # values are computed from the live receiver list, so a second
        # group's registration or a sibling's dissolve cannot corrupt them
        tel = self.app_context.telemetry
        tel.gauge(f"fanout.{self.stream_id}.group_size",
                  lambda j=junction: sum(len(g.members)
                                         for g in _groups_of(j)))
        tel.gauge(f"fanout.{self.stream_id}.unique_programs",
                  lambda j=junction: sum(len(g._clusters) or len(g.members)
                                         for g in _groups_of(j)))

    # ------------------------------------------------------ keyer sharing

    def alias_keyers(self):
        """Share one GroupKeyer across members with identical group-by
        expressions AND identical current maps (identical by construction
        on a fresh runtime; a restore may bring divergent maps, which
        stay private). The member's own keyer survives in ``_own_keyer``
        for restore to write into."""
        from siddhi_tpu.core.plan.fanout_plan import keyer_signature

        leaders = {}
        for m in self.members:
            if getattr(m, "_own_keyer", None) is None:
                m._own_keyer = m.keyer
            sig = keyer_signature(m)
            if sig is None or m.keyer is None:
                continue
            lead = leaders.get(sig)
            if lead is None:
                leaders[sig] = m
            elif (m.keyer._map == lead.keyer._map
                    and m.keyer._next == lead.keyer._next):
                m.keyer = lead.keyer
        self._step = None
        self._sig = None

    def on_restore(self):
        """Snapshot restore wrote each member's map into its OWN keyer
        (``snapshot.py``): re-derive sharing from the restored maps and
        drop the compiled step (key capacities/slot layout may differ)."""
        with self._lock:
            for m in self.members:
                own = getattr(m, "_own_keyer", None)
                if own is not None:
                    m.keyer = own
            self.alias_keyers()

    # --------------------------------------------------------- unwiring

    def release(self, member):
        """Hand one member back its own subscription (``parallel/mesh``
        sharding takes over its step). A first/last member splices out in
        place; releasing a MIDDLE member dissolves the whole group — the
        survivors' fused slot could not keep the released member between
        them, and subscription-order delivery outranks keeping the
        fusion. A group left with fewer than two members dissolves."""
        with self._lock:
            if member not in self.members:
                return
            idx = self.members.index(member)
            if 0 < idx < len(self.members) - 1:
                self.dissolve()
                return
            self.members.remove(member)
            self._restore_member(member, after_group=idx > 0)
            self._step = None
            self._sig = None
            if len(self.members) < 2:
                self.dissolve()

    def dissolve(self):
        """Unfuse entirely: members resume their own receiver slots in
        subscription order (used by ``SiddhiAppRuntime.debug()`` — the
        debugger instruments per-runtime delivery methods)."""
        with self._lock:
            recs = self.junction.receivers
            if self in recs:
                pos = recs.index(self)
                recs[pos:pos + 1] = list(self.members)
            for m in self.members:
                self._unalias(m)
            self.members = []
            if not _groups_of(self.junction):
                # last group on the stream: retire its metric surface
                tel = self.app_context.telemetry
                tel.remove_gauge(f"fanout.{self.stream_id}.group_size")
                tel.remove_gauge(f"fanout.{self.stream_id}.unique_programs")

    def _restore_member(self, member, after_group: bool):
        self._unalias(member)
        recs = self.junction.receivers
        if self in recs:
            pos = recs.index(self)
            recs.insert(pos + (1 if after_group else 0), member)

    @staticmethod
    def _unalias(member):
        member._fanout_group = None
        own = getattr(member, "_own_keyer", None)
        if own is not None:
            member.keyer = own
        if member._state is not None:
            # identical-program dedup may have the member sharing its
            # (immutable) state arrays with cluster siblings; the unfused
            # step donates its inputs, so a released member needs its own
            # buffers or its first donation deletes the siblings' state
            member._state = jax.tree_util.tree_map(
                lambda x: jnp.array(x), member._state)

    # ---------------------------------------------------------- receiving

    def receive(self, events: List[Event]):
        batch = HostBatch.from_events(
            events, self.input_definition, self.dictionary,
            pool=pack_pool_of(self.app_context))
        self.process_batch(batch)

    def receive_batch(self, batch: HostBatch, junction=None):
        from siddhi_tpu.core.query.runtime import backfill_null_masks

        backfill_null_masks(batch, self.input_definition)
        self.process_batch(batch, junction=junction)

    def process_batch(self, batch: HostBatch, junction=None):
        from siddhi_tpu.core.stream.junction import \
            current_delivering_junction

        if junction is None:
            junction = current_delivering_junction()
        with span("fanout.step", batch=journey.batch_of(batch),
                  stream=self.stream_id, members=len(self.members)):
            with self._lock, contextlib.ExitStack() as stack:
                # member locks in subscription order (snapshot takes them
                # one at a time — no cycle)
                for m in self.members:
                    stack.enter_context(m._lock)
                self._process_locked(batch, junction=junction)

    # ----------------------------------------------------------- internals

    def _now64(self) -> np.int64:
        return np.int64(
            int(self.app_context.timestamp_generator.current_time()))

    def _instruments_on(self) -> bool:
        from siddhi_tpu.observability import instruments

        return instruments.app_instruments_on(self.app_context)

    def _keys(self) -> int:
        """Group keys the members' keyers hold (one count a keyer)."""
        return sum(len(k) for k in {id(m.keyer): m.keyer
                                    for m in self.members
                                    if m.keyer is not None}.values())

    def _prepare(self, batch: HostBatch, jr=None):
        """Shared per-batch prep: group-key columns (deduplicated by
        keyer identity), per-member capacity/state, the fused input dict,
        and the fused step (re-jitted when the slot layout or any key
        capacity changed — rebuilds also re-derive the identical-program
        clusters). Returns ``(states, cols_dev)`` ready for
        ``self._step``, where ``states`` holds ONE pytree per cluster."""
        cols = batch.cols
        cap = dict.__getitem__(cols, VALID_KEY).shape[0]
        gk_cols: List[np.ndarray] = []
        slots: List[int] = []
        slot_of = {}
        with journey.keying(jr, f"fanout.{self.stream_id}", cap, self._keys):
            for m in self.members:
                kid = id(m.keyer) if m.keyer is not None else 0
                s = slot_of.get(kid)
                if s is None:
                    s = slot_of[kid] = len(gk_cols)
                    gk_cols.append(np.zeros(cap, np.int32)
                                   if m.keyer is None else m.keyer(cols))
                slots.append(s)
            for m in self.members:
                if m.keyer is not None:
                    m._ensure_capacity()
                if m._state is None:
                    m._state = m._init_state()
                prep = getattr(m, "prepare_cols", None)
                if prep is not None and prep(cols):
                    # a join side grew its partition directory: the
                    # member's state shapes changed under the same (slots,
                    # capacities) signature — drop the fused step so it
                    # re-jits
                    self._step = None
        cols_dev = dict(cols)   # jit boundary: raw (possibly device) arrays
        for s, gk in enumerate(gk_cols):
            cols_dev[_FGK.format(s)] = gk
        if self._needs_rank:
            cols_dev[STR_RANK] = self.dictionary.rank_table()
        sig = (tuple(slots), tuple((m.selector_plan.num_keys, m._win_keys)
                                   for m in self.members))
        if self._step is None or sig != self._sig:
            self._step = self._build_step(tuple(slots), len(gk_cols),
                                          cols_dev)
            self._sig = sig
        else:
            tel = self.app_context.telemetry
            for _m in self.members:   # member hit-counting: N query-batches
                tel.record_jit(f"fanout.{self.stream_id}.step", hit=True)
        return (tuple(self.members[c[0]]._state for c in self._clusters),
                cols_dev)

    def _build_step(self, slots: Tuple[int, ...], n_slots: int, cols_dev):
        """Compile the group's single step. Members are first partitioned
        into identical-program clusters (equal jaxpr text + embedded
        constants + output tree, same group-key slot, bit-equal current
        state): each cluster contributes ONE sub-computation whose result
        every cluster member shares — the semantic-overlap dedup — and
        distinct programs sit side by side in the same module."""
        member_fns = [m.build_step_fn() for m in self.members]
        gk_names = tuple(_FGK.format(s) for s in range(n_slots))
        gk_set = frozenset(gk_names)
        base_example = {k: v for k, v in cols_dev.items() if k not in gk_set}
        now = self._now64()

        programs = []
        for i, fn in enumerate(member_fns):
            mcols = dict(base_example)
            mcols[GK_KEY] = cols_dev[gk_names[slots[i]]]
            try:
                jaxpr, out_shape = jax.make_jaxpr(fn, return_shape=True)(
                    self.members[i]._state, mcols, now)
                programs.append((str(jaxpr.jaxpr), jaxpr.consts, out_shape))
            except Exception:  # noqa: BLE001 — tracing for dedup is
                programs.append(None)   # best-effort; None never clusters
        clusters: List[List[int]] = []
        for i in range(len(self.members)):
            placed = False
            for c in clusters:
                lead = c[0]
                if (slots[i] == slots[lead] and programs[i] is not None
                        and programs[lead] is not None
                        and _same_program(programs[i], programs[lead])
                        and _states_equal(self.members[i]._state,
                                          self.members[lead]._state)):
                    c.append(i)
                    placed = True
                    break
            if not placed:
                clusters.append([i])
        self._clusters = clusters
        self._cluster_of = [next(ci for ci, c in enumerate(clusters)
                                 if i in c)
                            for i in range(len(self.members))]
        # distinct leader state objects per cluster: a stale shared object
        # (from a pre-rebuild cluster that has since split) would be
        # donated twice in one call
        seen_ids = set()
        for c in clusters:
            lead = self.members[c[0]]
            if id(lead._state) in seen_ids:
                lead._state = jax.tree_util.tree_map(
                    lambda x: jnp.array(x), lead._state)
            seen_ids.add(id(lead._state))
        cluster_fns = [member_fns[c[0]] for c in clusters]
        cluster_slots = [slots[c[0]] for c in clusters]
        ins_on = self._instruments_on()

        def fused(states, cols, now):
            base = {k: v for k, v in cols.items() if k not in gk_set}
            new_states, outs, metas = [], [], []
            for ci, fn in enumerate(cluster_fns):
                mcols = dict(base)
                mcols[GK_KEY] = cols[gk_names[cluster_slots[ci]]]
                st, out = fn(states[ci], mcols, now)
                # instruments ON: per-member meta SUFFIXES (a join
                # side's sequence + partition fills, a window member's
                # ring fill) ride the stack, zero-padded to the widest
                # member so it stays rectangular — the drain decodes
                # each member's row by its own instrument spec. OFF:
                # [:3] strips them, today's [n, 3] layout bit-for-bit.
                meta = out.pop("__meta__")
                metas.append(meta if ins_on else meta[:3])
                new_states.append(st)
                outs.append(out)
            with jax.named_scope(instruments.META_SCOPE):
                width = max(m.shape[0] for m in metas)
                metas = [m if m.shape[0] == width else jnp.concatenate(
                    [m, jnp.zeros(width - m.shape[0], m.dtype)])
                    for m in metas]
                return tuple(new_states), (tuple(outs), jnp.stack(metas))

        jitted = jax.jit(instruments.named_step(fused, "fused_fanout"),
                         donate_argnums=0)
        return self.app_context.telemetry.instrument_jit(
            jitted, f"fanout.{self.stream_id}.step", family="fused_fanout")

    def _process_locked(self, batch: HostBatch, junction=None):
        from siddhi_tpu.core.util.statistics import (latency_t0,
                                                     record_elapsed_ms)

        members = self.members
        if not members:          # dissolved under a racing release
            return
        sm = self.app_context.statistics_manager
        tel = self.app_context.telemetry
        t0 = latency_t0(sm)
        # one journey per group batch: the shared dispatch/device stages
        # are recorded under EVERY member's name at finish
        jr = journey.begin(batch) if journey.enabled() else None
        states, cols_dev = self._prepare(batch, jr)
        new_states, (outs, metas) = launch_step(
            self._step, states, cols_dev, self._now64(),
            query=f"fanout.{self.stream_id}", jr=jr)
        if jr is not None:
            jr.end_dispatch()
        tel.count(f"fanout.{self.stream_id}.dispatches")
        for i, m in enumerate(members):
            # cluster members share the (immutable) result arrays
            m._state = new_states[self._cluster_of[i]]
        pump = getattr(self.app_context, "completion_pump", None)
        if pump is not None and pump.depth > 1:
            # pipelined: the whole group batch rides in flight; per-member
            # emission/attribution runs at drain (complete_entry). The
            # member list and cluster map are snapshotted — a release or
            # rebuild between dispatch and drain must not re-map outputs.
            from siddhi_tpu.core.query.completion import FusedCompletion

            for m in members:
                record_elapsed_ms(sm, m.name, t0)
            pump.submit(FusedCompletion(
                self, outs, metas, list(members), list(self._cluster_of),
                batch, junction=junction, journey=jr))
            return
        # ONE combined [n_clusters, 3] meta pull for the whole group — the
        # single device->host round trip this layer exists to amortize
        if jr is not None:
            jr.pre_drain(journey.ready_of(metas))
        with span("meta_pull", query=f"fanout.{self.stream_id}",
                  batch=jr.batch if jr is not None else None) as sp:
            metas_host = np.asarray(jax.device_get(metas))
        if jr is not None:
            jr.meta_pulled(sp.ms)
        tel.count(f"fanout.{self.stream_id}.meta_pulls")
        fatal = self._timed_emit_members(jr, list(members),
                                         list(self._cluster_of), outs,
                                         metas_host, batch, t0sm=t0)
        if fatal is not None:
            # surfaced AFTER every member emitted: the junction's
            # handle_error stores it so later sends re-raise, exactly as
            # an unfused member's fatal would
            raise fatal

    def complete_entry(self, entry, metas_host) -> Optional[Exception]:
        """Drain-side tail of a pipelined group batch (CompletionPump):
        per-member emission and fault attribution over the snapshotted
        member list. Returns the fatal (if any) for the pump's
        drain-then-raise instead of raising mid-round."""
        tel = self.app_context.telemetry
        tel.count(f"fanout.{self.stream_id}.meta_pulls")
        with self._lock, contextlib.ExitStack() as stack:
            for m in entry.members:
                stack.enter_context(m._lock)
            return self._timed_emit_members(
                entry.journey, entry.members, entry.cluster_of, entry.outs,
                np.asarray(metas_host), entry.batch, t0sm=None)

    def _timed_emit_members(self, jr, members, cluster_of, outs, metas_host,
                            batch, t0sm) -> Optional[Exception]:
        """``_emit_members`` inside the group journey's emit stage: one
        ``siddhi.emit`` span for the group batch, recorded under every
        member's name; ``rows_out`` is the members' row counts summed."""
        if jr is None:
            return self._emit_members(members, cluster_of, outs, metas_host,
                                      batch, t0sm)
        rows_out = sum(int(metas_host[c][2]) for c in cluster_of)
        with jr.emitting(self.app_context, tuple(m.name for m in members),
                         rows_out):
            return self._emit_members(members, cluster_of, outs, metas_host,
                                      batch, t0sm)

    def _emit_members(self, members, cluster_of, outs, metas_host, batch,
                      t0sm) -> Optional[Exception]:
        from siddhi_tpu.core.util.statistics import record_elapsed_ms

        sm = self.app_context.statistics_manager
        fatal: Optional[Exception] = None
        for i, m in enumerate(members):
            row = metas_host[cluster_of[i]]
            overflow, notify, size = int(row[0]), int(row[1]), int(row[2])
            try:
                if row.shape[0] > 3:
                    # per-member instrument suffix (zero-padded to the
                    # stack width): each member decodes its own spec —
                    # device.<q>.<slot> telemetry, join seq (self-
                    # skipping inside a fused group)
                    decode = getattr(m, "decode_meta_suffix", None)
                    if decode is not None:
                        decode(row)
                if overflow > 0:
                    raise FatalQueryError(
                        f"query '{m.name}': {m.overflow_knob_msg(overflow)} "
                        f"before creating the runtime")
                if t0sm is not None:   # pipelined path recorded at dispatch
                    record_elapsed_ms(sm, m.name, t0sm)
                # own LazyColumns wrapper per member over the shared
                # arrays: materialization/mutation must not leak across
                m._emit(HostBatch(LazyColumns(outs[cluster_of[i]]),
                                  size=size))
                if notify >= 0 and m.scheduler is not None:
                    # defensive: eligible members carry no scheduler-driven
                    # window, so this timer re-entry (which would run the
                    # member's own unfused step) should never arm
                    m.scheduler.notify_at(notify, m.process_timer)
            except Exception as e:  # noqa: BLE001 — per-member attribution
                fatal = self._route_member_error(m, batch, e, fatal)
        return fatal

    def _route_member_error(self, member, batch: HostBatch, e: Exception,
                            fatal: Optional[Exception]):
        """Per-member fault attribution: framework failures route to the
        fault stream when @OnError(action='stream') is configured —
        naming ONLY the failing member — else they re-raise to the
        sender after the other members emitted; per-event processing
        errors take the junction's reference routing (route or
        log-and-drop)."""
        from siddhi_tpu.ops.expressions import CompileError

        j = self.junction
        if isinstance(e, (FatalQueryError, CompileError)):
            if j.on_error_action == "STREAM" and j.fault_junction is not None:
                j.route_fault_events(j.decode_events(batch), e)
                return fatal
            return fatal if fatal is not None else e
        try:
            j.handle_error(j.decode_events(batch), e)
        except Exception as raised:  # noqa: BLE001 — handle_error re-raises
            return fatal if fatal is not None else raised  # fatals only
        return fatal

    # ------------------------------------------------------------ tooling

    def lower_hlo_text(self, batch: HostBatch) -> str:
        """Lower the fused step for ``batch`` and return its optimized
        HLO — ONE module containing every member's computation
        (``tools/hlo_audit.py`` asserts exactly that)."""
        with self._lock, contextlib.ExitStack() as stack:
            for m in self.members:
                stack.enter_context(m._lock)
            states, cols_dev = self._prepare(batch)
            return self._step.lower(
                states, cols_dev, self._now64()).compile().as_text()
