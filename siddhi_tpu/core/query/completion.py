"""CompletionPump: a depth-bounded software pipeline for device batches.

PR 3 collapsed N device dispatches per junction batch into one, but every
batch still ended in a synchronous ``__meta__`` pull
(``runtime._finish_device_batch``): the host pack of batch k+1 could not
start until the device->host round trip of batch k completed (its
cost on a co-located chip: not measured), so the engine ran at
``pack + step + pull`` instead of ``max(pack, step)``. The static
``defer_meta`` hold-N-then-flush queue attacked only the pull count, was
opt-in, lagged emission by a full window under trickle load, and excluded
joins and scheduler-driven windows entirely.

The pump replaces both. A query step dispatches (JAX dispatch is already
asynchronous) and hands its device output plus the RAW ``__meta__`` ref
to the per-app pump; up to ``pipeline_depth`` batches per query ride in
flight while the producer packs the next batch ("Scaling Ordered Stream
Processing on Shared-Memory Multicores", PAPERS.md: ordered emission is
compatible with out-of-order/pipelined execution). Depth 1 is exactly
today's synchronous behavior (the runtimes bypass the pump).

Contract:

- **Per-owner dispatch order.** Each owner (a ``QueryRuntime`` or a
  ``FusedFanoutRuntime`` group) has a FIFO of in-flight completions;
  drains pop strictly from the head, so emission order per query always
  equals dispatch order. No ordering is promised ACROSS queries (the
  reference's @Async path never promised one either).
- **Batched drain rounds.** A drain pulls every popped entry's meta in
  ONE ``jax.device_get`` (or one bounded ``guarded_pull`` when the owner
  is sharded and ``cluster_step_timeout`` is set, so a dead peer still
  surfaces as a labeled ``ClusterPeerError``) — the metas-per-pull ratio
  is exported on ``/metrics``.
- **Overflow surfaces on the producer's next send.** A capacity overflow
  discovered at drain raises ``FatalQueryError`` out of whoever drained:
  the producer's own submit/flush (sync sends), or the @Async worker's
  idle flush — where the junction's ``_fatal`` pattern makes every later
  send re-raise. Drain-then-raise: the other entries of the round still
  emit; the overflowed batch itself is NOT emitted (matching the
  synchronous path's raise-before-emit).
- **Prompt completion.** Sync junction sends flush the pump before
  returning (synchronous semantics preserved — tests and single-shot
  sends observe their outputs immediately); @Async workers flush when
  their queue goes idle and on exit, bounding emission lag under trickle
  load to one idle poll — this is what lets scheduler-driven windows
  ride the pipeline (their ``__notify__`` wake times are delivered at
  drain, promptly) where ``defer_meta`` had to exclude them. Joins stay
  synchronous: their notify values are per SIDE and their two-sided
  state updates are order-coupled across streams (``join_runtime``).
- **Completion latency feedback.** Each entry remembers the delivering
  junction; at drain the TRUE pack->emit latency (not just the dispatch
  slice) feeds ``junction.record_completion`` -> the ``latency.target``
  adaptive batching loop, so a slow device step shrinks the batch cap
  even though dispatch returns instantly.

Telemetry (exported as ``siddhi_pipeline_*`` on ``GET /metrics``):
``pipeline.<owner>.inflight`` gauges, ``pipeline.stalls`` (forced drains
that had to WAIT on an unready meta — the producer genuinely blocked),
``pipeline.metas`` / ``pipeline.pulls`` (batching ratio).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from siddhi_tpu.analysis.guards import guarded
from siddhi_tpu.analysis.locks import make_lock
from siddhi_tpu.core.stream.junction import FatalQueryError
from siddhi_tpu.observability import journey as journey_mod
from siddhi_tpu.observability.tracing import span

log = logging.getLogger(__name__)


class QueryCompletion:
    """One in-flight batch of a (single-stream / NFA / join) query
    runtime."""

    __slots__ = ("owner", "out", "overflow_msg", "junction", "batch",
                 "timer_cb", "t0", "wall", "tid", "journey")

    def __init__(self, owner, out, overflow_msg: str, junction=None,
                 batch=None, journey=None):
        self.owner = owner
        self.out = out                    # LazyColumns, __meta__ still inside
        self.overflow_msg = overflow_msg
        self.junction = junction          # delivering junction (or None)
        self.journey = journey            # batch-journey context (or None)
        # input batch, retained ONLY when the junction routes errors to a
        # fault stream (@OnError action='stream') — drain-time errors
        # must publish the failing events there, like the sync path
        self.batch = batch
        # per-SIDE notify attribution: a join batch's __notify__ must
        # re-arm the dispatching side's own timer callback, snapshotted
        # here at submit (the runtime's _cur_timer_cb is per-batch state)
        self.timer_cb = getattr(owner, "_cur_timer_cb", None)
        self.t0 = time.perf_counter()
        self.wall = time.monotonic()      # wedge detection (supervisor)
        self.tid = threading.get_ident()  # submitting thread (scoped flush)

    @property
    def label(self) -> str:
        return self.owner.name

    def meta_refs(self) -> list:
        return [dict.__getitem__(self.out, "__meta__")]

    def ready(self) -> bool:
        return _is_ready(self.meta_refs()[0])

    def complete(self, metas: list) -> Optional[Exception]:
        q = self.owner
        meta = np.asarray(metas[0])
        dict.pop(self.out, "__meta__")
        overflow, notify, size = int(meta[0]), int(meta[1]), int(meta[2])
        try:
            check = getattr(q, "decode_meta_suffix", None)
            if check is not None and len(meta) > 3:
                # instrument/structural suffix behind the standard
                # prefix (observability/instruments.py): data slots feed
                # device.<q>.<slot> telemetry; check slots (route
                # overflow, join seq) run their structural consumers —
                # an exchange overflow is fatal for this batch exactly
                # like a capacity overflow
                try:
                    check(meta, self.journey)
                except FatalQueryError as routed_err:
                    return routed_err
            if overflow > 0:
                # the overflowed batch's rows are clamped garbage —
                # matching the synchronous path, it does not emit (the
                # rest of the drain round still does: drain-then-raise).
                # Joins pass a CALLABLE decoding the overflow bitmask to
                # the exact knob (overflow_knob_msg convention).
                msg = (self.overflow_msg(overflow)
                       if callable(self.overflow_msg) else self.overflow_msg)
                return FatalQueryError(
                    f"query '{q.name}': {msg} before "
                    f"creating the runtime")
            # the owner's emit stage (siddhi.emit span + journey), as in
            # the synchronous tail
            q._timed_emit(q._host_batch(self.out, size), self.journey,
                          rows_out=size)
            if notify >= 0 and q.scheduler is not None:
                q.scheduler.notify_at(
                    notify, self.timer_cb
                    or getattr(q, "_timer_cb", q.process_timer))
            return None
        finally:
            if self.junction is not None:
                # recorded AFTER emit: the depth-1 _timed_deliver sample
                # covered decode/rate-limit/callbacks too, and an
                # emit-dominated workload must still shrink the cap
                self.junction.record_completion(
                    (time.perf_counter() - self.t0) * 1000.0)


class FusedCompletion:
    """One in-flight junction batch of a fused fan-out group: a single
    stacked ``[n_clusters, 3]`` meta covers every member; per-member
    emission/attribution runs in ``FusedFanoutRuntime.complete_entry``."""

    __slots__ = ("owner", "outs", "metas_ref", "members", "cluster_of",
                 "batch", "junction", "t0", "wall", "tid", "journey")

    def __init__(self, owner, outs, metas_ref, members, cluster_of, batch,
                 junction=None, journey=None):
        self.owner = owner
        self.outs = outs
        self.metas_ref = metas_ref
        self.members = members            # member list snapshot (ordering)
        self.cluster_of = cluster_of
        self.batch = batch                # input batch, for fault routing
        self.junction = junction
        self.journey = journey            # one journey for the group batch
        self.t0 = time.perf_counter()
        self.wall = time.monotonic()
        self.tid = threading.get_ident()  # submitting thread (scoped flush)

    @property
    def label(self) -> str:
        return f"fanout.{self.owner.stream_id}"

    def meta_refs(self) -> list:
        return [self.metas_ref]

    def ready(self) -> bool:
        return _is_ready(self.metas_ref)

    def complete(self, metas: list) -> Optional[Exception]:
        try:
            return self.owner.complete_entry(self, np.asarray(metas[0]))
        finally:
            if self.junction is not None:
                # after per-member emission — see QueryCompletion
                self.junction.record_completion(
                    (time.perf_counter() - self.t0) * 1000.0)


# numpy/unknown/deleted refs read as ready (never stalls) — shared with
# the journey's device-attribution pivot so the two probes cannot drift
_is_ready = journey_mod.ready_of


@guarded
class CompletionPump:
    """Per-app registry of in-flight device batches (one FIFO per owner).

    Thread contract: ``submit`` and ``flush_owner`` are called with the
    owner's ``_lock`` held (process_batch already holds it); ``flush``
    acquires each owner's lock itself. Lock order is always
    ``owner._lock`` -> ``pump._lock`` — the pump lock is never held
    across a device pull or an emit.
    """

    # `_n_pending` and `_submits_by_j` stay undeclared: both are
    # lock-free has-work/progress probes read from hot sync paths
    GUARDED_BY = {"_pending": "pump"}

    def __init__(self, app_context):
        self.app_context = app_context
        self._pending: Dict[object, deque] = {}
        self._lock = make_lock("pump")
        self._tls = threading.local()
        self._n_pending = 0       # cheap has-work probe for sync senders
        # monotonic submit counts PER DELIVERING JUNCTION: lets a worker
        # tell whether ITS delivery pipelined (and skip the near-zero
        # dispatch-slice _adapt sample) without a foreign stream's
        # concurrent submit suppressing an unrelated junction's sample
        self._submits_by_j: Dict[int, int] = {}
        self._gauged = set()

    # ------------------------------------------------------------- config

    @property
    def depth(self) -> int:
        return max(1, int(getattr(self.app_context, "pipeline_depth", 1)))

    @property
    def has_pending(self) -> bool:
        return self._n_pending > 0

    def submits_of(self, junction) -> int:
        """Monotonic count of entries this junction's deliveries have
        submitted (see ``StreamJunction._pump_submits``)."""
        return self._submits_by_j.get(id(junction), 0)

    def inflight(self, owner) -> int:
        with self._lock:
            dq = self._pending.get(owner)
            return len(dq) if dq is not None else 0

    @staticmethod
    def _label_of(owner) -> str:
        name = getattr(owner, "name", None)
        return name if name is not None else f"fanout.{owner.stream_id}"

    def _inflight_by_label(self, label: str) -> int:
        """Gauge backend: resolves owners by LABEL at scrape time, so a
        rebuilt owner under the same label (a fused group dissolved and
        re-formed) keeps feeding the same /metrics series — and no owner
        object is pinned by a gauge closure."""
        with self._lock:
            return sum(len(dq) for o, dq in self._pending.items()
                       if self._label_of(o) == label)

    def oldest_age_s(self) -> Optional[float]:
        """Age of the oldest in-flight entry (wedge detection: a meta
        that never arrives means the device/collective hung)."""
        with self._lock:
            oldest = None
            for dq in self._pending.values():
                if dq and (oldest is None or dq[0].wall < oldest):
                    oldest = dq[0].wall
        if oldest is None:
            return None
        return time.monotonic() - oldest

    # ------------------------------------------------------------- submit

    def submit(self, entry) -> None:
        """Hand a dispatched batch to the pipeline (owner lock held).

        Keeps at most ``depth`` batches of this owner in flight: when the
        new entry would exceed the bound, the older entries drain in one
        batched round (the newest keeps riding, so the producer can go
        straight back to packing). With overload quotas registered
        (``resilience/overload.py``) the app-wide ``pipeline_quota``
        additionally collapses each submitting owner to ONE riding entry
        while the app total exceeds it — bounding the steady-state total
        at ``max(quota, one per active query)`` instead of
        ``depth × N_queries`` (cross-owner drains are off-limits here:
        lock order is owner -> pump, and we hold only OUR owner's lock) —
        and each submit is a weighted-fair yield point so a flooded
        tenant's dispatches don't monopolize the device."""
        owner = entry.owner
        ctl = getattr(self.app_context, "overload", None)
        if ctl is not None:
            ctl.throttle(0)     # yield-only: usage is charged at delivery
        with self._lock:
            dq = self._pending.get(owner)
            if dq is None:
                dq = self._pending[owner] = deque()
                self._register_gauge(owner, entry.label)
            dq.append(entry)
            self._n_pending += 1
            j = getattr(entry, "junction", None)
            if j is not None:
                self._submits_by_j[id(j)] = \
                    self._submits_by_j.get(id(j), 0) + 1
            # per-thread count: flush() loops only while THIS thread's
            # own emit cascades keep producing new entries
            self._tls.submitted = getattr(self._tls, "submitted", 0) + 1
            over = len(dq) - self.depth
            pq = ctl.pipeline_quota if ctl is not None else None
            if pq is not None and over <= 0 and self._n_pending > pq:
                # app-wide quota: drain THIS owner's older entries (other
                # owners' locks cannot be taken here — their own submits
                # and flushes bound them the same way)
                over = 1
        if over > 0:
            # drain everything but the newest in ONE batched pull: the
            # oldest entries have had depth-1 pack cycles to complete, so
            # the producer rarely blocks, and the just-dispatched batch
            # keeps riding while the producer goes back to packing
            self._drain_owner(owner, keep_newest=1, forced=True)

    def _register_gauge(self, owner, label: str) -> None:
        if label in self._gauged:
            return
        self._gauged.add(label)
        tel = getattr(self.app_context, "telemetry", None)
        if tel is not None:
            tel.gauge(f"pipeline.{label}.inflight",
                      lambda lbl=label: self._inflight_by_label(lbl))

    # -------------------------------------------------------------- drain

    def _draining(self) -> set:
        s = getattr(self._tls, "draining", None)
        if s is None:
            s = self._tls.draining = set()
        return s

    def _drain_owner(self, owner, keep_newest: Optional[int],
                     forced: bool = False) -> None:
        """Pop entries from ``owner``'s FIFO head and complete them in
        order; the popped metas travel in ONE device pull. Caller holds
        ``owner._lock``. Re-entrant submits for the SAME owner (feedback
        topologies: a query emitting into its own input stream) must not
        drain past the in-progress round — they queue and the outer
        flush/drain picks them up."""
        draining = self._draining()
        if id(owner) in draining:
            return
        with self._lock:
            dq = self._pending.get(owner)
            if not dq:
                return
            n = len(dq) - (keep_newest or 0)
            if n <= 0:
                return
            take = [dq.popleft() for _ in range(n)]
            self._n_pending -= n
            if not dq:
                # an empty deque must not keep a released/dissolved owner
                # alive for the app's lifetime — re-submits re-key it
                del self._pending[owner]
        tel = getattr(self.app_context, "telemetry", None)
        if tel is not None:
            if forced and not take[0].ready():
                # the producer genuinely blocks on the device here — the
                # pipeline is too shallow for this pack/step ratio
                tel.count("pipeline.stalls")
            tel.count("pipeline.pulls")
            tel.count("pipeline.metas", len(take))
        draining.add(id(owner))
        try:
            refs = [r for e in take for r in e.meta_refs()]
            riding = [e for e in take if e.journey is not None]
            # device-stage pivot: is_ready BEFORE the blocking pull
            # tells whether the device was still busy for the ride
            # (service) or the output sat parked (slack) — journey.py
            for e in riding:
                e.journey.pre_drain(e.ready())
            try:
                # one batched round trip serves the whole round: the
                # span names the round's first batch, and each entry's
                # journey is attributed the round's pull
                with span("meta_pull", query=self._label_of(owner),
                          batch=riding[0].journey.batch if riding else None,
                          batches=len(take)) as sp:
                    metas = self._pull(owner, refs)
            except Exception as pull_err:  # noqa: BLE001 — dead peer etc.
                # the pull itself failed (a dead peer's ClusterPeerError
                # from guarded_pull): route it exactly like the old
                # synchronous _pull_meta raise inside a delivery —
                # through EVERY distinct delivering junction among the
                # popped entries (a multi-stream NFA's FIFO can mix
                # junctions), so each one's supervisor/_fatal machinery
                # sees it. The entries are lost either way:
                # ClusterPeerError is terminal for this runtime (see
                # parallel/distributed.guarded_pull).
                routed = False
                seen = set()
                for e in take:
                    jn = getattr(e, "junction", None)
                    if jn is None or id(jn) in seen:
                        continue
                    seen.add(id(jn))
                    routed = self._route_error(e, pull_err) or routed
                if not routed:
                    raise
                return
            for e in riding:
                e.journey.meta_pulled(sp.ms)
            errors: List[Exception] = []
            i = 0
            for e in take:
                k = len(e.meta_refs())
                try:
                    err = e.complete(metas[i:i + k])
                except Exception as raised:  # noqa: BLE001 — drain-then-raise
                    err = raised
                if err is not None:
                    # route through the entry's OWN delivering junction
                    # (fatals arm THAT junction's _fatal so ITS producers
                    # re-raise; peer failures notify the supervisor;
                    # others log-and-drop, exactly like the synchronous
                    # per-receiver delivery path) — the drain may have
                    # been triggered by an unrelated stream's send, whose
                    # junction must not absorb this error's attribution
                    if not self._route_error(e, err):
                        errors.append(err)
                i += k
            if errors:
                for extra in errors[1:]:
                    # drain-then-raise can only surface one exception to
                    # the caller; the rest must not vanish silently
                    log.error("pipeline drain: additional error "
                              "suppressed behind the raised one: %r", extra)
                raise errors[0]
        finally:
            draining.discard(id(owner))

    @staticmethod
    def _route_error(entry, err: Exception) -> bool:
        """Returns True when the error is fully ABSORBED by the routing
        (non-fatal, logged/dropped or fault-routed by the junction — the
        synchronous path's per-receiver semantics); False when the drain
        must still raise it to its caller (framework fatals, which
        handle_error re-raises after arming ``_fatal``, and any error of
        an entry that has no delivering junction)."""
        j = getattr(entry, "junction", None)
        if j is None:
            return False
        # fused entries retain the input batch (per-member fault
        # attribution needs it) — hand its events to the fault-stream
        # routing; query entries retain only the device OUTPUT, so their
        # non-fatal drain errors are logged here (an empty-events STREAM
        # route would silently publish nothing)
        events = []
        batch = getattr(entry, "batch", None)
        if batch is not None:
            try:
                events = j.decode_events(batch)
            except Exception:  # noqa: BLE001 — routing must not mask
                events = []
        if not events and not isinstance(err, FatalQueryError):
            # fatals surface loudly through _fatal + the drain's raise;
            # a NON-fatal with no events would otherwise vanish into an
            # empty fault-stream publish
            log.error(
                "pipeline drain error on stream '%s' (input events not "
                "retained past dispatch): %r", j.definition.id, err)
        try:
            # handle_error arms j._fatal and re-raises for framework
            # failures, notifies the supervisor of peer failures, and
            # logs/fault-routes the rest; the re-raise is swallowed here
            # because the drain raises the collected error to ITS caller
            j.handle_error(events, err)
        except Exception:  # noqa: BLE001 — fatal: surfaced by the drain
            return False
        return True

    def _pull(self, owner, refs: list) -> list:
        import jax

        timeout = getattr(self.app_context, "cluster_step_timeout", None)
        if timeout is not None and getattr(owner, "_shard_mesh", None) is not None:
            from siddhi_tpu.parallel.distributed import guarded_pull

            name = getattr(owner, "name", None) or getattr(
                owner, "stream_id", "?")
            return guarded_pull(refs, timeout,
                                what=f"query '{name}' pipeline drain")
        return jax.device_get(refs)

    # -------------------------------------------------------------- flush

    def flush_owner(self, owner) -> None:
        """Drain everything of one owner (owner lock held) — called
        before a timer step so the timer observes a fully-drained
        timeline, and by restores/tests."""
        self._drain_owner(owner, keep_newest=None)

    def flush(self, own_only: bool = False) -> None:
        """Drain owners to empty. Sync junction sends and @Async workers
        call this with ``own_only=True`` — draining only owners whose
        FIFO head was submitted by THIS thread (its own dispatches and
        their emit cascades), so a latency-sensitive synchronous sender
        never pays an unrelated busy stream's device pulls; ``persist``
        (inside the barrier), shutdown, and restore flush everything.
        Nested flushes (an emit cascading into a downstream sync send)
        are no-ops — the outer flush loops until nothing is pending."""
        if self._n_pending == 0:
            return
        if getattr(self._tls, "in_flush", False):
            return
        if self._draining():
            # this thread is inside a drain round (submit's forced drain
            # or flush_owner) and HOLDS that owner's lock: acquiring a
            # different owner's lock here would ABBA-deadlock against a
            # peer worker doing the mirror-image cascade. The entries
            # this nested flush wanted stay pending for the caller's own
            # idle/sync flush, which runs lock-free.
            return
        self._tls.in_flush = True
        ident = threading.get_ident()
        try:
            while True:
                draining = self._draining()
                with self._lock:
                    # owners THIS thread is mid-draining are excluded:
                    # their new entries (feedback topologies) belong to
                    # the in-progress round's caller, and looping on them
                    # here would spin forever without progress
                    # own_only matches ANY entry of this thread, not just
                    # the head: a sync sender's dispatch queued behind a
                    # worker's entry in the same owner FIFO must still
                    # drain before the send returns (the foreign head
                    # drains first — same-owner FIFO order is inherent)
                    owners = [o for o, dq in self._pending.items()
                              if dq and id(o) not in draining
                              and (not own_only
                                   or any(en.tid == ident for en in dq))]
                if not owners:
                    return
                submitted0 = getattr(self._tls, "submitted", 0)
                for owner in owners:
                    lock = getattr(owner, "_lock", None)
                    if lock is not None:
                        with lock:
                            self._drain_owner(owner, keep_newest=None)
                    else:
                        self._drain_owner(owner, keep_newest=None)
                if getattr(self._tls, "submitted", 0) == submitted0:
                    # only re-loop when THIS thread's own emit cascades
                    # produced new entries — a busy @Async producer on
                    # another thread must not turn a synchronous sender's
                    # flush into an unbounded drain of foreign streams
                    return
        finally:
            self._tls.in_flush = False

    def discard_all(self) -> None:
        """Drop every in-flight entry WITHOUT emitting (snapshot restore:
        pre-restore outputs belong to the rolled-back timeline, exactly
        like ``q._deferred``)."""
        with self._lock:
            self._pending.clear()
            self._n_pending = 0
