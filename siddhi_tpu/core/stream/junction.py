"""StreamJunction: per-stream pub/sub bus.

Mirror of reference ``core/stream/StreamJunction.java``: each defined stream
gets a junction; producers publish event chunks, receivers (query input
processors, stream callbacks, sinks) subscribe. Sync mode fans out directly
(``StreamJunction.java:175-178``); ``@Async`` buffering is a host-side queue
+ worker thread (the Disruptor's role, ``:276-313``) — see
``enable_async``. ``@OnError(action='STREAM')`` fault routing
(``:368-430``) publishes failed events + error into the shadow ``!stream``
junction.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import traceback
from typing import List, Optional

from siddhi_tpu.analysis.guards import guarded
from siddhi_tpu.analysis.locks import make_lock
from siddhi_tpu.core.event import Event
from siddhi_tpu.observability import journey
from siddhi_tpu.observability.tracing import span
from siddhi_tpu.query_api.definitions import StreamDefinition

log = logging.getLogger(__name__)

# marker for "no batch in flight" (None is the queue's stop sentinel)
_NOTHING = object()

# the junction whose delivery loop is running on THIS thread: receivers
# reached through the Event path (Receiver.receive has no junction
# parameter) read it so their pipelined completions still know their
# delivering junction (error attribution + completion-latency feedback)
_DELIVERING = threading.local()


def current_delivering_junction() -> Optional["StreamJunction"]:
    return getattr(_DELIVERING, "junction", None)

# worker heartbeat floor: the drain loop polls its queue with this bound,
# so a healthy worker — even an idle one — bumps its beats counter at
# least ~10x/sec and the supervisor can tell wedged from idle (its
# wedge timeout is clamped to a multiple of this floor)
_IDLE_POLL_S = 0.1


class FatalQueryError(RuntimeError):
    """Framework-infrastructure failure (dense-capacity overflow knobs):
    unlike per-event processing errors — which the junction logs/routes
    per @OnError like the reference — these always propagate to the
    sender."""


class Receiver:
    """Subscriber interface (reference StreamJunction.Receiver)."""

    def receive(self, events: List[Event]):
        raise NotImplementedError

    def receive_batch(self, batch, junction: "StreamJunction"):
        """Columnar fast path: receivers that can consume a HostBatch
        directly override this; the default decodes to Events (so every
        receiver keeps working when a producer uses the bulk API)."""
        self.receive(junction.decode_events(batch))


@guarded
class StreamJunction:
    # only the adaptive-batch control loop's read-modify-write state is
    # lock-guarded; the resilience counters (`_beats`, `_inflight`) and
    # the delivery-thread-confined registries (`receivers`,
    # `_pending_mutations`, `_wal_seq_of`, `_jt_enq`) are deliberately
    # lock-free — gauges and the supervisor read them racily on purpose
    GUARDED_BY = {"_lat_ewma": "adapt", "_cur_batch": "adapt"}

    def __init__(self, definition: StreamDefinition, app_context, fault_junction: Optional["StreamJunction"] = None):
        self.definition = definition
        self.app_context = app_context
        self.receivers: List[Receiver] = []
        self.fault_junction = fault_junction
        self.on_error_action = "LOG"  # LOG | STREAM (from @OnError)
        self._async = False
        self._queue: Optional[queue.Queue] = None
        self._worker: Optional[threading.Thread] = None
        self._batch_size = 256
        self._cur_batch = 256
        self._max_delay_s: Optional[float] = None
        self._latency_target_ms: Optional[float] = None
        self._lat_ewma = 0.0
        # _adapt used to run only on the single worker thread; pipelined
        # completions now also feed it from whichever thread drains the
        # pump, so the EWMA/cap read-modify-write needs a lock
        self._adapt_lock = make_lock("adapt")
        self._running = False
        self._fatal: Optional[Exception] = None  # async worker's FatalQueryError
        # resilience hooks (resilience/supervisor.py, resilience/faults.py):
        # the in-flight batch survives a worker death for its replacement;
        # the generation token retires late-waking stale workers; beats is
        # the supervisor's liveness counter; fault_hook is the injection
        # point the drain loop polls
        self._inflight = _NOTHING
        self._inflight_owner = None    # thread that parked _inflight
        self._gen = 0
        self._beats = 0
        self.fault_hook = None
        # overload armor (resilience/overload.py): queued unit id -> its
        # ingest-WAL sequence number, so a shed unit's record can be
        # discarded (replay must cover exactly the non-shed suffix).
        # Empty unless the app registered quotas AND runs a WAL.
        self._wal_seq_of: dict = {}
        # batch-journey tracing (observability/journey.py): queued unit
        # id -> enqueue perf_counter, so the worker can attribute the
        # @Async queue residence. Empty unless journeys are enabled.
        self._jt_enq: dict = {}
        # deferred receiver-set mutations (autopilot fusion actuator):
        # drained by the DELIVERING thread before it fans a batch out,
        # so the receiver list is never rewired mid-iteration. Empty
        # unless a controller scheduled a dissolve/re-form.
        self._pending_mutations: List = []

    def defer_mutation(self, fn) -> None:
        """Schedule ``fn()`` to run on the next delivering thread BEFORE
        it iterates receivers — the only point where the receiver set
        may be rewired live (fused-group dissolve/re-form). A junction
        that never delivers again simply never applies it."""
        self._pending_mutations.append(fn)

    def _drain_mutations(self) -> None:
        while self._pending_mutations:
            fn = self._pending_mutations.pop(0)
            try:
                fn()
            except Exception:  # noqa: BLE001 — a failed rewire must not
                # poison the delivery that happened to drain it
                logging.getLogger(__name__).exception(
                    "deferred receiver mutation failed on stream '%s'",
                    self.definition.id)

    def subscribe(self, receiver: Receiver):
        if receiver not in self.receivers:
            self.receivers.append(receiver)

    def replace_receivers(self, members: List[Receiver], group: Receiver):
        """Swap a contiguous run of subscribed receivers for ONE fused
        receiver at the run's position (fan-out fusion,
        ``core/plan/fanout_plan.py``) — every other subscriber keeps its
        delivery slot, so callback/sink ordering is unchanged."""
        i = self.receivers.index(members[0])
        for m in members:
            self.receivers.remove(m)
        self.receivers.insert(i, group)

    def enable_async(self, buffer_size: int = 1024, batch_size: int = 256,
                     max_delay_ms: Optional[float] = None,
                     latency_target_ms: Optional[float] = None):
        """@Async: decouple producers via a bounded queue + one worker that
        re-batches up to batch_size (the role of StreamHandler.java:57-71).

        Adaptive batching (SURVEY §7 hard part 6 — batch size trades p99
        against events/sec; the reference's Disruptor has no such knob,
        its batch is whatever the ring hands the worker):
        - ``max.delay`` ('5 ms', '1 sec', …): a partial batch waits at
          most this long for more events before delivering — bounds the
          queueing half of tail latency under trickle load.
        - ``latency.target``: a closed loop on the PROCESSING half. Each
          delivery is timed; when the smoothed per-delivery latency
          overshoots the target the worker halves its current batch cap
          (floor 16), and when it runs under half the target the cap
          climbs 25% back toward ``batch.size``. Throughput degrades
          gracefully instead of p99 exploding when a query's step gets
          slower (capacity regrow, device contention)."""
        self._async = True
        self._batch_size = batch_size
        self._max_delay_s = (max_delay_ms / 1000.0
                             if max_delay_ms is not None else None)
        self._latency_target_ms = latency_target_ms
        with self._adapt_lock:
            # a live re-enable (autopilot re-tune) races the control
            # loop's read-modify-write in _adapt — same lock
            self._cur_batch = batch_size      # adaptive cap (<= batch_size)
            self._lat_ewma = 0.0
        self._queue = queue.Queue(maxsize=buffer_size)
        # observability: queue depth + in-flight unit gauges, scraped via
        # GET /metrics (telemetry is level-independent — a wedging @Async
        # queue must be visible whether or not @app:statistics is on)
        tel = getattr(self.app_context, "telemetry", None)
        if tel is not None:
            sid = self.definition.id
            tel.gauge(f"junction.{sid}.queue_depth", self._queue.qsize)
            tel.gauge(f"junction.{sid}.inflight_batches",
                      lambda j=self: 0 if j._inflight is _NOTHING else 1)

    def start_processing(self):
        self._running = True
        if self._async:
            self._start_worker()

    def _start_worker(self):
        self._gen += 1
        self._worker = threading.Thread(
            target=self._drain, args=(self._gen,), daemon=True,
            name=f"junction-{self.definition.id}-g{self._gen}")
        self._worker.start()

    def restart_worker(self):
        """Replace a dead or wedged worker (supervisor path): the queue and
        any in-flight batch stay intact; the generation bump makes a stale
        worker that later wakes exit without double-delivering."""
        if not (self._async and self._running):
            return
        self._start_worker()

    def stop_processing(self):
        self._running = False
        if self._worker is not None:
            if self._fatal is None:
                self._queue.put(None)
            else:
                # the worker died on a fatal error with producers possibly
                # having filled the queue — a blocking put would hang
                # shutdown on a queue nobody drains
                try:
                    self._queue.put_nowait(None)
                except queue.Full:
                    pass
            self._worker.join(timeout=5)
            self._worker = None

    def send_events(self, events: List[Event], wal_seq: Optional[int] = None):
        if not events:
            return
        sm = self.app_context.statistics_manager
        if sm is not None and sm.level >= 1:
            sm.throughput_tracker(self.definition.id).add(len(events))
        if self._fatal is not None:
            # the async worker died on a framework failure — surface it to
            # the producer instead of blocking on a queue nobody drains
            raise self._fatal
        if self._async and self._running:
            self._enqueue(events, wal_seq)
        else:
            self._deliver(events)
            # synchronous sends keep synchronous semantics: any batches
            # the receivers pipelined (CompletionPump) drain before the
            # send returns — the caller observes its outputs immediately,
            # exactly as before the pump existed. Overlap comes from
            # producers that deliver back-to-back (@Async workers).
            # own_only: this sender's dispatches and cascades, not an
            # unrelated busy stream's in-flight pulls.
            self._flush_pipeline(own_only=True)

    def decode_events(self, batch) -> List[Event]:
        return batch.to_events(
            [(a.name, a.type) for a in self.definition.attributes],
            self.app_context.string_dictionary,
            object_meta=getattr(self.definition, "object_elem_types", None),
            object_multi=getattr(self.definition, "object_multi_attrs", None),
        )

    def send_batch(self, batch, wal_seq: Optional[int] = None):
        """Columnar publish (no Event objects). @Async junctions enqueue the
        batch behind any pending event chunks (producer ordering is kept);
        it is delivered as one unit — already a batch."""
        sm = self.app_context.statistics_manager
        if sm is not None and sm.level >= 1:
            sm.throughput_tracker(self.definition.id).add(int(batch.size))
        if self._fatal is not None:
            raise self._fatal
        if self._async and self._running:
            self._enqueue(batch, wal_seq)
        else:
            self._deliver_batch(batch)
            self._flush_pipeline(own_only=True)   # see send_events

    def _flush_pipeline(self, own_only: bool = False):
        """Drain the app's CompletionPump (no-op when empty or when this
        is a nested flush inside an emit cascade). ``own_only`` (sync
        senders) drains only this thread's own dispatches and cascades;
        worker-loop flushes drain everything — including entries a dead
        predecessor worker left riding. The pump routes each drain error
        through the ENTRY's own delivering junction (fatals arm that
        junction's ``_fatal``, peer failures notify the supervisor, the
        rest log) — this junction only propagates the raise so a
        synchronous sender (or the worker loop) still sees the failure
        at the flush point."""
        pump = getattr(self.app_context, "completion_pump", None)
        if pump is None or not pump.has_pending:
            return
        pump.flush(own_only=own_only)

    def record_completion(self, elapsed_ms: float):
        """Completion-latency feedback from the CompletionPump: the TRUE
        deliver->emit time of a pipelined batch (the worker's own timing
        only saw the dispatch slice, which returns instantly once a batch
        rides the pipeline) — without this, ``latency.target`` would see
        near-zero latency and never shrink the batch cap on a slow
        device step."""
        self._adapt(elapsed_ms)

    def _enqueue(self, item, wal_seq: Optional[int] = None):
        """Producer-side @Async enqueue, counting backpressure stalls
        (sends that found the queue FULL and had to block) so sizing
        regressions are visible on /metrics before they become p99.

        Overload armor (resilience/overload.py): with quotas registered,
        admission runs FIRST — past the queue quota the stream's shed
        policy engages (shed_newest/shed_oldest drop a unit and discard
        its WAL record; block waits bounded, escalating to the
        supervisor). The blocking fallback itself is BOUNDED in all
        configurations: it re-checks ``_fatal`` each slice (a worker
        dying mid-wait used to leave the producer parked forever) and
        escalates to the supervisor every ``block_timeout_s`` so a
        wedged consumer is replaced instead of deadlocking the
        producer with only a stall counter to show for it."""
        from siddhi_tpu.resilience.overload import (
            BLOCK_PUT_SLICE_S,
            DEFAULT_BLOCK_TIMEOUT_S,
        )

        ctl = getattr(self.app_context, "overload", None)
        if ctl is not None and not ctl.admit(self, item, wal_seq):
            return                    # shed (counted; WAL record discarded)
        if wal_seq is not None:
            # mapped BEFORE the put: once queued, the worker (or a
            # shed_oldest eviction) may pop it at any moment
            self._wal_seq_of[id(item)] = wal_seq
        if journey.enabled():
            # queue-residence stamp (same before-the-put discipline).
            # Units evicted by shed_oldest leave stale stamps behind; at
            # most qsize stamps can be LIVE, so past that bound the
            # OLDEST surplus is stale (insertion-ordered dict) — evict
            # exactly it, never the live backlog's stamps (wiping those
            # would blind queue attribution during the very overload
            # episode being diagnosed)
            live_cap = (self._queue.maxsize or 8192) + 256
            while len(self._jt_enq) > live_cap:
                try:
                    # concurrent producers race this unlocked dict: pop
                    # tolerates losing the key, and a torn iterator just
                    # retries on the next enqueue
                    self._jt_enq.pop(next(iter(self._jt_enq)), None)
                except (StopIteration, RuntimeError):
                    break
            self._jt_enq[id(item)] = time.perf_counter()
        try:
            self._queue.put_nowait(item)
            return
        except queue.Full:
            pass
        tel = getattr(self.app_context, "telemetry", None)
        if tel is not None:
            tel.count(f"junction.{self.definition.id}.backpressure_stalls")
        timeout_s = (ctl.block_timeout_s if ctl is not None
                     else DEFAULT_BLOCK_TIMEOUT_S)
        waited = 0.0
        while True:
            try:
                self._queue.put(item, timeout=BLOCK_PUT_SLICE_S)
                return
            except queue.Full:
                pass
            if self._fatal is not None:
                self._wal_seq_of.pop(id(item), None)
                self._jt_enq.pop(id(item), None)
                raise self._fatal
            waited += BLOCK_PUT_SLICE_S
            if waited >= timeout_s:
                waited = 0.0
                if ctl is not None:
                    ctl.escalate(self)
                else:
                    self._escalate_default()

    def _escalate_default(self) -> None:
        """Bounded-wait escalation for apps WITHOUT overload quotas: the
        blocked producer is still visible (counter + log) and the
        supervisor still gets a chance to replace a wedged consumer."""
        from siddhi_tpu.resilience import stat_count

        tel = getattr(self.app_context, "telemetry", None)
        if tel is not None:
            tel.count(f"junction.{self.definition.id}.enqueue_timeouts")
        stat_count(self.app_context, "resilience.enqueue_timeouts")
        sup = getattr(self.app_context, "supervisor", None)
        if sup is not None and hasattr(sup, "notify_backpressure"):
            try:
                sup.notify_backpressure(self)
                return
            except Exception:  # noqa: BLE001 — escalation must not mask
                log.exception("backpressure escalation failed")
        log.warning(
            "producer blocked on full @Async queue of stream '%s' — the "
            "consumer is not draining (wedged worker? attach "
            "rt.supervise() to auto-replace it)", self.definition.id)

    def _deliver_batch(self, batch, enq_t=None):
        from siddhi_tpu.core.event import HostBatch, LazyColumns

        if self._pending_mutations:
            self._drain_mutations()
        with span("junction.dispatch", batch=journey.batch_of(batch),
                  stream=self.definition.id,
                  rows=int(batch._size) if batch._size is not None else -1):
            prev = current_delivering_junction()
            _DELIVERING.junction = self
            jt = journey.enabled()
            # queue-residence scope: receivers of THIS delivery read it;
            # nested sync deliveries (emit cascades) mask it (journey.py)
            prev_q = journey.push_delivery_queue_wait(enq_t) if jt else None
            try:
                for r in self.receivers:
                    # receivers mutate batch.cols in place (filters, key
                    # columns) — hand each its own dict so mutations don't
                    # leak across; LazyColumns keeps device-held outputs
                    # unpulled until read
                    try:
                        sub = HostBatch(LazyColumns(batch.cols),
                                        size=batch._size)
                        # pack stamp rides the re-wrap; each receiver
                        # forks its own journey (journey.begin)
                        sub.journey = batch.journey
                        r.receive_batch(sub, self)
                    except Exception as e:  # noqa: BLE001 — fault routing
                        self.handle_error(self.decode_events(batch), e)
            finally:
                _DELIVERING.junction = prev
                if jt:
                    journey.pop_delivery_queue_wait(prev_q)

    def _adapt(self, elapsed_ms: float):
        """Latency-target control loop: EWMA the delivery latency, shrink
        the batch cap on overshoot, regrow on sustained headroom. Every
        @Async delivery's latency also lands in the junction's histogram
        tracker — the batcher's contribution to tail latency is exactly
        what max.delay / latency.target tune."""
        sm = self.app_context.statistics_manager
        if sm is not None and sm.level >= 1:
            sm.latency_tracker(
                f"junction.{self.definition.id}").record(elapsed_ms)
        target = self._latency_target_ms
        if target is None:
            return
        with self._adapt_lock:
            self._lat_ewma = (0.7 * self._lat_ewma + 0.3 * elapsed_ms
                              if self._lat_ewma else elapsed_ms)
            if self._lat_ewma > target:
                self._cur_batch = max(16, self._cur_batch // 2)
                self._lat_ewma = target  # re-converge from the new cap
            elif (self._lat_ewma < target / 2
                  and self._cur_batch < self._batch_size):
                self._cur_batch = min(self._batch_size,
                                      max(self._cur_batch + 1,
                                          int(self._cur_batch * 1.25)))

    def _pump_submits(self) -> int:
        pump = getattr(self.app_context, "completion_pump", None)
        return pump.submits_of(self) if pump is not None else 0

    def _timed_deliver(self, events: List[Event], enq_t=None):
        ctl = getattr(self.app_context, "overload", None)
        if ctl is not None:
            # weighted fair scheduling (resilience/overload.py): a worker
            # of an app running over its fair share yields briefly while
            # a sibling app is backlogged — one flooded tenant must not
            # monopolize the cores its siblings' workers need
            ctl.throttle(len(events))
        t0 = time.perf_counter()
        n0 = self._pump_submits()
        self._deliver(events, enq_t)
        if self._pump_submits() == n0:
            # pipelined deliveries return at dispatch; their near-zero
            # slice must not feed the latency loop — record_completion
            # supplies the TRUE sample at drain instead
            self._adapt((time.perf_counter() - t0) * 1000.0)

    def _timed_deliver_batch(self, batch, enq_t=None):
        # columnar unit variant of _timed_deliver — same pipelined-skip
        # and fair-throttle rules; the two must stay in lock-step
        ctl = getattr(self.app_context, "overload", None)
        if ctl is not None:
            n = batch._size   # known count only — never force a pull here
            ctl.throttle(int(n) if n is not None else 1)
        t0 = time.perf_counter()
        n0 = self._pump_submits()
        self._deliver_batch(batch, enq_t)
        if self._pump_submits() == n0:
            self._adapt((time.perf_counter() - t0) * 1000.0)

    def _drain(self, gen: Optional[int] = None):
        if gen is None:
            gen = self._gen
        while True:
            self._beats += 1
            hook = self.fault_hook
            if hook is not None:
                # fault-injection point (resilience/faults.py): the hook
                # may raise (simulated worker crash — the in-flight batch
                # stays parked for the replacement) or block (wedge)
                try:
                    hook(self)
                except Exception as e:  # noqa: BLE001 — injected death
                    log.warning("junction '%s' worker killed: %s",
                                self.definition.id, e)
                    return
            if gen != self._gen:
                return     # superseded by a restart while wedged/blocked
            if self._inflight is not _NOTHING:
                owner = self._inflight_owner
                if (owner is not None and owner.is_alive()
                        and owner is not threading.current_thread()):
                    # a superseded-but-ALIVE predecessor still owns the
                    # unit (slow delivery, e.g. a first-batch jit
                    # compile): adopting it would double-deliver when the
                    # predecessor eventually completes. Wait for it to
                    # finish or die, beating so the supervisor sees this
                    # worker as healthy (and keeping queue order intact).
                    time.sleep(_IDLE_POLL_S)
                    continue
                item = self._inflight    # predecessor died mid-delivery
                self._inflight_owner = threading.current_thread()
                enq_t = None             # stamp went with the predecessor
            else:
                try:
                    item = self._queue.get(timeout=_IDLE_POLL_S)
                    if self._wal_seq_of:
                        # dequeued for delivery: its WAL record is now
                        # "will be processed" — drop the shed handle
                        self._wal_seq_of.pop(id(item), None)
                    enq_t = (self._jt_enq.pop(id(item), None)
                             if self._jt_enq else None)
                except queue.Empty:
                    # idle: drain any batches still riding the pipeline —
                    # bounds emission lag under trickle load to one idle
                    # poll (this is what lets scheduler-driven windows
                    # and absent deadlines ride the pump)
                    self._flush_pipeline()
                    if not self._running and self._queue.empty():
                        return   # stop raced our sentinel away
                    continue
                self._inflight = item
                self._inflight_owner = threading.current_thread()
                if gen != self._gen:
                    return   # superseded mid-fetch: item handed over
            if item is None:
                self._inflight = _NOTHING
                self._flush_pipeline()   # the worker's last act: nothing
                #                          may stay riding after shutdown
                return
            if not isinstance(item, list):
                # columnar HostBatch: delivered as ONE pre-formed unit
                # (the cap never splits producer batches — max.delay /
                # latency.target shape only the event-path coalescing),
                # but its delivery latency still feeds the adaptive loop
                # (unless it pipelined — see _timed_deliver)
                self._timed_deliver_batch(item, enq_t)
                self._inflight = _NOTHING
                if self._queue.empty():
                    self._flush_pipeline()
                continue
            batch = list(item)
            self._inflight = batch   # coalesced extras ride the same unit
            deadline = (time.perf_counter() + self._max_delay_s
                        if self._max_delay_s is not None else None)
            stop_after = False
            follow = None            # HostBatch that broke the coalesce
            follow_enq = None
            # re-batch pending chunks up to the (adaptive) cap; a partial
            # batch waits at most max.delay for more. The cap is read
            # ONCE per drain, under the adapt lock — the control loop
            # may rewrite it concurrently from a pump-draining thread
            with self._adapt_lock:
                cap = self._cur_batch
            while len(batch) < cap:
                try:
                    if deadline is None:
                        more = self._queue.get_nowait()
                    else:
                        wait = deadline - time.perf_counter()
                        if wait <= 0:
                            break
                        # bounded slices of the max.delay wait, beating
                        # between them — a worker waiting out a LONG
                        # coalesce deadline is healthy, and must not look
                        # wedged to the supervisor
                        more = self._queue.get(
                            timeout=min(wait, _IDLE_POLL_S))
                except queue.Empty:
                    if deadline is None or time.perf_counter() >= deadline:
                        break
                    self._beats += 1
                    continue
                if self._wal_seq_of:
                    self._wal_seq_of.pop(id(more), None)
                more_enq = (self._jt_enq.pop(id(more), None)
                            if self._jt_enq else None)
                if more is None:
                    stop_after = True
                    break
                if not isinstance(more, list):
                    follow = more
                    follow_enq = more_enq
                    break
                batch.extend(more)
            if gen != self._gen and follow is None and not stop_after:
                return   # superseded while coalescing: the (possibly
                #          grown) batch stays parked for the replacement
            # coalesced extras keep the FIRST chunk's enqueue stamp — the
            # longest (and attribution-relevant) residence of the unit
            self._timed_deliver(batch, enq_t)
            if follow is not None:
                self._inflight = follow
                self._timed_deliver_batch(follow, follow_enq)
            self._inflight = _NOTHING
            if stop_after or self._queue.empty():
                self._flush_pipeline()
            if stop_after:
                return

    def _deliver(self, events: List[Event], enq_t=None):
        if self._pending_mutations:
            self._drain_mutations()
        with span("junction.dispatch", stream=self.definition.id,
                  rows=len(events)):
            prev = current_delivering_junction()
            _DELIVERING.junction = self
            jt = journey.enabled()
            prev_q = journey.push_delivery_queue_wait(enq_t) if jt else None
            try:
                for r in self.receivers:
                    try:
                        r.receive(events)
                    except Exception as e:  # noqa: BLE001 — fault routing
                        self.handle_error(events, e)
            finally:
                _DELIVERING.junction = prev
                if jt:
                    journey.pop_delivery_queue_wait(prev_q)

    def handle_error(self, events: List[Event], e: Exception):
        from siddhi_tpu.ops.expressions import CompileError

        supervisor = getattr(self.app_context, "supervisor", None)
        if supervisor is not None:
            # cluster-peer failures trigger the recovery protocol
            # (resilience/supervisor.py); other errors are ignored there
            try:
                supervisor.notify_error(self, e)
            except Exception:  # noqa: BLE001 — supervision must not mask
                log.exception("supervisor notification failed")

        if isinstance(e, (FatalQueryError, CompileError)):
            # framework-infrastructure failures (capacity overflow knobs)
            # and deferred compile errors (first-trace design diagnostics)
            # always surface to the sender — routing them to a fault
            # stream would hide a misconfigured deployment. On an @Async
            # junction the raise unwinds the worker; the stored error makes
            # every later send re-raise instead of hanging on a full queue.
            self._fatal = e
            raise e
        if self.on_error_action == "STREAM" and self.fault_junction is not None:
            self.route_fault_events(events, e)
        else:
            # default/LOG action: log and DROP — the reference's
            # StreamJunction never propagates processing errors back to
            # the sender (FaultStreamTestCase test1/test2)
            log.error(
                "error processing events in stream '%s': %s\n%s",
                self.definition.id, e, traceback.format_exc(),
            )

    def route_fault_events(self, events: List[Event], e: Exception):
        """Publish ``events`` + error to the '!stream' fault junction —
        fault stream schema = original attrs + _error (reference
        FaultStreamEventConverter). The tail of ``handle_error``'s STREAM
        action, also used directly by receivers that do their own
        per-member attribution (fused fan-out groups)."""
        self.fault_junction.send_events([
            Event(timestamp=ev.timestamp, data=list(ev.data) + [str(e)])
            for ev in events
        ])
