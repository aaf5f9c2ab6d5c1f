"""Batch-journey tracing + critical-path attribution.

A journey is the per-batch record; a span (``tracing.span``) is the
per-stage one. Each stage below is opened with ``tracing.span`` at ONE
site, which also stamps the journey from that span's duration, and
``enable`` is the one switch (it sets ``tracing.profiler_spans`` for as
long as anybody holds it): while journeys are on, every span is also a ``siddhi.<name>`` event of a profiler
trace, carrying the journey's ``batch`` id (a per-process sequence
number given at pack and kept by every fork of the journey).

The spans of ``tracing.py`` time components in isolation; once the
dispatch pipeline overlaps stages (``core/query/completion.py``,
depth >= 2) they cannot say where a batch's END-TO-END latency actually
goes — the dispatch slice of a pipelined batch returns instantly and
the device time hides inside the ride. This module follows each batch
through the pipeline with host-side monotonic timestamps only (zero
changes inside jitted step code — sanitizers and ``hlo_audit`` stay
quiet) and attributes wall-clock per stage the way "Scaling Ordered
Stream Processing on Shared-Memory Multicores" (PAPERS.md) prescribes:
service time vs queueing time, per stage, with overlapped stages
attributed by MAX, not sum.

Stage glossary (exported as ``siddhi_stage_ms{query,stage}`` service
histograms and ``siddhi_stage_queue_ms{query,stage}`` queueing
histograms on ``GET /metrics``):

- ``pack``     — host event->columnar encode (``HostBatch.from_events``
                 / ``from_columns``), stamped where the batch is born.
- ``queue``    — residence in the @Async junction queue (enqueue ->
                 dequeue); a queue-only stage: its signal is queueing
                 time, service is the worker's re-batching (~0).
- ``dispatch`` — host work inside ``process_batch``: key computation,
                 capacity checks, routing prep, jitted-step dispatch.
                 Two sub-stages have a span and a ring counter of their
                 own (below): ``key`` and ``launch``.
- ``device``   — observed device busy time. A pipelined batch rides in
                 flight; at drain the existing ``jax.Array.is_ready``
                 machinery tells which side was waiting: output NOT
                 ready => the device worked the whole ride (service =
                 ride + meta pull), output ready => the device finished
                 mid-ride and only the pull is service — the ride was
                 the output parked waiting for the host (recorded as
                 ``device`` queueing/slack, NOT service). This is the
                 max-not-sum rule: when the host is the bottleneck the
                 ride must not ALSO count as device service.
- ``emit``     — output decode + downstream publish (sink/junction),
                 the user's callback and the output pull inside it.

Two pulls cross from the device to the host, and the journey keeps them
apart (neither is a stage of the glossary; both are counters of the
ring record):

- ``meta_pull`` — the packed ``__meta__`` array (overflow, notify, row
                 count, instrument lanes): ``_pull_meta`` in the
                 synchronous tail, the pump's one batched pull at drain.
                 It is the round trip that waits for the step.
- ``pull``      — the OUTPUT columns, pulled by ``LazyColumns`` when a
                 consumer first reads one (inside ``emit``): ``pull_ms``
                 and ``rows_padded`` (the length the columns were pulled
                 at) beside ``rows_out`` (the meta's count of valid
                 rows). The bytes are on the ``siddhi.pull`` span.

Two sub-stages of ``dispatch`` are counters of the ring record too, each
stamped from its own span inside ``siddhi.query.step`` (``None`` where the
stage did not run):

- ``key_ms``    — ``siddhi.key`` (``keying``): the partition-key and
                 group-key computation and the key-capacity check; a
                 ``siddhi.grow`` is its child. Where a host window runs
                 between the partition key and the group key there are
                 two spans a batch, and ``key_ms`` is their sum.
- ``launch_ms`` — ``siddhi.launch`` (``core/event.py`` ``launch_step``):
                 the call of the jitted step and nothing else: the
                 flatten of the arguments, the host->device transfer of
                 the batch's numpy columns (there is no ``device_put`` on
                 the hot path) and the enqueue. ``h2d_bytes`` is what
                 crossed: the ``nbytes`` of the argument leaves that were
                 numpy. A batch dispatched in pieces sums both over its
                 pieces (``dispatch_ms`` ends with the first piece: a
                 split batch's journey rides it).

A device-routed query (``parallel/mesh.py``) stamps four more counters
of the ring record, ``None`` for every other query: ``route_prep_ms`` and
``route_pieces`` from the ``siddhi.route.prepare`` span around
``prepare_routed_batches`` (host work inside ``dispatch``; pieces > 1: a
source-destination pair exceeded its quota and the batch was split), and
at the drain ``shard_rows_max`` (the fullest shard's received rows, from
the meta's ``rows_0..n-1`` lanes) beside ``shard_capacity`` (``n x Q``).
A split batch's journey rides its first piece.

A tumbling window folded into per-group accumulators
(``ops/tumbling_agg.py``) stamps two more, ``None`` for every other
query: ``flush_rows`` on the journey of a step that closed a window (the
groups it delivered), and ``timer_steps`` = 1 on the journey of a TIMER
step (``QueryRuntime.process_timer``, under the ``siddhi.timer`` span).
Under ``@app:playback`` the scheduler fires a window's timer while the
send that crosses the boundary advances the clock, before that send's own
step: the TIMER step's journey and span carry that send's ``batch`` id,
so the fields add up per send.

Every query stamps three more: ``grow_ms``, the ``siddhi.grow`` spans
of the key-capacity growths this batch forced (``None``: none), and
``state_bytes`` / ``state_slots``, the state its step left on the device
(bytes from the leaves' shapes; the ring slots of a keyed window, key
capacity x ring, ``None`` where there is no keyed ring).

Cost model: near-zero when off — every instrumented site checks one
module flag and does nothing else. When on, a batch carries one small
``Journey`` object (a handful of floats); finished journeys land in
per-(query, stage) telemetry histograms plus a bounded ring buffer of
recent per-batch records (tracing never grows without bound).

The analyzer (:func:`critical_path_report`) aggregates the histograms
into a report naming the bottleneck stage per query: the stage with the
largest mean service time per batch — except a ``queue``-stage residence
dominating every service mean names the queue itself (the consumer is
stalled OUTSIDE its measured service, e.g. a wedged/throttled worker).
Utilization = stage busy time / observed wall. Rendered by
``tools/critical_path.py``; served at ``GET /profile/critical_path``.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from siddhi_tpu.observability import tracing
from siddhi_tpu.observability.tracing import span

STAGES = ("pack", "queue", "dispatch", "device", "emit")

_DEFAULT_RING = 4096

# module flag: the ONE check every instrumented hot-path site pays when
# journey tracing is off (HostBatch.from_events runs per batch, not per
# event — same discipline as tracing.span)
_ENABLED = False
_enable_count = 0
_lock = threading.RLock()

# ring of recently finished journeys (dicts; see Journey.finish)
_RING: deque = deque(maxlen=_DEFAULT_RING)

# (app, query) -> [first_seen, last_seen] perf_counter span: the
# observed wall the analyzer divides stage busy time by
_WALL: Dict[Tuple[str, str], List[float]] = {}

# fault injection (tests / tools): stage -> seconds of planted service
# delay, consulted only by instrumented sites and only when enabled —
# FaultInjector.delay_stage is the public face (resilience/faults.py)
_DELAYS: Dict[str, float] = {}

# per-delivery-thread context: the @Async worker stamps the queue wait
# of the unit it is about to deliver; every receiving query's journey
# picks it up (one delivery fans out to N receivers). ``emitting`` is
# the journey whose emit stage is open on this thread: the output pull
# inside it (LazyColumns) is charged there.
_TLS = threading.local()

# the batch id: one per packed batch (and per journey begun without a
# pack stamp), shared by every span and every fork of that batch
_BATCH_SEQ = itertools.count(1)


def enabled() -> bool:
    return _ENABLED


def enable(ring_capacity: Optional[int] = None) -> None:
    """Turn journey tracing on (refcounted: one ``disable()`` per
    ``enable()``; the first enable resets the ring and wall tracking)."""
    global _ENABLED, _enable_count, _RING
    with _lock:
        _enable_count += 1
        tracing.profiler_spans(True)
        if not _ENABLED:
            _RING = deque(maxlen=int(ring_capacity or _DEFAULT_RING))
            _WALL.clear()
            _ENABLED = True
        elif ring_capacity is not None and ring_capacity != _RING.maxlen:
            _RING = deque(_RING, maxlen=int(ring_capacity))


def disable(force: bool = False) -> None:
    global _ENABLED, _enable_count
    with _lock:
        _enable_count = 0 if force else max(0, _enable_count - 1)
        if _enable_count == 0:
            _ENABLED = False
            tracing.profiler_spans(False)


def forget_app(app_name: str) -> None:
    """Drop an app's wall-tracking entries (called at runtime shutdown):
    a redeployed same-named app must not inherit a dead app's
    first-seen timestamp — its utilization would read ~0% across the
    gap — and app churn must not grow the map without bound."""
    with _lock:
        for key in [k for k in _WALL if k[0] == app_name]:
            del _WALL[key]


def inject_delay(stage: str, seconds: float) -> None:
    """Plant a service delay inside an instrumented stage (the
    critical-path tests' known bottleneck). Only ``pack`` is a direct
    injection point today; queue bottlenecks are planted with
    ``FaultInjector.delay_worker`` (the consumer side)."""
    if stage not in STAGES:
        raise ValueError(f"unknown journey stage '{stage}' — one of {STAGES}")
    _DELAYS[stage] = float(seconds)


def clear_delays() -> None:
    _DELAYS.clear()


def maybe_delay(stage: str) -> None:
    d = _DELAYS.get(stage)
    if d:
        time.sleep(d)


def ring() -> list:
    """Snapshot of the recent-journeys ring (newest last)."""
    with _lock:
        return list(_RING)


def ready_of(ref) -> bool:
    """``jax.Array.is_ready`` verdict of a device ref (True for numpy /
    unknown / deleted — also aliased as ``completion._is_ready``, the
    pump's stall probe)."""
    is_ready = getattr(ref, "is_ready", None)
    if is_ready is None:
        return True
    try:
        return bool(is_ready())
    except Exception:   # noqa: BLE001 — deleted/donated buffers etc.
        return True


# ------------------------------------------------------- delivery context

def push_delivery_queue_wait(enq_t: Optional[float]):
    """Open one junction delivery's scope on this thread: receivers of
    THIS delivery read the unit's queue residence (None = not from an
    @Async queue). Returns the previous value for the paired
    :func:`pop_delivery_queue_wait` — a nested delivery (a receiver's
    synchronous emit cascading into a downstream junction) masks the
    outer wait instead of charging the upstream queue residence to
    queries that never sat in that queue."""
    prev = getattr(_TLS, "queue_ms", None)
    _TLS.queue_ms = (None if enq_t is None
                     else (time.perf_counter() - enq_t) * 1000.0)
    return prev


def pop_delivery_queue_wait(prev) -> None:
    _TLS.queue_ms = prev


def _delivery_queue_ms() -> Optional[float]:
    return getattr(_TLS, "queue_ms", None)


# ---------------------------------------------------------------- journey

class Journey:
    """Per-batch trace context: stamped at pack, carried on the
    ``HostBatch`` through junction delivery, forked per receiving query,
    riding the batch's ``QueryCompletion``/``FusedCompletion`` through
    the pump, finished after emit. All timestamps host-monotonic."""

    __slots__ = ("batch", "pack_ms", "queue_ms", "_t_disp0", "dispatch_ms",
                 "_t_disp1", "_t_drain0", "ready", "meta_pull_ms",
                 "emit_ms", "pull_ms", "pulls", "rows_out", "rows_padded",
                 "route_prep_ms", "route_pieces", "shard_rows_max",
                 "shard_capacity", "flush_rows", "timer_steps", "grow_ms",
                 "state_bytes", "state_slots", "key_ms", "launch_ms",
                 "h2d_bytes", "_rec")

    def __init__(self, pack_ms: Optional[float] = None,
                 batch: Optional[int] = None):
        self.batch = next(_BATCH_SEQ) if batch is None else batch
        self.pack_ms = pack_ms
        self.queue_ms: Optional[float] = None
        self._t_disp0: Optional[float] = None
        self.dispatch_ms = 0.0
        self._t_disp1: Optional[float] = None
        self._t_drain0: Optional[float] = None
        self.ready: Optional[bool] = None
        self.meta_pull_ms = 0.0
        self.emit_ms = 0.0
        self.pull_ms = 0.0
        self.pulls = 0
        self.rows_out: Optional[int] = None
        self.rows_padded = 0
        self.route_prep_ms: Optional[float] = None
        self.route_pieces: Optional[int] = None
        self.shard_rows_max: Optional[int] = None
        self.shard_capacity: Optional[float] = None
        self.flush_rows: Optional[int] = None
        self.timer_steps: Optional[int] = None
        self.grow_ms: Optional[float] = None
        self.state_bytes: Optional[int] = None
        self.state_slots: Optional[int] = None
        self.key_ms: Optional[float] = None
        self.launch_ms: Optional[float] = None
        self.h2d_bytes: Optional[int] = None
        self._rec: Optional[dict] = None     # the ring record, once finished

    # one journey object is stamped on the batch at pack time; each
    # receiving query forks its own (stage times are per query)
    def fork(self) -> "Journey":
        return Journey(pack_ms=self.pack_ms, batch=self.batch)

    def begin_dispatch(self) -> None:
        self.queue_ms = _delivery_queue_ms()
        self._t_disp0 = time.perf_counter()

    def end_dispatch(self) -> None:
        if self._t_disp0 is not None and self._t_disp1 is None:
            self._t_disp1 = time.perf_counter()
            self.dispatch_ms = (self._t_disp1 - self._t_disp0) * 1000.0

    def pre_drain(self, ready: bool) -> None:
        """Stamped immediately BEFORE the meta pull, with the output's
        ``is_ready`` verdict — the pivot of the device attribution."""
        self._t_drain0 = time.perf_counter()
        self.ready = bool(ready)

    def meta_pulled(self, ms: Optional[float]) -> None:
        """The ``siddhi.meta_pull`` span's duration (one batched round
        trip may serve several entries: each is attributed the round)."""
        self.meta_pull_ms = float(ms or 0.0)

    def pulled(self, ms: float, rows: int) -> None:
        """One ``siddhi.pull`` inside this journey's emit stage
        (``LazyColumns``), of columns ``rows`` long."""
        self.pull_ms += ms
        self.pulls += 1
        self.rows_padded += rows

    def route_prepared(self, ms: Optional[float], pieces: int) -> None:
        """The ``siddhi.route.prepare`` span's duration and the pieces
        the routed batch went to the device in."""
        self.route_prep_ms = ms
        self.route_pieces = pieces

    def shards_filled(self, rows_max: int, capacity: Optional[float]) -> None:
        """The drained meta's ``shard_rows`` lanes: the fullest shard's
        received rows, and what a shard can receive."""
        self.shard_rows_max = rows_max
        self.shard_capacity = capacity

    def flushed(self, rows: int) -> None:
        """This step closed a tumbling window (``ops/tumbling_agg.py``)
        and delivers ``rows`` groups."""
        self.flush_rows = rows

    def grown(self, ms: Optional[float]) -> None:
        """This batch forced a key-capacity growth: the ``siddhi.grow``
        span's duration (a batch can force several: summed)."""
        self.grow_ms = (self.grow_ms or 0.0) + float(ms or 0.0)

    def keyed(self, ms: Optional[float]) -> None:
        """One ``siddhi.key`` span of this batch (summed: a host window
        between the partition key and the group key makes two)."""
        self.key_ms = (self.key_ms or 0.0) + float(ms or 0.0)

    def launched(self, ms: Optional[float], nbytes: int) -> None:
        """One ``siddhi.launch`` span of this batch, and the bytes its
        arguments took to the device; summed over the pieces of a split
        batch. A later piece may be launched after the first piece's
        emit finished the journey (synchronous tail): the ring record
        then takes the sum too."""
        self.launch_ms = (self.launch_ms or 0.0) + float(ms or 0.0)
        self.h2d_bytes = (self.h2d_bytes or 0) + int(nbytes)
        if self._rec is not None:
            self._rec["launch_ms"] = self.launch_ms
            self._rec["h2d_bytes"] = self.h2d_bytes

    def state_sized(self, nbytes: int, slots: Optional[int]) -> None:
        """The query's state on the device as this batch's step left it:
        its bytes, and the ring slots of a keyed window's."""
        self.state_bytes = nbytes
        self.state_slots = slots

    def emitting(self, app_context, names, rows_out: Optional[int] = None):
        """The emit stage as a context manager: ``siddhi.emit`` span,
        this journey current on the thread for the pulls inside it, and
        at the close ``emit_ms`` stamped and the journey finished."""
        return _EmitStage(self, app_context, names, rows_out)

    def device_times(self) -> Tuple[float, float]:
        """(service_ms, queue_ms) of the device stage — see the module
        docstring's max-not-sum rule."""
        ride = 0.0
        if self._t_drain0 is not None and self._t_disp1 is not None:
            ride = max(0.0, (self._t_drain0 - self._t_disp1) * 1000.0)
        if self.ready is False:
            return ride + self.meta_pull_ms, 0.0
        # ready (or never observed): only the pull is known device work;
        # the ride was the finished output parked waiting for the host
        return self.meta_pull_ms, ride

    def finish(self, app_context, names) -> None:
        """Record this journey's stage times into the app's telemetry
        histograms (one set per query name — a fused group records the
        shared batch under every member) and the recent-journeys ring."""
        if not _ENABLED:
            return
        tel = getattr(app_context, "telemetry", None)
        if tel is None:
            return
        app = getattr(app_context, "name", "")
        dev_service, dev_queue = self.device_times()
        now = time.perf_counter()
        for name in names:
            if self.pack_ms is not None:
                tel.histogram(
                    f"stage.{name}.pack.service_ms").record(self.pack_ms)
            if self.queue_ms is not None:
                tel.histogram(
                    f"stage.{name}.queue.queue_ms").record(self.queue_ms)
            tel.histogram(
                f"stage.{name}.dispatch.service_ms").record(self.dispatch_ms)
            tel.histogram(
                f"stage.{name}.device.service_ms").record(dev_service)
            tel.histogram(
                f"stage.{name}.device.queue_ms").record(dev_queue)
            tel.histogram(f"stage.{name}.emit.service_ms").record(self.emit_ms)
        with _lock:
            # under the lock: forget_app's clear must not interleave
            # with this read-modify-write (a last in-flight finish
            # re-inserting a dead app's first-seen timestamp)
            for name in names:
                wall = _WALL.get((app, name))
                if wall is None:
                    t0 = self._t_disp0 if self._t_disp0 is not None else now
                    _WALL[(app, name)] = [t0, now]
                else:
                    wall[1] = now
            self._rec = {
                "app": app, "queries": list(names),
                "pack_ms": self.pack_ms, "queue_ms": self.queue_ms,
                "dispatch_ms": self.dispatch_ms,
                "device_service_ms": dev_service,
                "device_queue_ms": dev_queue,
                "emit_ms": self.emit_ms, "t": now,
                # emit_ms includes the output pull: its self time is
                # emit_ms - pull_ms. pull_ms is None where nothing was
                # pulled (no consumer read a device column)
                "batch": self.batch,
                "meta_pull_ms": self.meta_pull_ms,
                "pull_ms": self.pull_ms if self.pulls else None,
                "rows_out": self.rows_out,
                "rows_padded": self.rows_padded,
                # a device-routed query's; None for every other
                "route_prep_ms": self.route_prep_ms,
                "route_pieces": self.route_pieces,
                "shard_rows_max": self.shard_rows_max,
                "shard_capacity": self.shard_capacity,
                # a tumbling window's: the groups this step's flush
                # delivered; 1 where the step was a scheduler's TIMER
                # step (its batch id is that of the send whose clock
                # advance fired it); None for every other
                "flush_rows": self.flush_rows,
                "timer_steps": self.timer_steps,
                # the ms this batch spent growing key capacity (the
                # ``siddhi.grow`` spans; None: it forced no growth), and
                # the state its step left on the device: bytes, and the
                # ring slots of a keyed window (None: no keyed ring)
                "grow_ms": self.grow_ms,
                "state_bytes": self.state_bytes,
                "state_slots": self.state_slots,
                # the two sub-stages of dispatch that have a span of
                # their own (``siddhi.key``, ``siddhi.launch``) and the
                # bytes the launch took to the device; None: not run
                "key_ms": self.key_ms,
                "launch_ms": self.launch_ms,
                "h2d_bytes": self.h2d_bytes,
            }
            _RING.append(self._rec)


class _EmitStage:
    """``Journey.emitting``: the one place the emit stage is timed."""

    __slots__ = ("jr", "app_context", "names", "_span", "_prev")

    def __init__(self, jr, app_context, names, rows_out):
        self.jr, self.app_context, self.names = jr, app_context, names
        jr.rows_out = rows_out
        self._span = span("emit", query=names[0], batch=jr.batch)

    def __enter__(self):
        self._prev = getattr(_TLS, "emitting", None)
        _TLS.emitting = self.jr
        self._span.__enter__()
        return self.jr

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        _TLS.emitting = self._prev
        self.jr.emit_ms = self._span.ms or 0.0
        self.jr.finish(self.app_context, self.names)
        return False


class _KeyStage:
    """``keying``: the one place the key sub-stage is timed."""

    __slots__ = ("jr", "_size", "_before", "_span")

    def __init__(self, jr, query, rows, size):
        self.jr, self._size, self._before = jr, size, size()
        self._span = span("key", query=query, rows=rows,
                          batch=jr.batch if jr is not None else None)

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        keys = self._size()
        self._span.note(keys=keys, new_keys=max(0, keys - self._before))
        self._span.__exit__(*exc)
        if self.jr is not None:
            self.jr.keyed(self._span.ms)
        return False


def keying(jr: Optional[Journey], query: str, rows: int, size):
    """The key sub-stage of dispatch as a context manager: a
    ``siddhi.key`` span around the partition-key and group-key
    computation and the capacity check of ``rows`` rows, with ``keys``
    (what ``size()`` says once they ran: the dictionary's size) and
    ``new_keys`` (its growth: the ids this batch allocated); at the close
    ``key_ms`` is stamped on ``jr``. The shared no-op, and ``size`` never
    called, when spans are off."""
    if not tracing.spans_on():
        return tracing.NOOP
    return _KeyStage(jr, query, rows, size)


def emitting_journey() -> Optional[Journey]:
    """The journey whose emit stage is open on this thread, if any."""
    return getattr(_TLS, "emitting", None)


class _Sending:
    """``sending``: the batch whose send is advancing the clock."""

    __slots__ = ("jr", "_prev")

    def __init__(self, jr):
        self.jr = jr

    def __enter__(self):
        self._prev = getattr(_TLS, "sending", None)
        _TLS.sending = self.jr
        return self

    def __exit__(self, *exc):
        _TLS.sending = self._prev
        return False


def sending(batch):
    """Held by ``InputHandler.send_columns`` while it advances the playback
    clock for ``batch``: the TIMER steps the scheduler fires meanwhile (a
    tumbling window's flush) are that send's work, and
    ``QueryRuntime.process_timer`` gives their chunk's stamp and their
    ``siddhi.timer`` span its batch id. The shared no-op when journeys are
    off."""
    jr = getattr(batch, "journey", None) if _ENABLED else None
    return tracing.NOOP if jr is None else _Sending(jr)


def sending_batch() -> Optional[int]:
    """The batch id of the send advancing the clock on this thread."""
    jr = getattr(_TLS, "sending", None)
    return jr.batch if jr is not None else None


def pack_span():
    """The ``siddhi.pack`` span of a batch about to be built
    (``HostBatch.from_events``/``from_columns``), carrying the batch id
    its journey will keep; the shared no-op when spans are off."""
    if not tracing.spans_on():
        return tracing.NOOP
    return span("pack", batch=next(_BATCH_SEQ))


def stamp_pack(batch, sp, pack_ms: Optional[float] = None) -> None:
    """Attach the pack-stage stamp to the batch that ``sp`` (a closed
    :func:`pack_span`) covered. Pack service is the span's duration,
    unless the caller computed its own: the parallel ingest pack
    (``core/event._parallel_from_events``) attributes max-over-sub-batches
    plus the serial merge, per the max-not-sum rule (concurrent packer
    time must not count once per worker)."""
    if _ENABLED and sp.ms is not None:
        batch.journey = Journey(
            pack_ms=sp.ms if pack_ms is None else float(pack_ms),
            batch=sp.args["batch"])


def batch_of(batch) -> Optional[int]:
    """The batch id a delivered batch carries from its pack, if any: what
    the spans opened before the receiver's own journey begins
    (``junction.dispatch``, ``query.step``) give as ``batch=``."""
    jr = getattr(batch, "journey", None)
    return jr.batch if jr is not None else None


def begin(batch=None) -> Journey:
    """Per-receiver journey for a delivered batch: forks the batch's
    pack stamp (N receivers must not share mutable stage state) and
    opens the dispatch stage. A batch without a stamp was not packed
    here: re-published by the query whose emit stage is open on this
    thread, it keeps that journey's batch id; with neither (an NFA timer
    sweep) the journey starts here, with a batch id of its own."""
    src = getattr(batch, "journey", None)
    if src is not None:
        jr = src.fork()
    else:
        up = emitting_journey()
        jr = Journey(batch=up.batch if up is not None else None)
    jr.begin_dispatch()
    return jr


# --------------------------------------------------------------- analyzer

# residence in the queue stage must dominate every service mean by this
# factor before the analyzer blames the queue itself: queueing time is
# a symptom, and a modest wait in front of a genuinely busy stage should
# name the busy stage, not the line in front of it
_QUEUE_DOMINANCE = 2.0

_STAGE_KINDS = ("service", "queue")


def _parse_stage_hists(hist_snapshot: dict) -> Dict[str, dict]:
    """``stage.<query>.<stage>.<kind>_ms`` histogram snapshots grouped
    as {query: {stage: {kind: snap}}} (query names may contain dots —
    the stage/kind tail is fixed, so parse from the right)."""
    out: Dict[str, dict] = {}
    for name, snap in hist_snapshot.items():
        if not name.startswith("stage."):
            continue
        rest = name[len("stage."):]
        parts = rest.rsplit(".", 2)
        if len(parts) != 3:
            continue
        query, stage, kind_ms = parts
        if not kind_ms.endswith("_ms"):
            continue
        kind = kind_ms[:-3]
        if stage not in STAGES or kind not in _STAGE_KINDS:
            continue
        out.setdefault(query, {}).setdefault(stage, {})[kind] = snap
    return out


def _parse_device_signals(hist_snapshot: dict,
                          gauge_snapshot: dict) -> Dict[str, dict]:
    """``device.<query>.<slot>`` instrument histograms paired with their
    ``.capacity`` gauges, grouped per query (slot names come from the
    DEVICE_SLOTS declaration in export.py — the graftlint-R6-checked
    tuple, so a newly declared slot is visible here by construction;
    query names may contain dots, so parse from the right against the
    known slot set)."""
    from siddhi_tpu.observability.export import DEVICE_SLOTS

    slots = sorted(DEVICE_SLOTS, key=len, reverse=True)
    out: Dict[str, dict] = {}
    for name, snap in hist_snapshot.items():
        if not name.startswith("device."):
            continue
        rest = name[len("device."):]
        for slot in slots:
            if rest.endswith("." + slot):
                query = rest[: -len(slot) - 1]
                cap = gauge_snapshot.get(f"device.{query}.{slot}.capacity")
                out.setdefault(query, {})[slot] = {
                    "snap": snap, "capacity": cap}
                break
    return out


def _device_structure(device_slots: Optional[dict]) -> Optional[dict]:
    """The most saturated device structure of one query, from its
    drained instrument histograms: max p99/capacity ratio across slots
    with a known capacity — the thing to name when the device stage is
    the bottleneck ('join right side partition fill p99 = 0.97 of
    Wp')."""
    from siddhi_tpu.observability.instruments import (
        RESIDUAL_SLOTS, SLOT_CAP_NAMES, SLOT_LABELS)

    best = None
    for slot, rec in (device_slots or {}).items():
        cap = rec.get("capacity")
        if not cap or cap != cap:      # missing or NaN denominator
            continue
        label = SLOT_LABELS.get(slot, slot)
        cap_name = SLOT_CAP_NAMES.get(slot, "capacity")
        if slot in RESIDUAL_SLOTS:
            # a residual saturates toward ZERO: the worst case over the
            # window is the MINIMUM residual seen, not a high quantile
            # (p99 would be the healthiest batch)
            quoted = float(rec["snap"].get("min", 0.0))
            ratio = max(0.0, 1.0 - quoted / float(cap))
            text = (f"{label} min = {quoted:.0f} of {cap_name} "
                    f"({ratio:.2f} saturated)")
        else:
            quoted = float(rec["snap"].get("p99", 0.0))
            ratio = quoted / float(cap)
            text = f"{label} p99 = {ratio:.2f} of {cap_name}"
        if best is None or ratio > best["ratio"]:
            best = {
                "slot": slot,
                "label": label,
                # the quoted statistic: p99 for fill-style slots, MIN
                # for residuals (the field name must not lie about it)
                "stat": "min" if slot in RESIDUAL_SLOTS else "p99",
                "value": round(quoted, 3),
                "capacity": float(cap),
                "ratio": round(ratio, 4),
                "text": text,
            }
    return best


def _query_report(app: str, query: str, stages: Dict[str, dict],
                  device_slots: Optional[dict] = None) -> dict:
    per_stage = {}
    for stage in STAGES:
        kinds = stages.get(stage)
        if not kinds:
            continue
        service = kinds.get("service") or {}
        queue = kinds.get("queue") or {}
        per_stage[stage] = {
            "batches": int(service.get("count") or queue.get("count") or 0),
            "service_ms": service,
            "queue_ms": queue,
            "busy_ms": round(float(service.get("sum", 0.0)), 3),
            "mean_service_ms": round(
                float(service.get("sum", 0.0))
                / max(1, int(service.get("count", 0))), 4),
            "mean_queue_ms": round(
                float(queue.get("sum", 0.0))
                / max(1, int(queue.get("count", 0))), 4),
        }
    wall = _WALL.get((app, query))
    wall_ms = (wall[1] - wall[0]) * 1000.0 if wall else 0.0

    # bottleneck: largest mean service per batch; a queue-stage
    # residence dominating every service mean names the queue itself
    best_stage, best_mean = None, -1.0
    for stage, rec in per_stage.items():
        if stage == "queue":
            continue
        if rec["mean_service_ms"] > best_mean:
            best_stage, best_mean = stage, rec["mean_service_ms"]
    queue_rec = per_stage.get("queue")
    if queue_rec is not None:
        q_mean = queue_rec["mean_queue_ms"]
        if q_mean > 0 and q_mean >= _QUEUE_DOMINANCE * max(best_mean, 0.0):
            best_stage, best_mean = "queue", q_mean
    structure = _device_structure(device_slots)
    bottleneck = None
    if best_stage is not None:
        rec = per_stage[best_stage]
        busy = (float(rec["queue_ms"].get("sum", 0.0))
                if best_stage == "queue" else rec["busy_ms"])
        bottleneck = {
            "stage": best_stage,
            "kind": "queueing" if best_stage == "queue" else "service",
            "mean_ms": round(best_mean, 4),
            "utilization": round(min(1.0, busy / wall_ms), 4)
            if wall_ms > 0 else None,
        }
        if best_stage == "device" and structure is not None:
            # the device is the bottleneck AND its instruments say which
            # structure is saturated — name it right in the verdict
            bottleneck["structure"] = structure["text"]
    report = {"stages": per_stage, "wall_ms": round(wall_ms, 3),
              "bottleneck": bottleneck}
    if structure is not None:
        report["device_structure"] = structure
    return report


def critical_path_report(manager, app_name: Optional[str] = None) -> dict:
    """Aggregate the per-stage histograms into the critical-path report
    (per app, per query): stage service/queue quantiles, busy time,
    observed wall, and the named bottleneck stage with its utilization.
    Correct under pipelining: overlapped stages were attributed by max
    at record time (see ``Journey.device_times``), so a host-bound
    pipeline never shows the device as busy for the full wall."""
    runtimes = manager.app_runtimes
    if app_name is not None:
        rt = runtimes.get(app_name)
        if rt is None:
            raise KeyError(f"app '{app_name}' is not deployed")
        runtimes = {app_name: rt}
    apps = {}
    for name in sorted(runtimes):
        rt = runtimes[name]
        tel = rt.app_context.telemetry
        snap = tel.snapshot()
        hists = snap.get("histograms", {})
        # device instruments (on by default, independent of journey
        # tracing): when the device stage is the bottleneck, the report
        # names the saturated structure behind it
        device = _parse_device_signals(hists, snap.get("gauges", {}))
        queries = {
            q: _query_report(name, q, stages, device_slots=device.get(q))
            for q, stages in sorted(_parse_stage_hists(hists).items())
        }
        apps[name] = {"queries": queries}
    return {
        "enabled": enabled(),
        "stage_glossary": list(STAGES),
        "recent_journeys": len(_RING),
        "apps": apps,
    }
