"""InputHandler / InputManager: API entry for pushing events.

Mirror of reference ``core/stream/input/InputHandler.java:59`` (``send``
variants set the playback clock then forward into the junction) and
``InputManager.java``. The snapshot quiesce gate (``InputEntryValve`` +
``ThreadBarrier``) is a host-side RLock here.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

from siddhi_tpu.core.event import Event
from siddhi_tpu.core.stream.junction import StreamJunction


class InputHandler:
    def __init__(self, stream_id: str, junction: StreamJunction, app_context, barrier: threading.RLock,
                 ensure_started=None):
        self.stream_id = stream_id
        self.junction = junction
        self.app_context = app_context
        self._barrier = barrier
        self._ensure_started = ensure_started
        self._last_ts = None   # @app:enforceOrder monotonicity watermark

    def _check_order(self, first_ts: int, last_ts: int):
        """@app:enforceOrder: reject out-of-order ingestion on this stream
        (the reference carries the flag on SiddhiAppContext with no
        enforcement anywhere — here it buys a real guarantee: a send whose
        timestamp precedes the stream's watermark raises instead of
        silently reordering window/pattern state)."""
        if self._last_ts is not None and first_ts < self._last_ts:
            raise ValueError(
                f"@app:enforceOrder: event timestamp {first_ts} precedes "
                f"stream '{self.stream_id}' watermark {self._last_ts}")
        self._last_ts = last_ts if self._last_ts is None \
            else max(self._last_ts, last_ts)

    def send(self, *args):
        """send(data_list) | send(ts, data_list) | send(Event) | send([Event,...])"""
        if getattr(self.app_context, "stopped", False):
            # reference: sends after shutdown fail (the disruptor is gone,
            # StartStopTestCase test1 expects an exception)
            raise RuntimeError(
                f"SiddhiApp '{self.app_context.name}' has been shut down — "
                f"cannot send to '{self.stream_id}'")
        if self._ensure_started is not None:
            self._ensure_started()
        tsg = self.app_context.timestamp_generator
        if len(args) == 1:
            a = args[0]
            if isinstance(a, Event):
                events = [a]
            elif isinstance(a, (list, tuple)) and a and isinstance(a[0], Event):
                events = list(a)
            else:
                events = [Event(timestamp=tsg.current_time(), data=list(a))]
        elif len(args) == 2 and isinstance(args[0], int):
            events = [Event(timestamp=args[0], data=list(args[1]))]
        else:
            raise TypeError(f"unsupported send arguments: {args!r}")
        for ev in events:
            if ev.timestamp < 0:
                ev.timestamp = tsg.current_time()
        wal = getattr(self.app_context, "ingest_wal", None)
        replaying = wal is not None and wal.in_replay()
        with self._barrier:  # snapshot quiesce gate (ThreadBarrier.java:30-36)
            # order check INSIDE the barrier (atomic with delivery order)
            # and BEFORE the clock advances — a rejected batch must not
            # fire timers or expire windows as a side effect. A WAL replay
            # bypasses the watermark: the suffix re-enters with its
            # ORIGINAL (already-validated, arrival-ordered) timestamps,
            # which an in-process restore's watermark has already passed.
            if self.app_context.enforce_order and events and not replaying:
                ts_seq = [e.timestamp for e in events]
                if any(b < a for a, b in zip(ts_seq, ts_seq[1:])):
                    raise ValueError(
                        f"@app:enforceOrder: non-monotone timestamps inside "
                        f"a batch on stream '{self.stream_id}'")
                self._check_order(ts_seq[0], ts_seq[-1])
            # WAL boundary (resilience/replay.py): the batch is ACCEPTED
            # once validation passed — record before delivery, inside the
            # snapshot barrier so a checkpoint always cuts between batches.
            # The record's seq rides to the junction: if quota admission
            # SHEDS the batch (resilience/overload.py) the record is
            # discarded, keeping replay exactly the non-shed suffix.
            wal_seq = None
            if wal is not None:
                wal_seq = wal.record_events(self.stream_id, events)
            for ev in events:
                tsg.set_current_timestamp(ev.timestamp)
            self.junction.send_events(events, wal_seq=wal_seq)

    def send_columns(self, data, timestamps=None):
        """Columnar bulk ingestion — the TPU-native fast path: one numpy
        array per attribute (strings as str arrays or pre-encoded int ids),
        optional per-row timestamps. Skips Event objects entirely; receivers
        that understand batches consume them directly."""
        import numpy as np

        from siddhi_tpu.core.event import HostBatch, pack_pool_of
        from siddhi_tpu.observability import journey

        if self._ensure_started is not None:
            self._ensure_started()
        tsg = self.app_context.timestamp_generator
        now = tsg.current_time()
        batch = HostBatch.from_columns(
            data, self.junction.definition,
            self.app_context.string_dictionary,
            timestamps=timestamps, default_ts=now,
            pool=pack_pool_of(self.app_context))
        wal = getattr(self.app_context, "ingest_wal", None)
        replaying = wal is not None and wal.in_replay()
        with self._barrier:
            if timestamps is not None:
                ts_arr = np.asarray(timestamps, np.int64)
                if ts_arr.size:
                    # order check before the clock advances (see send();
                    # a WAL replay bypasses the watermark)
                    if self.app_context.enforce_order and not replaying:
                        if np.any(ts_arr[1:] < ts_arr[:-1]):
                            raise ValueError(
                                f"@app:enforceOrder: non-monotone timestamps "
                                f"inside a batch on stream '{self.stream_id}'")
                        self._check_order(int(ts_arr[0]), int(ts_arr[-1]))
                    # advance in two hops so clock listeners observe the
                    # batch's EARLIEST timestamp first (a head-absent wait
                    # must anchor at the first event, not the batch max)
                    lo = int(ts_arr.min())
                    hi = int(ts_arr.max())
                    # the timers this advance fires are this batch's work
                    with journey.sending(batch):
                        if lo != hi:
                            tsg.set_current_timestamp(lo)
                        tsg.set_current_timestamp(hi)
            wal_seq = None
            if wal is not None:
                # raw columns, not the encoded HostBatch: replay re-encodes
                # against the restored dictionary. Timestamps are recorded
                # RESOLVED — a default-stamped batch must replay at its
                # original ingest time, not the replay wall clock
                wal_seq = wal.record_columns(
                    self.stream_id, data,
                    timestamps if timestamps is not None
                    else np.full(int(batch.size), now, np.int64))
            self.junction.send_batch(batch, wal_seq=wal_seq)


class InputManager:
    """Reference ``core/stream/input/InputManager.java``."""

    def __init__(self, app_context, junctions: Dict[str, StreamJunction], barrier: threading.RLock):
        self.app_context = app_context
        self._junctions = junctions
        self._barrier = barrier
        self._handlers: Dict[str, InputHandler] = {}
        self.ensure_started = None  # set by SiddhiAppRuntime (lazy app start)

    def get_input_handler(self, stream_id: str) -> InputHandler:
        h = self._handlers.get(stream_id)
        if h is None:
            if stream_id not in self._junctions:
                raise KeyError(f"stream '{stream_id}' is not defined")
            h = InputHandler(stream_id, self._junctions[stream_id], self.app_context, self._barrier,
                             ensure_started=lambda: self.ensure_started and self.ensure_started())
            self._handlers[stream_id] = h
        return h
