"""Structured tracing spans: one primitive, two records.

A span covers one host-side stage of the pipeline (compile, plan, jit,
pack, junction dispatch, query step with its two sub-stages key and
launch, route prepare, meta pull, emit, output pull, sink publish,
persist) at batch granularity. ``span(...)``
is the ONLY way the engine opens one. While it is on, a span

- lands in the Chrome-trace ring of ``TRACER`` (when ``TRACER`` is
  started: ``POST /trace/start``), and
- enters ``jax.profiler.TraceAnnotation("siddhi.<name>", **args)``, so
  that a profiler trace (``SiddhiAppRuntime.start_trace``, the
  benchmark's ``--trace 1``) holds it on ``/host:CPU`` on the clock of
  the device planes: an idle gap of the device can be put down to the
  engine stage the host was in. Outside a profiler session the
  annotation is a flag check inside the profiler.

Spans are on while ``TRACER`` is started or batch journeys are enabled
(``journey.enable`` sets ``profiler_spans`` for as long as anybody holds
it: the benchmark's traced run, ``start_trace`` and the REST profiler
routes). ``batch=`` in a span's arguments is the per-process sequence
number of the batch (``journey.py``): the spans of one batch share it.

Design constraints, in priority order:

1. **Near-zero cost when disabled.** ``span(...)`` checks the two
   switches in one expression and returns a shared no-op context
   manager — no allocation beyond the kwargs dict, no locks. The hot
   path (pack, junction dispatch, query step) runs it per *batch*, not
   per event.
2. **Thread-safe when enabled.** Spans finish in LIFO order per thread
   (context managers), so nesting is correct by construction; the ring
   buffer is a ``deque(maxlen=...)`` whose appends are atomic under the
   GIL. When full, the OLDEST span falls off (``dropped`` counts them) —
   tracing never grows without bound and never blocks.
3. **Standard output.** ``to_chrome_trace()`` emits the Trace Event
   Format (complete events, ``ph: "X"`` with pid/tid/ts/dur/name/args)
   that ``chrome://tracing`` and Perfetto load directly.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

_DEFAULT_CAPACITY = 65_536


class _FinishedSpan:
    __slots__ = ("name", "tid", "ts_us", "dur_us", "args")

    def __init__(self, name, tid, ts_us, dur_us, args):
        self.name = name
        self.tid = tid
        self.ts_us = ts_us
        self.dur_us = dur_us
        self.args = args


class _NoopSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()
    ms = None     # a real span's duration once closed; None: not timed

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args):
        pass


NOOP = _NoopSpan()


class _Span:
    """One open span. ``ms`` is its duration once it has closed: the
    site that opened it stamps the batch's journey from it, so the two
    records cannot drift."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_ann", "ms")

    def __init__(self, tracer: "Tracer", name: str, args: dict,
                 annotate: bool = False):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.ms = None
        self._ann = None
        if annotate:
            self._ann = TraceAnnotation("siddhi." + name, **{
                k: _jsonable(v) for k, v in args.items() if v is not None})

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.ms = (t1 - self._t0) / 1e6
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False

    def note(self, **args):
        """Arguments known only once the stage has run (the keys a batch
        allocated): added to both records while the span is open."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**{
                k: _jsonable(v) for k, v in args.items() if v is not None})


class Tracer:
    """Ring-buffered span collector (one per process — see ``TRACER``)."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self.enabled = False
        self.capacity = int(capacity)
        self._buf: deque = deque(maxlen=self.capacity)
        # guards buffer swaps and export snapshots against concurrent
        # producer appends ("deque mutated during iteration"); producers
        # hold it only for one append, so contention is one span long
        self._lock = threading.Lock()
        self.dropped = 0
        self._epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------ control

    def start(self, capacity: Optional[int] = None) -> None:
        """Enable collection into a fresh ring buffer."""
        with self._lock:
            if capacity is not None:
                self.capacity = int(capacity)
            self._buf = deque(maxlen=self.capacity)
            self.dropped = 0
            self._epoch_ns = time.perf_counter_ns()
            self.enabled = True

    def stop(self) -> dict:
        """Disable collection and return the Chrome-trace JSON object."""
        self.enabled = False
        return self.to_chrome_trace()

    def clear(self) -> None:
        with self._lock:
            self._buf = deque(maxlen=self.capacity)
            self.dropped = 0

    def __len__(self) -> int:
        return len(self._buf)

    # ---------------------------------------------------------- recording

    def span(self, name: str, **args):
        """A span of this tracer's ring only (no profiler annotation)."""
        if not self.enabled:
            return NOOP
        return _Span(self, name, args)

    def _record(self, name: str, t0_ns: int, t1_ns: int, args: dict):
        if not self.enabled:
            return     # stopped while the span was open
        span_rec = _FinishedSpan(
            name, threading.get_ident(),
            (t0_ns - self._epoch_ns) / 1000.0,
            max(t1_ns - t0_ns, 1) / 1000.0,
            args)
        with self._lock:
            if not self.enabled:
                return   # a racing stop() export must not see new appends
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1     # deque evicts the oldest on append
            self._buf.append(span_rec)

    # ------------------------------------------------------------- export

    def to_chrome_trace(self) -> dict:
        """Trace Event Format: complete events sorted by (tid, ts) so
        parents precede children, plus process/thread metadata."""
        pid = os.getpid()
        with self._lock:   # snapshot against concurrent producer appends
            buf = list(self._buf)
            dropped = self.dropped
        spans = sorted(buf, key=lambda s: (s.tid, s.ts_us))
        events = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "siddhi_tpu"},
        }]
        for s in spans:
            ev = {
                "name": s.name,
                "cat": "siddhi",
                "ph": "X",
                "pid": pid,
                "tid": s.tid,
                "ts": round(s.ts_us, 3),
                "dur": round(s.dur_us, 3),
            }
            if s.args:
                ev["args"] = {k: _jsonable(v) for k, v in s.args.items()}
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": dropped},
        }


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


# process-global tracer: spans from every app/runtime in this process
# land in one timeline (pid/tid separate them), controlled by
# POST /trace/start|stop on the REST service or Tracer.start()/stop()
TRACER = Tracer()

# set by journey.enable/disable from their own count of holders: while
# true, spans enter their TraceAnnotation whether or not TRACER is started
_profiler_spans = False


def profiler_spans(on: bool) -> None:
    global _profiler_spans
    _profiler_spans = bool(on)


def spans_on() -> bool:
    return TRACER.enabled or _profiler_spans


def span(name: str, **args):
    """``with span("jit", key="q1") as sp: ...`` — the engine's one span
    primitive (module docstring); the shared no-op when spans are off."""
    if not (TRACER.enabled or _profiler_spans):
        return NOOP
    return _Span(TRACER, name, args, annotate=True)
