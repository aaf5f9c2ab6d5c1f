"""Shared by the three readers of a tumbling window's flush
(``ops/tumbling_agg.py``): the device's time in ``siddhi.flush`` and the
two journey fields a flush stamps.

The folded tumbling stage traces what a flush computes and emits (the
sort of the groups seen, the gathers of their accumulators and keys) in
``jax.named_scope`` ``siddhi.flush``, inside the branch of a ``lax.cond``
that only a step that closes a window takes; the scope nests in
``siddhi.state``. It reaches the trace as the ``tf_op`` stat of an ``XLA
Ops`` event's METADATA, read with ``_route.scoped_ops`` under a pattern
of its own. The operations of one flush follow each other within
microseconds and two flushes are batches apart, so a flush is a run of
such operations with no gap above ``GAP_NS``.

The journey (``observability/journey.py``): ``flush_rows`` is the rows a
step delivered where that step closed a window (groups emitted; None for
every other step), ``timer_steps`` is 1 on the journey of a TIMER step
(None on a data step's); the TIMER steps a send's clock advance fires
carry that send's ``batch`` id.

A program without the scope or the fields (the parent of PR 31; any
other query) gives ``None``: nothing to read, nothing returned.
"""

from __future__ import annotations

import functools
import re

from benchmarks import tracereduce
from benchmarks.metrics import _route, _spans
from benchmarks.tracereduce import SEND

SCOPE = re.compile(r"siddhi\.(flush)\b")
GAP_NS = 1e6      # operations further apart belong to two flushes


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events as ``tracereduce.load``
    gives them; ``flush``: the operations of the scope by device plane.
    Plain lists: a cut of a real trace is kept beside the test."""
    from jax.profiler import ProfileData

    host = [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name == SEND]
    try:
        flush = _route.scoped_ops(path, SCOPE)
    except (ValueError, IndexError):
        flush = {}                # laid out otherwise: goes unread
    return {"host": host, "flush": flush}


def attribute(events: dict) -> dict | None:
    """Of the ``siddhi.flush`` operations inside the window (first
    ``bench.send_columns`` start to the last one's end), per device plane:
    their seconds and the flushes they make up; mean over the planes.
    None where the trace has no send or no such operation."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    scoped = events.get("flush") or {}
    if not sends or not scoped:
        return None
    lo, hi = sends[0][0], sends[-1][1]
    busy = flushes = 0
    for ops in scoped.values():
        at = None
        for _scope, s, d in sorted(ops, key=lambda op: op[1]):
            if s < lo or s + d > hi:
                continue
            busy += d
            if at is None or s - at > GAP_NS:
                flushes += 1
            at = s + d
    if not flushes:
        return None
    return {"flush_s": busy / 1e9 / len(scoped),
            "flushes": flushes / len(scoped)}


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return attribute(load(path))


def flush_ms() -> float | None:
    """Device milliseconds a flush, mean of the traced flushes, of the
    trace this process's run wrote."""
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    got = _of_file(path) if path else None
    return got["flush_s"] / got["flushes"] * 1e3 if got else None


def per_flush(ctx, field) -> float | None:
    """The sum of a journey field over the window's journeys, over the
    flushes among them (the journeys whose ``flush_rows`` is above 0)."""
    flushes = sum(1 for j in ctx["journeys"] if j.get("flush_rows"))
    if not flushes:
        return None
    return sum(j.get(field) or 0 for j in ctx["journeys"]) / flushes
