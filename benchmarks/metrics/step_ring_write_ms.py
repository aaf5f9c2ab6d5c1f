"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.ring_write``: the keyed length window's
ring writes (``ops/keyed_windows.py`` ``_ring_write``), one sort of
(slot, word) and one scatter a ring leaf, eleven leaves in the
partitioned cells. It says how the scatters were lowered: at 131,072,000
slots a leaf's write is 1-2 ms where windows of the ring pass through
fast memory and 5.5-6.0 ms where the updates go one after another
(PERF.md section 7). Mean over the device planes; the scope is read from
the ``tf_op`` of each ``XLA Ops`` event's metadata, as ``step_merge_ms``
reads its own (benchmarks/metrics/_route.py). Nothing on a trace of a
program without the scope (no keyed length window; the parent of PR 36).
Moves ``events_per_s``."""

from __future__ import annotations

import functools
import re

from benchmarks import tracereduce
from benchmarks.metrics import _route, _spans
from benchmarks.tracereduce import SEND

SCOPE = re.compile(r"siddhi\.(ring_write)\b")


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events as ``tracereduce.load``
    gives them; ``ring_write``: {device plane: [[start_ns, duration_ns],
    ...]}, the ``XLA Ops`` events traced in the scope. Plain lists: a cut
    of a real trace is kept beside the test."""
    from jax.profiler import ProfileData

    host = [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name == SEND]
    try:
        scoped = _route.scoped_ops(path, SCOPE)
    except (ValueError, IndexError):
        scoped = {}               # laid out otherwise: goes unread
    return {"host": host,
            "ring_write": {plane: [[start, duration]
                                   for _scope, start, duration in ops]
                           for plane, ops in scoped.items()}}


def attribute(events: dict) -> dict | None:
    """Seconds in the scope inside the window (first send's start to the
    last one's end), mean over the device planes that hold such
    operations, and the sends. None where the trace has no
    ``bench.send_columns`` or no operation of the scope."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    planes = events.get("ring_write") or {}
    if not sends or not planes:
        return None
    lo, hi = sends[0][0], sends[-1][1]
    inside = sum(max(0.0, min(s + d, hi) - max(s, lo))
                 for ops in planes.values() for s, d in ops)
    return {"scope_s": inside / 1e9 / len(planes), "sends": len(sends)}


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return attribute(load(path))


def read(ctx):
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    got = _of_file(path) if path else None
    if not got:
        return None
    return got["scope_s"] / got["sends"] * 1e3
