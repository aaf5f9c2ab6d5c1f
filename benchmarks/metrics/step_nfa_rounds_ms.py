"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.nfa_rounds``: the generic NFA engine's
round loop, a ``while_loop`` inside ``siddhi.state`` (so
``step_state_ms`` holds it). The scope is read from the ``tf_op`` of each
``XLA Ops`` event's metadata as ``step_ring_write_ms`` reads its own;
the time is the UNION of those events inside the window (a loop's own
event may span the operations of its body), per device plane, mean over
the planes, over the sends. Nothing on a trace of a program without the
scope (no generic NFA step; the parent of the PR that brought it).
Moves ``events_per_s``."""

from __future__ import annotations

import functools
import re

from benchmarks import tracereduce
from benchmarks.metrics import _route, _spans
from benchmarks.tracereduce import SEND, _clip, _union

SCOPE = re.compile(r"siddhi\.(nfa_rounds)\b")


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events; ``rounds``: {device
    plane: [[start_ns, duration_ns], ...]} of the scope's operations."""
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
            "rounds": {plane: [[start, duration]
                               for _scope, start, duration in ops]
                       for plane, ops in scoped.items()}}


def attribute(events: dict) -> dict | None:
    """Seconds of the union of the scope's operations inside the window,
    mean over the planes that hold any, and the sends; None where the
    trace has no send or no such operation."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    planes = events.get("rounds") or {}
    if not sends or not planes:
        return None
    lo, hi = sends[0][0], sends[-1][1]
    busy = sum(b - a for ops in planes.values()
               for a, b in _clip(_union([s, s + d] for s, d in ops), lo, hi))
    return {"scope_s": busy / 1e9 / len(planes), "sends": len(sends)}


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return attribute(load(path))


def read(ctx):
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    got = _of_file(path) if path else None
    if not got:
        return None
    return got["scope_s"] / got["sends"] * 1e3
