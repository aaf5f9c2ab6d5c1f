"""Read by ``step_ring_pass_ms``: the device's time in the operations of
a keyed ring write that read or write a WHOLE ring column without being
the scatter. Their time grows with the state (key capacity x window), not
with the batch.

``ops/keyed_windows.py`` ``_ring_write`` writes an int64 ring as its two
uint32 words. Around the two word scatters it takes the ring apart and
puts it together again, and traces those operations in
``jax.named_scope`` ``siddhi.ring_pass`` (inside ``siddhi.state``): the
high plane's pass and the re-join. The scope reaches the trace as the
``tf_op`` stat of an ``XLA Ops`` event's METADATA (``_spans.py``). The
compiler's own 64-bit rewrite adds, with no scope at all, the custom calls
``X64SplitLow`` / ``X64SplitHigh`` / ``X64Combine``, timed copies on the
v5e (PERF.md section 5); an event's name is its HLO line, which holds the
call's target and its shape, so those on a ring column are the ones whose
shape is ``[slots]`` long, ``slots`` being the journey's ``state_slots``
(key capacity x window). An ``X64*`` call carries no scope (its
``tf_op`` names the parameter it splits, or nothing), so no event is found
both ways.

The window (first ``bench.send_columns`` start to the last one's end),
the sends and the division over the device planes are ``_spans``'s.

A trace of a program without the scope and without the journey field
(the parent of PR 33) gives ``None``: nothing to read, nothing returned.
A program that comes back from a compile cache filled before the scope
existed (JAX's cache key leaves scopes out) has the custom calls only.
"""

from __future__ import annotations

import functools
import re

from benchmarks import tracereduce
from benchmarks.metrics import _route, _spans
from benchmarks.tracereduce import DEVICE_PLANE, OPS_LINE, SEND

SCOPE = re.compile(r"siddhi\.(ring_pass)\b")
X64 = re.compile(r"X64(?:SplitLow|SplitHigh|Combine)")
SHAPE = re.compile(r"\[(\d+)\]")


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events as ``tracereduce.load``
    gives them; ``ring``: {device plane: [[kind, elements, start_ns,
    duration_ns], ...]}, the ``XLA Ops`` events traced in the scope
    (``kind`` "scope", ``elements`` 0: ``_route.scoped_ops`` under this
    pattern) and the 64-bit rewrite's custom calls (``kind`` "x64";
    ``elements`` the longest one-dimensional shape in the event's name, 0:
    none). Plain lists: a cut of a real trace is kept beside the test."""
    from jax.profiler import ProfileData

    host, ring = [], {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == "/host:CPU":
                host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                         for e in line.events if e.name == SEND]
            elif plane.name.startswith(DEVICE_PLANE) and line.name == OPS_LINE:
                ring.setdefault(plane.name, []).extend(
                    ["x64", max(map(int, SHAPE.findall(e.name)), default=0),
                     float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if X64.search(e.name))
    try:
        scoped = _route.scoped_ops(path, SCOPE)
    except (ValueError, IndexError):
        scoped = {}               # laid out otherwise: goes unread
    for plane, ops in scoped.items():
        ring.setdefault(plane, []).extend(
            ["scope", 0, start, duration] for _scope, start, duration in ops)
    return {"host": host, "ring": {p: ops for p, ops in ring.items() if ops}}


def attribute(events: dict, slots: int | None) -> dict | None:
    """Seconds in the scope and in the custom calls on ``[slots]``-long
    columns inside the window, mean over the device planes that hold
    either, and the sends. None where the trace has no
    ``bench.send_columns`` or no such operation."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    ring = {plane: [op for op in ops
                    if op[0] == "scope" or (slots and op[1] == slots)]
            for plane, ops in (events.get("ring") or {}).items()}
    ring = {plane: ops for plane, ops in ring.items() if ops}
    if not sends or not ring:
        return None
    lo, hi = sends[0][0], sends[-1][1]
    kind_s = {"scope": 0.0, "x64": 0.0}
    for ops in ring.values():
        for kind, _n, s, d in ops:
            kind_s[kind] += max(0.0, min(s + d, hi) - max(s, lo))
    return {"kind_s": {k: v / 1e9 / len(ring) for k, v in kind_s.items()},
            "sends": sum(1 for s in sends if s[1] <= hi)}


@functools.lru_cache(maxsize=2)
def _loaded(path: str) -> dict:
    return load(path)


def ring_pass_ms(ctx) -> float | None:
    """Device milliseconds a batch in whole-ring passes, of the trace
    this process's run wrote; the ring's length from the window's first
    journey that states it."""
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    if not path:
        return None
    slots = next((j["state_slots"] for j in ctx["journeys"]
                  if j.get("state_slots")), None)
    got = attribute(_loaded(path), slots)
    if not got or not got["sends"]:
        return None
    return sum(got["kind_s"].values()) / got["sends"] * 1e3
