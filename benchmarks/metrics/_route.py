"""Shared by ``step_route_ms`` and ``step_merge_ms``: the device's time
in the two scopes that only a device-routed step has.

``parallel/mesh.py`` ``routed_step_for`` traces its ingress (owner,
bucketing, the exchange, the id rewrite) in ``jax.named_scope``
``siddhi.route`` and its egress (the order keys' gather and sort, the
columns' gather and permutation, the meta's cross-shard reductions) in
``siddhi.merge``, beside the inner step's three scopes and never around
them. As with those (``_spans.py``), the scope reaches the trace as the
``tf_op`` stat of an ``XLA Ops`` event's METADATA, read here off the
file's protobuf wire format with ``_spans._fields`` / ``_map_entry``
under a pattern of its own; ``_spans.scoped_ops`` keeps its three.

The window (first ``bench.send_columns`` start to the last one's end),
the sends and the division over the device planes that hold such
operations are ``_spans.attribute``'s: a scope's time is the plain sum of
its events inside the window (``XLA Ops`` events do not overlap on a
plane), per plane, averaged over the planes: on four chips, the mean of
the four. What an asynchronous collective leaves on ``XLA Ops`` is its
``-start`` and ``-done`` operations, so the sum counts the time the
core spent issuing and waiting, not the transfer that overlapped other
work.

A trace of a program without the scopes (an unrouted query; the parent
of PR 27) gives ``None``: nothing to read, nothing returned.
"""

from __future__ import annotations

import functools
import re

from benchmarks import tracereduce
from benchmarks.metrics import _spans
from benchmarks.tracereduce import DEVICE_PLANE, OPS_LINE, SEND

SCOPE = re.compile(r"siddhi\.(route|merge)\b")
SCOPES = ("route", "merge")


def scoped_ops(path: str, pattern=SCOPE) -> dict:
    """{device plane: [[scope, start_ns, duration_ns], ...]}: the
    ``XLA Ops`` events whose metadata's ``tf_op`` matches ``pattern``
    (its first group is the scope). The field numbers are those listed
    at ``_spans.scoped_ops``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _spans._fields(space):
        if field != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for field, value in _spans._fields(plane):
            if field == 2:
                name = str(value, "utf-8")
            elif field == 3:
                lines.append(value)
            elif field == 4:
                key, meta = _spans._map_entry(value)
                event_meta[key] = meta
            elif field == 5:
                key, meta = _spans._map_entry(value)
                stat_names[key] = str(
                    dict(_spans._fields(meta)).get(2, b""), "utf-8")
        if not name.startswith(DEVICE_PLANE):
            continue
        scope_of = {}
        for key, meta in event_meta.items():
            for field, stat in _spans._fields(meta):
                if field != 5:
                    continue
                stat = dict(_spans._fields(stat))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                op_name = (str(stat[5], "utf-8") if 5 in stat
                           else stat_names.get(stat.get(7), ""))
                found = pattern.search(op_name)
                if found:
                    scope_of[key] = found.group(1)
        ops = []
        for line in lines:
            line = list(_spans._fields(line))
            if str(dict(line).get(2, b""), "utf-8") != OPS_LINE:
                continue
            t0 = dict(line).get(3, 0)
            for field, event in line:
                if field != 4:
                    continue
                event = dict(_spans._fields(event))
                scope = scope_of.get(event.get(1))
                if scope:
                    ops.append([scope, t0 + event.get(2, 0) / 1e3,
                                event.get(3, 0) / 1e3])
        if ops:
            out[name] = ops
    return out


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events as ``tracereduce.load``
    gives them; ``routed``: the operations of the two scopes by device
    plane. Plain lists: a cut of a real trace is kept beside the test."""
    from jax.profiler import ProfileData

    host = [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name == SEND]
    try:
        routed = scoped_ops(path)
    except (ValueError, IndexError):
        routed = {}               # laid out otherwise: goes unread
    return {"host": host, "routed": routed}


def attribute(events: dict) -> dict | None:
    """Seconds of device time by scope (mean over the planes that hold
    either), the window, the sends and the planes: None where the trace
    has no ``bench.send_columns`` or no operation of either scope."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    routed = events.get("routed") or {}
    if not sends or not routed:
        return None
    lo, hi = sends[0][0], sends[-1][1]
    scope_s = dict.fromkeys(SCOPES, 0.0)
    for ops in routed.values():
        for scope, s, d in ops:
            scope_s[scope] += max(0.0, min(s + d, hi) - max(s, lo))
    return {
        "scope_s": {k: v / 1e9 / len(routed) for k, v in scope_s.items()},
        "window_s": (hi - lo) / 1e9,
        "sends": sum(1 for s in sends if s[1] <= hi),
        "planes": len(routed),
    }


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return attribute(load(path))


def scoped_ms(scope: str) -> float | None:
    """Device milliseconds a batch in operations traced in
    ``siddhi.<scope>``, of the trace this process's run wrote."""
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    got = _of_file(path) if path else None
    if not got or not got["sends"]:
        return None
    return got["scope_s"][scope] / got["sends"] * 1e3
