"""Shared by the ``exposed_*`` readers: the engine's own spans in the
profiler trace that ``run.py --trace 1`` wrote.

The engine opens every host span through ``observability/tracing.py``
``span``, which while tracing is on also enters
``jax.profiler.TraceAnnotation("siddhi.<name>", batch=<id>, ...)``: the
spans are events on ``/host:CPU`` beside ``bench.send_columns``, on the
clock of ``/device:TPU:<n>`` (looked at by hand on the v5e, PR 25;
PERF.md section 3).

- idle: the window (first ``bench.send_columns`` start to the last one's
  end) less the busy union, exactly as ``tracereduce.reduce`` takes them
  (its own ``_union`` and ``_clip``), per device plane, averaged.
- exposed: every idle instant goes to the INNERMOST ``siddhi.*`` span
  open on the host at that instant (a gap that several spans cover is
  split at their boundaries), and from the span to its layer: ``pack``;
  ``dispatch`` (the self time of ``junction.dispatch`` and
  ``query.step``); ``meta_pull``; ``emit`` (``emit`` and
  ``sink.publish``, which hold the user's callback); ``pull``. A
  ``junction.dispatch`` inside an ``emit`` is the delivery to the
  output stream's subscribers and counts as emit. Idle under no engine
  span (the generator between sends; the instants of a send between two
  spans, 0.3-0.4 ms a batch on the v5e) is ``other``, so the six sum to
  the reducer's idle.

- scoped: the device's time by the ``jax.named_scope`` its operations
  were traced in. Every step body is traced in three scopes
  (``siddhi.state``, ``siddhi.select``, ``siddhi.meta``:
  ``observability/instruments.py``), and XLA keeps the scope in the
  operation's ``op_name``. On the v5e that name is the ``tf_op`` stat of
  the ``XLA Ops`` event's METADATA (``jit(siddhi_query_step)/
  siddhi.state/gather:``; a fusion carries its root's), which
  ``ProfileData`` does not show: an event's ``stats`` there are the
  event's own three (offset, duration, time scale). So ``scoped_ops``
  reads those few fields of the file's protobuf wire format itself. The
  events of ``XLA Ops`` do not overlap (looked at on all three cells),
  so a scope's time is the plain sum of its events inside the window.

A trace of a program without the spans or the scopes (the parent of
PR 25) gives ``None`` everywhere: nothing to read, nothing returned. The
file is read once a process, with ``jax.profiler.ProfileData`` and, for
the scopes alone, the reader below.
"""

from __future__ import annotations

import functools
import os
import re

from benchmarks import tracereduce
from benchmarks.tracereduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                    SEND, _clip, _union)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")   # where run.py traces to

# span -> layer; junction.dispatch only where no other span is open
LAYER_OF = {
    "siddhi.pack": "pack",
    "siddhi.query.step": "dispatch",
    "siddhi.fanout.step": "dispatch",
    "siddhi.meta_pull": "meta_pull",
    "siddhi.emit": "emit",
    "siddhi.sink.publish": "emit",
    "siddhi.pull": "pull",
}
JUNCTION = "siddhi.junction.dispatch"
LAYERS = ("pack", "dispatch", "meta_pull", "emit", "pull", "other")
SCOPE = re.compile(r"siddhi\.(state|select|meta)\b")


def load(path: str) -> dict:
    """What ``tracereduce.load`` gives (so ``tracereduce.reduce`` reads
    it too), and beside it ``spans``: the engine's host spans as
    [name, start_ns, duration_ns, batch id or None]. Plain lists: a cut
    of a real trace is kept beside the test."""
    from jax.profiler import ProfileData

    devices, host, spans = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            devices[plane.name] = {
                line.name: [[tracereduce._short(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in (SEND, tracereduce.CALLBACK):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif e.name in LAYER_OF or e.name == JUNCTION:
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns),
                                      dict(e.stats).get("batch")])
    try:
        scoped = scoped_ops(path)
    except (ValueError, IndexError):
        # a file laid out otherwise than the reader expects: the scopes
        # go unread, and the run with its other metrics stands
        scoped = {}
    return {"devices": devices, "host": host, "spans": spans,
            "scoped": scoped}


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields (doubles) are passed over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        yield key >> 3, value


def _map_entry(buf):
    """(key, value message) of one entry of a ``map<int64, message>``."""
    got = dict(_fields(buf))
    return got.get(1, 0), got.get(2, b"")


def scoped_ops(path: str) -> dict:
    """{device plane: [[scope, start_ns, duration_ns], ...]}: the
    ``XLA Ops`` events whose metadata names a scope in its ``tf_op``
    stat (module docstring). From the ``XSpace`` message of
    ``tsl/profiler/protobuf/xplane.proto``: ``planes`` = 1; of an
    ``XPlane`` ``name`` = 2, ``lines`` = 3, ``event_metadata`` = 4,
    ``stat_metadata`` = 5; of an ``XLine`` ``name`` = 2,
    ``timestamp_ns`` = 3, ``events`` = 4; of an ``XEvent``
    ``metadata_id`` = 1, ``offset_ps`` = 2, ``duration_ps`` = 3; of an
    ``XEventMetadata`` ``stats`` = 5; of an ``XStat`` ``metadata_id`` =
    1, ``str_value`` = 5, ``ref_value`` = 7; of an ``XStatMetadata``
    ``name`` = 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for field, value in _fields(plane):
            if field == 2:
                name = str(value, "utf-8")
            elif field == 3:
                lines.append(value)
            elif field == 4:
                key, meta = _map_entry(value)
                event_meta[key] = meta
            elif field == 5:
                key, meta = _map_entry(value)
                stat_names[key] = str(dict(_fields(meta)).get(2, b""),
                                      "utf-8")
        if not name.startswith(DEVICE_PLANE):
            continue
        scope_of = {}
        for key, meta in event_meta.items():
            for field, stat in _fields(meta):
                if field != 5:
                    continue
                stat = dict(_fields(stat))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                op_name = (str(stat[5], "utf-8") if 5 in stat
                           else stat_names.get(stat.get(7), ""))
                found = SCOPE.search(op_name)
                if found:
                    scope_of[key] = found.group(1)
        ops = []
        for line in lines:
            line = list(_fields(line))
            if str(dict(line).get(2, b""), "utf-8") != OPS_LINE:
                continue
            t0 = dict(line).get(3, 0)
            for field, event in line:
                if field != 4:
                    continue
                event = dict(_fields(event))
                scope = scope_of.get(event.get(1))
                if scope:
                    ops.append([scope, t0 + event.get(2, 0) / 1e3,
                                event.get(3, 0) / 1e3])
        if ops:
            out[name] = ops
    return out


def _layer(open_spans) -> str:
    """The layer of an instant from the spans open then, innermost last."""
    for name in reversed(open_spans):
        if name != JUNCTION:
            return LAYER_OF[name]
    return "dispatch"        # a junction's own delivery loop


def segments(spans) -> list:
    """The host's timeline as disjoint [start, end, layer] pieces: the
    innermost open span decides (across threads: the one opened last)."""
    marks = sorted({t for _n, s, d, *_ in spans for t in (s, s + d)})
    starting = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, open_now, k = [], [], 0
    for i, t in enumerate(marks[:-1]):
        open_now = [sp for sp in open_now if sp[1] + sp[2] > t]
        while k < len(starting) and starting[k][1] <= t:
            if starting[k][2] > 0:
                open_now.append(starting[k])
            k += 1
        if open_now:
            out.append([t, marks[i + 1], _layer([sp[0] for sp in open_now])])
    return out


def attribute(events: dict) -> dict | None:
    """Seconds of device idle by layer, the window and the sends: None
    where the trace holds no device plane that ran anything, no
    ``bench.send_columns`` or no engine span."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    ran = {p: ls for p, ls in events["devices"].items()
           if ls.get(OPS_LINE) or ls.get(MODULES_LINE)}
    if not sends or not ran or not events.get("spans"):
        return None
    lo, hi = sends[0][0], sends[-1][1]
    pieces = segments(events["spans"])
    idle = dict.fromkeys(LAYERS, 0.0)
    for lines in ran.values():
        ops = lines.get(OPS_LINE) or lines[MODULES_LINE]
        busy = _clip(_union([s, s + d] for _, s, d in ops), lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        k = 0
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0.0
            while k < len(pieces) and pieces[k][1] <= a:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < b:
                part = min(b, pieces[j][1]) - max(a, pieces[j][0])
                idle[pieces[j][2]] += part
                covered += part
                j += 1
            idle["other"] += (b - a) - covered
    scoped = events.get("scoped") or {}
    scope_s = dict.fromkeys(("state", "select", "meta"), 0.0)
    for ops in scoped.values():
        for scope, s, d in ops:
            scope_s[scope] += max(0.0, min(s + d, hi) - max(s, lo))
    return {
        "idle_s": {k: v / 1e9 / len(ran) for k, v in idle.items()},
        # None: no operation of the trace names a scope
        "scope_s": {k: v / 1e9 / len(scoped) for k, v in scope_s.items()}
        if scoped else None,
        "window_s": (hi - lo) / 1e9,
        "sends": sum(1 for s in sends if s[1] <= hi),
    }


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return attribute(load(path))


def of_run() -> dict | None:
    """The attribution of the trace this process's run wrote (each run
    writes a directory of its own: read once, whichever reader is
    first)."""
    path = tracereduce.find_xplane(TRACE_DIR)
    return _of_file(path) if path else None


def scoped_ms(scope: str) -> float | None:
    """Device milliseconds a batch in operations traced in
    ``siddhi.<scope>``."""
    got = of_run()
    if not got or not got["sends"] or not got["scope_s"]:
        return None
    return got["scope_s"][scope] / got["sends"] * 1e3


def exposed_ms(layer: str) -> float | None:
    """Device-idle milliseconds a batch under ``layer``'s spans."""
    got = of_run()
    if not got or not got["sends"]:
        return None
    return got["idle_s"][layer] / got["sends"] * 1e3
