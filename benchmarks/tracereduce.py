"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy seconds, the traced window, device time per
program, and the idle gaps by what the host was doing.

What the trace of this system looks like on the v5e (looked at by hand,
PR 24; PERF.md section 3 has the listing): one plane ``/device:TPU:<n>``
per chip, with a line ``XLA Modules`` (one event per executed program,
named ``jit_<fn>(<fingerprint>)``) and a line ``XLA Ops`` (one event per
HLO operation inside it); ``/host:CPU`` has one line per host thread, and
``jax.profiler.TraceAnnotation`` spans of the benchmark (``bench.*``) are
events on the line of the thread that made them. All planes share one
clock, in nanoseconds.

- busy: the union of the ``XLA Ops`` intervals of a device plane (of
  ``XLA Modules`` where a plane has no ops line), clipped to the window,
  averaged over the device planes that ran anything.
- window: from the start of the first ``bench.send_columns`` span to the
  end of the last one in the trace.
- gaps: the complement of busy inside the window, each given to the span
  that covers most of it: ``bench.callback``, else ``bench.send_columns``
  (the engine's own host work), else between sends (the generator).

Reads the file with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SEND, CALLBACK = "bench.send_columns", "bench.callback"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _short(name: str) -> str:
    """XLA names an operation by its whole HLO line; keep its result name
    and the start of its shape: ``%fusion.56 = (f32[2,16384]{0,1:T(8,...``"""
    return name if len(name) <= 72 else name[:69] + "..."


def load(path: str) -> dict:
    """The events the reduction needs, as plain lists of
    [name, start_ns, duration_ns]: small enough to keep a cut of a real
    trace beside the test as JSON."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [_short(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
            devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name in (SEND, CALLBACK))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged [start, end] intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def _overlap(a, b, spans):
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in spans)


def reduce(events: dict) -> dict | None:
    """None where the trace holds no device plane that ran anything or no
    ``bench.send_columns`` span: there is nothing to read, and the
    harness then leaves the trace's metrics out."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    calls = sorted([s, s + d] for n, s, d in events["host"] if n == CALLBACK)
    ran = {p: ls for p, ls in events["devices"].items()
           if ls.get(OPS_LINE) or ls.get(MODULES_LINE)}
    if not sends or not ran:
        return None
    lo, hi = sends[0][0], sends[-1][1]
    busy_s, programs, ops, gaps = [], {}, {}, []
    for plane, lines in sorted(ran.items()):
        src = lines.get(OPS_LINE) or lines[MODULES_LINE]
        busy = _clip(_union([s, s + d] for _, s, d in src), lo, hi)
        busy_s.append(sum(b - a for a, b in busy) / 1e9)
        for name, s, d in lines.get(MODULES_LINE, []):
            if s + d > lo and s < hi:
                programs[name] = programs.get(name, 0.0) + d / 1e9
        for name, s, d in lines.get(OPS_LINE, []):
            if s + d > lo and s < hi:
                ops[name] = ops.get(name, 0.0) + d / 1e9
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_planes = len(ran)
    by_host = {}
    longest = []
    for a, b in gaps:
        in_call = _overlap(a, b, calls)
        in_send = _overlap(a, b, sends) - in_call
        parts = {"in_callback": in_call, "in_send_columns": in_send,
                 "between_sends": (b - a) - in_call - in_send}
        for k, v in parts.items():
            by_host[k] = by_host.get(k, 0.0) + v / 1e9 / n_planes
        longest.append((max(parts, key=parts.get), (b - a) / 1e9))
    # the ops of one program leave nanoseconds between them: not gaps
    longest = sorted((g for g in longest if g[1] >= 1e-6),
                     key=lambda g: -g[1])
    totals = sorted(([f"total.{k}", v] for k, v in by_host.items()),
                    key=lambda kv: -kv[1])
    def top(seconds_by_name, n):
        return [[k, v / n_planes] for k, v in sorted(
            seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]

    # programs are what XLA names them; the ops inside the largest
    # program say where in it the time goes
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n_planes,
        "sends": sum(1 for s in sends if s[1] <= hi),
        "programs": top(programs, 10),
        "device_ops": (top(programs, 4) + top(ops, 6))[:10],
        "idle_gaps": (totals + [[f"longest.{k}", v]
                                for k, v in longest[:10 - len(totals)]]),
        "device_planes": n_planes,
    }
