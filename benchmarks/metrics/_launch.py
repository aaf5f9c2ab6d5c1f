"""Shared by the two gap readers (``launch_to_start_ms``,
``done_to_meta_ms``): a batch's dispatch seen from both ends, the
engine's ``siddhi.launch`` and ``siddhi.meta_pull`` spans on ``/host:CPU``
and the device's own ``XLA Modules`` events, on the trace's one clock.

``siddhi.launch`` (``core/event.py`` ``launch_step``) is exactly the call
of a jitted step: the batch's numpy columns go up inside it. ``XLA
Modules`` has one event per executed program on every
``/device:TPU:<n>``; the engine's step programs are named
``jit_siddhi_<family>`` (``observability/instruments.py`` ``named_step``),
which a growth's leaf programs and a snapshot's are not. So, for each
launch that OPENS inside the window (first ``bench.send_columns`` start
to the last one's end, as ``tracereduce.reduce`` takes it):

- its module, on each plane, is the first ``jit_siddhi_*`` event that
  begins at or after the span's open and before the next launch's open
  (paired by time, not by ``batch``: a tumbling window's TIMER step and
  the data step of the send that fired it share an id). A launch without
  one on every plane inside the window is left out.
- launch gap: from the span's open to the module's start, mean of the
  planes: what the device still waits once the host has the batch ready
  (flatten, transfer, enqueue, the runtime's launch).
- completion gap: from the module's END (the last plane's) to the close
  of the first ``siddhi.meta_pull`` span that closes after it: the meta's
  way back and the thread's wake-up, with no device work in it.

A trace of a program without the span (the parent of PR 35) gives
``None``: nothing to read, nothing returned. The file is read once a
process with ``jax.profiler.ProfileData``, one pass, beside the passes
of ``tracereduce``, ``_spans``, ``_route`` and ``_flush``.
"""

from __future__ import annotations

import bisect
import functools

from benchmarks import tracereduce
from benchmarks.metrics import _spans
from benchmarks.tracereduce import DEVICE_PLANE, MODULES_LINE, SEND

LAUNCH, KEY, META_PULL = "siddhi.launch", "siddhi.key", "siddhi.meta_pull"
STEP_MODULE = "jit_siddhi_"


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events; ``spans``: the three
    engine spans above as [name, start_ns, duration_ns]; ``modules``: the
    step programs' ``XLA Modules`` events by device plane. Plain lists: a
    cut of a real trace is kept beside the test."""
    from jax.profiler import ProfileData

    host, spans, modules = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events
                        if e.name.startswith(STEP_MODULE)]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == SEND:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif e.name in (LAUNCH, KEY, META_PULL):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"host": host, "spans": spans, "modules": modules}


def pairs(events: dict) -> list:
    """[launch open, module start (mean of the planes), module end (the
    last plane's), meta pull's close or None] of every launch of the
    window that has its module on every plane inside the window."""
    sends = sorted([s, s + d] for n, s, d in events["host"] if n == SEND)
    planes = [sorted([s, s + d] for _n, s, d in ms)
              for ms in (events.get("modules") or {}).values() if ms]
    opens = sorted(s for n, s, _d in events.get("spans", ()) if n == LAUNCH)
    if not sends or not planes or not opens:
        return []
    lo, hi = sends[0][0], sends[-1][1]
    closes = sorted(s + d for n, s, d in events["spans"] if n == META_PULL)
    out = []
    for i, t in enumerate(opens):
        if not lo <= t < hi:
            continue
        before = min(opens[i + 1], hi) if i + 1 < len(opens) else hi
        mine = []
        for ms in planes:
            k = bisect.bisect_left(ms, [t, t])
            if k < len(ms) and ms[k][0] < before and ms[k][1] <= hi:
                mine.append(ms[k])
        if len(mine) < len(planes):
            continue
        end = max(m[1] for m in mine)
        k = bisect.bisect_left(closes, end)
        out.append([t, sum(m[0] for m in mine) / len(mine), end,
                    closes[k] if k < len(closes) else None])
    return out


def attribute(events: dict) -> dict | None:
    """Mean seconds of the two gaps over the window's launches; None
    where the trace has no send, no step program or no launch span."""
    got = pairs(events)
    if not got:
        return None
    met = [close - end for _t, _start, end, close in got
           if close is not None]
    return {"launch_to_start_s":
            sum(start - t for t, start, _e, _c in got) / len(got) / 1e9,
            "done_to_meta_s": sum(met) / len(met) / 1e9 if met else None,
            "launches": len(got)}


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return attribute(load(path))


def gap_ms(which: str) -> float | None:
    """``launch_to_start`` or ``done_to_meta``, milliseconds a launch, of
    the trace this process's run wrote."""
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    got = _of_file(path) if path else None
    if not got or got[which + "_s"] is None:
        return None
    return got[which + "_s"] * 1e3
