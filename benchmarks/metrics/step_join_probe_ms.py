"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.join_probe``: the device join engine's keyed
probe (the probed ring's key-ordered view, each trigger row's range in
it, the expansion into matched pairs and the rest of the ``on``
condition), inside ``siddhi.state`` (so ``step_state_ms`` holds it). The
scope is read from the ``tf_op`` of each ``XLA Ops`` event's metadata as
``step_nfa_rounds_ms`` reads its own; the time is the UNION of those
events inside the window, per device plane, mean over the planes, over
the sends. Nothing on a trace of a program without the scope (no keyed
join; the parent of the PR that brought it). Moves ``events_per_s``."""

from __future__ import annotations

import functools
import re

from benchmarks import tracereduce
from benchmarks.metrics import _route, _spans, step_nfa_rounds_ms
from benchmarks.tracereduce import SEND

SCOPE = re.compile(r"siddhi\.(join_probe)\b")


def load(path: str) -> dict:
    """``host``: the ``bench.send_columns`` events; ``rounds``: {device
    plane: [[start_ns, duration_ns], ...]} of the scope's operations, the
    layout ``step_nfa_rounds_ms.attribute`` reads."""
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


@functools.lru_cache(maxsize=2)
def _of_file(path: str) -> dict | None:
    return step_nfa_rounds_ms.attribute(load(path))


def read(ctx):
    path = tracereduce.find_xplane(_spans.TRACE_DIR)
    got = _of_file(path) if path else None
    if not got:
        return None
    return got["scope_s"] / got["sends"] * 1e3
