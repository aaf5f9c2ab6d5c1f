"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.state``: the window, the NFA stage, the
join's insert and probe. From the ``tf_op`` of each ``XLA Ops`` event's
metadata in the profiler trace (benchmarks/metrics/_spans.py); a fusion
counts under its root's scope. Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.scoped_ms("state")
