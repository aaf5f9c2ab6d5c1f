"""Layer "query step (kernels)". Device milliseconds a flush in the
operations traced in ``siddhi.flush``: the sort of the groups a closing
tumbling window saw and the gathers of their keys and accumulators, once
a window. Mean of the traced flushes; from the ``tf_op`` of each ``XLA
Ops`` event's metadata (benchmarks/metrics/_flush.py). Nothing on a trace
of a program without the scope. Moves ``events_per_s``."""

from benchmarks.metrics import _flush


def read(ctx):
    return _flush.flush_ms()
