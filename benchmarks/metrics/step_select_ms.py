"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.select``: the selector (projection,
group-by aggregation, having, order and limit). 0 where the step's
selector was fused under a ``siddhi.state`` root or runs on the host.
From the ``tf_op`` of each ``XLA Ops`` event's metadata in the profiler
trace (benchmarks/metrics/_spans.py). Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.scoped_ms("select")
