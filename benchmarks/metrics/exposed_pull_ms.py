"""Layer "completion + emit". Device-idle milliseconds a batch under
``siddhi.pull``: the output columns crossing to the host
(``LazyColumns``), which a closed loop with one sender cannot overlap
with the next step. From the profiler trace
(benchmarks/metrics/_spans.py). Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.exposed_ms("pull")
