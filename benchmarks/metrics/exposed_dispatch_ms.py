"""Layer "junction + dispatch". Device-idle milliseconds a batch under
the self time of ``siddhi.junction.dispatch`` and ``siddhi.query.step``
(key computation, capacity checks, the enqueue of the jitted step): the
part of ``dispatch_ms_per_batch`` that the device waited for. From the
profiler trace (benchmarks/metrics/_spans.py). Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.exposed_ms("dispatch")
