"""Layer "completion + emit". Device-idle milliseconds a batch under
``siddhi.emit`` and ``siddhi.sink.publish``, less the ``siddhi.pull``
inside them: output decode, the delivery to the output stream and the
user's callback. From the profiler trace (benchmarks/metrics/_spans.py).
Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.exposed_ms("emit")
