"""Layer "completion + emit". Device-idle milliseconds a batch under
``siddhi.meta_pull``: the packed meta's round trip once the step has
finished (while the step runs the device is busy, so only the tail of
the pull is exposed: ROADMAP S3). From the profiler trace
(benchmarks/metrics/_spans.py). Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.exposed_ms("meta_pull")
