"""Layer "completion + emit". Milliseconds from the END of a step
program on the device (the last plane's) to the close of the first
``siddhi.meta_pull`` span that closes after it, mean of the traced
launches: the meta's way back to the host and the thread's wake-up, with
no device work in it. From the profiler trace
(benchmarks/metrics/_launch.py). Moves ``events_per_s``."""

from benchmarks.metrics import _launch


def read(ctx):
    return _launch.gap_ms("done_to_meta")
