"""Layer "junction + dispatch". Milliseconds from the open of a
``siddhi.launch`` span to the start of its step program on the device
(the first ``jit_siddhi_*`` event of ``XLA Modules`` after it, mean of
the device planes), mean of the traced launches: what the device still
waits once the host has the batch ready: the flatten, the transfer, the
enqueue, the runtime's launch. From the profiler trace
(benchmarks/metrics/_launch.py). Moves ``events_per_s``."""

from benchmarks.metrics import _launch


def read(ctx):
    return _launch.gap_ms("launch_to_start")
