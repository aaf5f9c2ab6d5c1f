"""Layer "query step (kernels)". Device milliseconds a batch in the
whole-ring passes of a keyed ring write: the operations traced in
``siddhi.ring_pass`` (an int64 ring's split into words, the high plane's
pass, the re-join) plus the compiler's unscoped ``X64SplitLow`` /
``X64SplitHigh`` / ``X64Combine`` copies of a ring column. The part of
the step that is paid per ring SLOT (key capacity x window), not per
event (benchmarks/metrics/_ring_pass.py). Nothing on a trace of a program
without the scope. Moves ``events_per_s``."""

from benchmarks.metrics import _ring_pass


def read(ctx):
    return _ring_pass.ring_pass_ms(ctx)
