"""Layer "junction + dispatch". Host milliseconds a batch spends inside
the call of its jitted step and nowhere else (``siddhi.launch``: the
flatten of the arguments, the transfer of the batch's numpy columns, the
enqueue; summed over the pieces of a split batch): journey ``launch_ms``,
service mean. It is part of ``dispatch_ms_per_batch``. Nothing where the
program has no such span (the parent of PR 35). Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "launch_ms")
