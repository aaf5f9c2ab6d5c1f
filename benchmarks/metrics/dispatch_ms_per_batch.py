"""Layer "junction + dispatch". Host milliseconds a batch spends inside
``process_batch`` up to the return of the jitted step's dispatch (key
computation, capacity checks, routing prep, enqueue of the step): journey
stage ``dispatch``, service mean. Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "dispatch_ms")
