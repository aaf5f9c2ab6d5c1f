"""Layer "junction + dispatch". TIMER steps a closed window cost: journey
``timer_steps`` summed over the window's journeys, over the flushes among
them. 1.0: every flush is a step of its own, fired by the scheduler when a
send advances the clock past the boundary, before that send's own step;
0.0 would mean the flush rides a data step. Nothing where no journey
stamps a flush. Moves ``events_per_s``."""

from benchmarks.metrics import _flush


def read(ctx):
    return _flush.per_flush(ctx, "timer_steps")
