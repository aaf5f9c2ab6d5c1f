"""Layer "query step (kernels)". Groups a closed window answers with:
journey ``flush_rows`` (the rows of the step that closed it), mean over
the window's flushes. About 9,990 of 10,000 under ``hot20_tick250`` says
every group that had an event came. Nothing where no journey stamps a
flush. Moves ``events_per_s``."""

from benchmarks.metrics import _flush


def read(ctx):
    return _flush.per_flush(ctx, "flush_rows")
