"""Layer "junction + dispatch". Bytes a batch takes to the device: the
``nbytes`` of the jitted step's argument leaves that are numpy (the
batch's columns with the key columns and the masks, the clock; a leaf
already on the device crosses nothing), the ``h2d_bytes`` of the
``siddhi.launch`` span: journey ``h2d_bytes``, mean. Nothing where the
program has no such span (the parent of PR 35). Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "h2d_bytes")
