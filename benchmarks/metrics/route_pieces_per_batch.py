"""Layer "junction + dispatch". Pieces a batch went to the routed step
in: journey ``route_pieces``, mean over the window's journeys. 1.0 means
no batch was split; above it, some source-destination pair exceeded its
quota (``rows_per_shard`` / chips) and the host halved the batch until
every piece fitted, each piece a step of its own. Nothing where no query
is routed. Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "route_pieces")
