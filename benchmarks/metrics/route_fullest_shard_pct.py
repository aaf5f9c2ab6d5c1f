"""Layer "query step (kernels)". How full the exchange ran: the rows the
fullest shard received in a batch (the meta's ``rows_0..n-1`` lanes,
journey ``shard_rows_max``) over what a shard can receive (chips x the
per-pair quota, journey ``shard_capacity``), as a share, mean over the
window's journeys. An even spread of this cell's traffic reads chips x
(batch rows / chips) / ``rows_per_shard`` = 1 / ``route_slack``; key
skew reads above it, and at 100 the next skewed batch is split. Nothing
where no query is routed. Moves ``events_per_s``."""


def read(ctx):
    got = [100.0 * j["shard_rows_max"] / j["shard_capacity"]
           for j in ctx["journeys"]
           if j.get("shard_rows_max") is not None
           and j.get("shard_capacity")]
    return sum(got) / len(got) if got else None
