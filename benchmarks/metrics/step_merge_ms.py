"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.merge``, the routed step's egress: every
shard's emitted rows and order keys gathered on every chip, one sort of
the order keys, every column permuted by it (the ordered re-merge), and
the meta's reductions across shards. Mean over the device planes; from
the ``tf_op`` of each ``XLA Ops`` event's metadata
(benchmarks/metrics/_route.py). Nothing on a trace of an unrouted
program. Moves ``events_per_s``."""

from benchmarks.metrics import _route


def read(ctx):
    return _route.scoped_ms("merge")
