"""Layer "query step (kernels)". Device milliseconds a batch in the
operations traced in ``siddhi.route``, the routed step's ingress: each
row's owner chip from its key, the rows bucketed by destination, the
exchange (``all_to_all``, or the ``pallas_ring`` kernel) and the rewrite
of key ids to the owner's local ones. Mean over the device planes; from
the ``tf_op`` of each ``XLA Ops`` event's metadata
(benchmarks/metrics/_route.py). Nothing on a trace of an unrouted
program. Moves ``events_per_s``."""

from benchmarks.metrics import _route


def read(ctx):
    return _route.scoped_ms("route")
