"""Layer "junction + dispatch". Host milliseconds a batch spends in
``mesh.prepare_routed_batches`` before the routed step is dispatched
(every column to numpy, the count of rows by source-destination pair
against the quota, the split where a pair exceeds it): journey
``route_prep_ms``, the ``siddhi.route.prepare`` span's duration, service
mean. It is part of ``dispatch_ms_per_batch``, and the device's idle
under it counts in ``exposed_dispatch_ms``. Nothing where no query is
routed. Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "route_prep_ms")
