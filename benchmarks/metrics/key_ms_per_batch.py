"""Layer "junction + dispatch". Host milliseconds a batch spends in the
partition-key and group-key computation and the key-capacity check
(``siddhi.key``, inside ``siddhi.query.step``): journey ``key_ms``,
service mean. It is part of ``dispatch_ms_per_batch``. Nothing where the
program has no such span (the parent of PR 35). Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "key_ms")
