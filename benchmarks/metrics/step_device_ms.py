"""Layer "query step (kernels)". Device milliseconds one batch costs: the
union of the device-operation intervals inside the traced window, over
the batches sent in it (profiler trace; benchmarks/tracereduce.py). All
device time counts, whatever program it ran in. Moves ``events_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["sends"]:
        return None
    return t["busy_s"] / t["sends"] * 1e3
