"""Layer "device". Share of the traced window in which no operation ran
on the device: 1 - busy union / window, from the profiler trace, averaged
over the chips used. Moves ``events_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
