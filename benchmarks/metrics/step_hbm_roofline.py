"""Layer "query step (kernels)". Share of the HBM roofline: the least
time the chip could take for one batch (the least bytes the query needs,
``bytes_per_batch`` of the configuration's family, over the peak HBM
bandwidth of benchmarks/peaks.py, divided over the chips used) over the
device time one batch took (``step_device_ms``). Bound: bytes; these
queries do a few operations a byte. It divides by ALL device time a
batch, not by one named fusion, so it reads the same work whatever
implements the step later. Moves ``events_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["sends"] or not t["busy_s"] or not ctx["peaks"]:
        return None
    least_s = (ctx["bytes_per_batch"] / ctx["chips"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["busy_s"] / t["sends"])
