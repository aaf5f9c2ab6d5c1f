"""Layer "query step (kernels)". Share of the HBM roofline of the device
join engine's keyed probe: the least time the chip could take for one
batch's probe work (``probe_bytes_per_batch`` of the ``join`` family at
the cell's sizes: the probed window's keys and the trigger rows' keys
read once, every matched pair's price read and its row written, mean over
a round of four tick batches and one tweet batch; over the peak HBM
bandwidth of benchmarks/peaks.py) over the device time the probe took
(``step_join_probe_ms``). Bound: bytes. Listed for the cell ``CELL``
alone, whose configuration and traffic give the sizes. Nothing where the
trace has no such probe. Moves ``events_per_s``."""

from benchmarks import manifest
from benchmarks.metrics import step_join_probe_ms

CELL = "join_time10s_len100.hot20_tweets1k"


def read(ctx):
    ms = step_join_probe_ms.read(ctx)
    if not ms or not ctx["peaks"]:
        return None
    cell = manifest.Cell(CELL)
    sizes, _traffic = cell.sized(False)
    least = cell.family.probe_bytes_per_batch(cell.config, sizes)
    least_s = least / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
