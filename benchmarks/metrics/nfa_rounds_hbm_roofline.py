"""Layer "query step (kernels)". Share of the HBM roofline of the generic
NFA engine's round loop: the least time the chip could take for one
batch's round work (``round_bytes_per_batch`` of the ``sequence`` family
at the cell's sizes: the batch's columns read once, the slot state of
the keys it touches read and written once, the matches written; over the
peak HBM bandwidth of benchmarks/peaks.py) over the device time the loop
took (``step_nfa_rounds_ms``). Bound: bytes. Listed for the cell
``CELL`` alone, whose configuration and traffic give the sizes. Nothing
where the trace has no such loop. Moves ``events_per_s``."""

from benchmarks import manifest
from benchmarks.metrics import step_nfa_rounds_ms

CELL = "sequence_peak_10k.hot20_walk16k"


def read(ctx):
    ms = step_nfa_rounds_ms.read(ctx)
    if not ms or not ctx["peaks"]:
        return None
    cell = manifest.Cell(CELL)
    sizes, _traffic = cell.sized(False)
    least = cell.family.round_bytes_per_batch(cell.config, sizes,
                                              sizes["batch_rows"])
    least_s = least / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
