"""Layer "query step (kernels)". Iterations the generic NFA engine's loop
took a batch (``ops/nfa.py`` ``_apply_stream_generic``: each round runs as
tiles of at most W rows of distinct keys, so a round wider than a tile
takes several): journey ``nfa_tiles``, from the step's meta, mean over the
window's batches. 0 where the two-step kernel ran. Nothing where the
program has no such field (the parent of the PR that brought it). Moves
``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "nfa_tiles")
