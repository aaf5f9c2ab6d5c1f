"""Layer "query step (kernels)". Rounds the generic NFA engine's loop ran
a batch (``ops/nfa.py`` ``_apply_stream_generic``: one round per
occurrence of the batch's most frequent partition key): journey
``nfa_rounds``, from the step's meta, mean over the window's batches. 0
where the two-step kernel ran. Nothing where the program has no such
field (the parent of the PR that brought it). Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "nfa_rounds")
