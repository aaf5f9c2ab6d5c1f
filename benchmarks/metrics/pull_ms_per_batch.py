"""Layer "completion + emit". Host milliseconds the output columns take
to cross to the host (``LazyColumns``: one ``jax.device_get`` of every
pending column, inside the user's callback): journey counter
``pull_ms``, mean over the batches of the window that pulled anything
(an NFA head batch emits no row and pulls nothing). ``emit_ms_per_batch``
includes it. Moves ``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "pull_ms")
