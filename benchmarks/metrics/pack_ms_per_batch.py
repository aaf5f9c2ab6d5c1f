"""Layer "ingest + pack". Host milliseconds a batch spends in
``HostBatch.from_columns`` (string symbols through the dictionary,
columns padded and masked): journey stage ``pack``, service mean. Moves
``events_per_s``."""

from benchmarks.metrics._journey import mean_ms


def read(ctx):
    return mean_ms(ctx, "pack_ms")
