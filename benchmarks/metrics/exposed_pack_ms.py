"""Layer "ingest + pack". Device-idle milliseconds a batch while the host
was inside ``siddhi.pack`` (``HostBatch.from_columns``: strings through
the dictionary, columns padded and masked): the part of
``pack_ms_per_batch`` that the device waited for. From the profiler
trace (benchmarks/metrics/_spans.py). Moves ``events_per_s``."""

from benchmarks.metrics import _spans


def read(ctx):
    return _spans.exposed_ms("pack")
