"""Plain reference, comparison and least bytes of the family ``from
S#window.length(W) select key, avg(x), sum(y) group by key insert into
O``: ONE ring of the last W events of the whole stream; ``group by`` only
buckets the aggregation over it.

Imports nothing of ``siddhi_tpu``. ``loop_reference`` is the semantics
event at a time (kept for the tests); ``reference`` computes the same
answers for a run of events at once: sort by key, stably, and take for
the event with stream position g the sum over the same key's events with
position above g - W.

The answer for an event depends on the W events before it and on nothing
older, so a sampled batch is computed from itself and the W rows before.
"""

from __future__ import annotations

import collections

import numpy as np

from benchmarks.references import _sliding
from benchmarks.references.keyed_window import compare  # noqa: F401 — same columns, same numbers


def loop_reference(key, price, volume, window):
    ring = collections.deque()
    out_avg = np.empty(len(key), np.float64)
    out_sum = np.empty(len(key), np.int64)
    for i, row in enumerate(zip(key.tolist(), price.tolist(),
                                volume.tolist())):
        if len(ring) == window:
            ring.popleft()
        ring.append(row)
        mine = [(p, v) for k, p, v in ring if k == row[0]]
        out_avg[i] = sum(p for p, _ in mine) / len(mine)
        out_sum[i] = sum(v for _, v in mine)
    return out_avg, out_sum


def _answers(key, price, volume, window, dtype):
    """avg and sum for every row of one run of events, the first of which
    finds the ring empty."""
    n = len(key)
    order = _sliding.stable_order(key)
    k = key[order]
    # (key, stream position) ascends along the sorted run; the window of
    # a row begins at the first row of its key with position > g - W
    where = k * n + order
    first = np.searchsorted(where, where - window, side="right")
    np.maximum(first, _sliding.run_starts(k), out=first)
    p = price[order]
    if dtype != "float64":
        p = _sliding.in_precision(p.astype(np.float64), np.ones(n), dtype)
    total = _sliding.tail_sums(p, first)
    count = (np.arange(1, n + 1) - first).astype(np.float64)
    avg = np.empty(n, np.float64)
    avg[order] = _sliding.in_precision(total, count, dtype)
    out_sum = np.empty(n, np.int64)
    out_sum[order] = _sliding.tail_sums(volume[order], first)
    return avg, out_sum


def reference(config, sizes, feed, n_batches, sample=None, dtype="float64"):
    """Keys for every row of batches [0, n_batches); aggregates for the
    rows of the batches in ``sample`` (None: all of them)."""
    avg_col, sum_col = config["aggregates"]["avg"], config["aggregates"]["sum"]
    window, rows = sizes["window"], feed.rows
    lead = -(-window // rows)           # batches that cover W rows
    batches = range(n_batches) if sample is None else sample
    at, avgs, sums = [], [], []
    for b in batches:
        lo = max(0, b - lead)
        hist = feed.history(lo, b + 1)
        avg, out_sum = _answers(hist["key"], hist["cols"][avg_col],
                                hist["cols"][sum_col], window, dtype)
        # rows before the lead-in's own W rows are the only ones that
        # see a ring emptier than it was: drop all of the lead-in
        avgs.append(avg[(b - lo) * rows:])
        sums.append(out_sum[(b - lo) * rows:])
        at.append(np.arange(b * rows, (b + 1) * rows, dtype=np.int64))
    return {"key": np.concatenate([feed.batch(i).keys
                                   for i in range(n_batches)]),
            "rows": np.concatenate(at), "avg": np.concatenate(avgs),
            "sum": np.concatenate(sums),
            "rows_per_batch": np.full(n_batches, rows, np.int64),
            "facts": {"batches_compared": len(at)}}


def bytes_per_batch(config, sizes, rows):
    """The least HBM traffic one batch of ``rows`` events needs, whatever
    implements the step.
    in:    per row key id 8, price (float32) 4, volume (int64) 8,
           timestamp 8                                          = 28 B
    state: the ring holds W rows of (key 8, price 4, volume 8) = 20 B a
           row; a batch of rows >= W reads all W evicted rows and writes
           W new ones: 2 * min(rows, W) * 20
           per DISTINCT key in the batch its group's aggregates (sum 8,
           vol 8, count 4) read and written: 40 B; at most
           min(rows, keys) keys
    out:   per row key id 8, avg (double) 8, sum (long) 8       = 24 B
    """
    return (rows * (28 + 24) + 2 * min(rows, sizes["window"]) * 20
            + min(rows, sizes["keys"]) * 40)
