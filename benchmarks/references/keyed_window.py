"""Plain reference, comparison and least bytes of the family
``partition with (key of S) begin from S#window.length(W) select key,
avg(x), sum(y) insert into O end``: one ring of the last W events PER KEY.

Imports nothing of ``siddhi_tpu``. Event at a time the semantics are
``loop_reference`` below (kept for the tests, which hold ``reference`` to
it); ``reference`` computes the same answers for a whole history at once:
sort the events by key (stably, so arrival order survives inside a key)
and take, for the j-th event of a key, the sum over that key's events
j-W+1 .. j.
"""

from __future__ import annotations

import collections

import numpy as np

from benchmarks.references import _sliding


def loop_reference(key, price, volume, window):
    """One deque per key; one output row per arriving event."""
    rings = collections.defaultdict(collections.deque)
    out_avg = np.empty(len(key), np.float64)
    out_sum = np.empty(len(key), np.int64)
    for i, (k, p, v) in enumerate(zip(key.tolist(), price.tolist(),
                                      volume.tolist())):
        ring = rings[k]
        if len(ring) == window:
            ring.popleft()
        ring.append((p, v))
        out_avg[i] = sum(x for x, _ in ring) / len(ring)
        out_sum[i] = sum(y for _, y in ring)
    return out_avg, out_sum


def _ranges(key, window):
    """Sorted order and, for every sorted position, the first position of
    its window: the same key's events, at most ``window`` of them."""
    order = _sliding.stable_order(key)
    first = _sliding.run_starts(key[order])
    np.maximum(first, np.arange(1 - window, len(key) + 1 - window),
               out=first)
    return order, first


def reference(config, sizes, feed, n_batches, sample=None, dtype="float64"):
    """The output columns for every event of batches [0, n_batches), in
    arrival order. A ring may reach back to the first batch, so the whole
    history is computed whatever ``sample`` says. ``dtype`` other than
    float64 gives the control (see _sliding.in_precision)."""
    hist = feed.history(0, n_batches)
    avg_col, sum_col = config["aggregates"]["avg"], config["aggregates"]["sum"]
    order, first = _ranges(hist["key"], sizes["window"])
    price = hist["cols"][avg_col][order]
    if dtype != "float64":
        price = _sliding.in_precision(price.astype(np.float64),
                                      np.ones(len(price)), dtype)
    total = _sliding.tail_sums(price, first)
    count = (np.arange(1, len(first) + 1) - first).astype(np.float64)
    avg = np.empty(len(order), np.float64)
    avg[order] = _sliding.in_precision(total, count, dtype)
    out_sum = np.empty(len(order), np.int64)
    out_sum[order] = _sliding.tail_sums(hist["cols"][sum_col][order], first)
    wrapped = int(np.count_nonzero(
        np.bincount(hist["key"]) >= sizes["window"]))
    return {"key": hist["key"], "rows": None, "avg": avg, "sum": out_sum,
            "rows_per_batch": np.full(n_batches, feed.rows, np.int64),
            "facts": {"rings_wrapped": wrapped}}


def compare(config, want, got):
    """The numbers compared, each with its limit (PERF.md section 2 gives
    the readings each limit was set from). ``want["rows"]`` names the
    delivered rows the aggregates were computed for (None: all)."""
    n = min(len(want["key"]), len(got["key"]))
    rows = want["rows"]
    if rows is None:
        rows = slice(0, n)
        w_avg, w_sum = want["avg"][:n], want["sum"][:n]
    else:
        keep = rows < n
        rows, w_avg, w_sum = rows[keep], want["avg"][keep], want["sum"][keep]
    return [
        ("rows_missing", abs(len(want["key"]) - len(got["key"])), 0),
        ("key_mismatch_rows",
         int(np.count_nonzero(want["key"][:n] != got["key"][:n])), 0),
        ("sum_mismatch_rows",
         int(np.count_nonzero(w_sum != got["sum"][rows])), 0),
        ("avg_max_abs_err",
         float(np.abs(w_avg - got["avg"][rows]).max(initial=0.0)),
         config["limits"]["avg_max_abs_err"]),
    ]


def bytes_per_batch(config, sizes, rows):
    """The least HBM traffic one batch of ``rows`` events needs, whatever
    implements the step.
    in:    per row the key id (int64, 8), price (float32, 4), volume
           (int64, 8), timestamp (int64, 8)                    = 28 B
    state: per row one ring slot read (the evicted price and volume, to
           take them out of the aggregates) and the same slot written
           (the new ones): (4 + 8) * 2                         = 24 B
           per DISTINCT key in the batch its running aggregates and ring
           cursor read and written: (sum 8 + vol 8 + count 4 + cursor 4)
           * 2 = 48 B; at most min(rows, keys) keys
    out:   per row key id 8, avg (double) 8, sum (long) 8       = 24 B
    """
    touched = min(rows, sizes["keys"])
    return rows * (28 + 24 + 24) + touched * 48
