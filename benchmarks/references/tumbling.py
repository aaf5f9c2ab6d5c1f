"""Plain reference, comparison and least bytes of the family ``@app:playback
from S#window.timeBatch(T) select key, count() as n, min(x) as lo, max(x)
as hi group by key insert into O``: a tumbling window that closes on event
time and answers once a window, one row a group.

Imports nothing of ``siddhi_tpu``. The semantics, each from upstream's
``TimeBatchWindowProcessor.java``, ``Scheduler.java`` and
``QuerySelector.processInBatchGroupBy``, as ``playbackTest1`` runs them
(events at t, t + 500, t + 1000, t + 2000 under ``timeBatch(1 sec)``: 3 in,
2 removed), none read off the program:

1. **Boundaries from the first event's time.** The first event that
   reaches the window sets ``next_emit`` to its timestamp + T; every flush
   moves it on by exactly T, whether the closing window held events or not.
2. **The event that crosses the boundary does not join the closing
   window.** Under playback ``InputHandler.send`` sets the clock to the
   event's timestamp BEFORE it publishes the event, and the scheduler
   fires every timer due by then first, each at its own scheduled time.
   So an event with ``ts >= next_emit`` finds its window already closed by
   the timer and opens the next one. (Without playback a chunk that
   arrives after the boundary and before the timer joins the flushing
   batch; this family is the playback one.)
3. **A flush's rows belong to the send that crossed the boundary.** They
   are delivered inside that send, before its own events are processed.
   An event several windows ahead fires the timers one after another; the
   later ones close windows that hold nothing and emit nothing.
4. **One row a group that had an event in the window**, with the
   aggregates over that window's events alone (the flush's RESET clears
   them) and the timestamp of the group's last event. A window that is
   still open when the run ends is not answered.
5. **Order inside a flush**: the engine documents it (``ops/tumbling_agg.py``,
   ``core/plan/selector_plan.py``: of a batch chunk the selector keeps each
   group's LAST row, where it stands) and the configuration states it under
   ``guarantees``: groups in the order of their last events in the window.
   ``loop_reference`` and ``reference`` emit in that order; ``compare``
   matches a flush's rows by key (a key comes at most once a flush) and
   counts the rows that stand elsewhere separately.

``loop_reference`` is these rules event at a time (kept for the tests);
``reference`` does a whole run of one-timestamp batches window by window.
"""

from __future__ import annotations

import numpy as np


def loop_reference(send, key, x, ts, window_ms):
    """Rows (send that delivered the row, key, n, lo, hi, timestamp) in
    emission order. ``send[i]`` names the send that brought event i."""
    rows = []
    window = {}            # key -> [n, lo, hi, ts of last, position of last]
    next_emit = None
    for i, (s, k, v, t) in enumerate(zip(send.tolist(), key.tolist(),
                                         x.tolist(), ts.tolist())):
        while next_emit is not None and t >= next_emit:     # rules 2, 3
            for k2, (n, lo, hi, t2, _p) in sorted(
                    window.items(), key=lambda kv: kv[1][4]):
                rows.append((s, k2, n, lo, hi, t2))
            window = {}
            next_emit += window_ms
        if next_emit is None:
            next_emit = t + window_ms                       # rule 1
        g = window.get(k)
        window[k] = ([1, v, v, t, i] if g is None else
                     [g[0] + 1, min(g[1], v), max(g[2], v), t, i])
    cols = list(zip(*rows)) if rows else [[]] * 6
    return (np.asarray(cols[0], np.int64), np.asarray(cols[1], np.int64),
            np.asarray(cols[2], np.int64), np.asarray(cols[3], np.float64),
            np.asarray(cols[4], np.float64), np.asarray(cols[5], np.int64))


def _in_precision(x, dtype):
    """The values as an implementation carrying them in ``dtype`` would
    hold them: rounded once (a min or max then selects one of them)."""
    if dtype in (None, "float32", "float64"):
        return x.astype(np.float64)
    import ml_dtypes  # ships with jax; numpy has no bfloat16 of its own

    dt = np.dtype(getattr(ml_dtypes, dtype, None) or dtype)
    return x.astype(dt).astype(np.float64)


def _flush(keys, x, ts, n_keys):
    """One closed window's rows: (key, n, lo, hi, timestamp), groups in
    the order of their last events."""
    n = np.bincount(keys, minlength=n_keys)
    last = np.full(n_keys, -1, np.int64)
    last[keys] = np.arange(len(keys))       # a repeated index keeps the last
    order = np.argsort(keys, kind="stable")
    by_key, x = keys[order], x[order]
    starts = np.flatnonzero(np.r_[True, by_key[1:] != by_key[:-1]])
    seen = by_key[starts]                   # ascending, each once
    lo = np.minimum.reduceat(x, starts)
    hi = np.maximum.reduceat(x, starts)
    by_last = np.argsort(last[seen], kind="stable")
    seen = seen[by_last]
    return seen, n[seen], lo[by_last], hi[by_last], ts[last[seen]]


def reference(config, sizes, feed, n_batches, sample=None, dtype=None):
    """Every row of every window that batches [0, n_batches) close, in
    emission order, and per batch the rows its send delivers (0 for a
    batch that closes no window). ``dtype`` bfloat16 gives the control:
    the values carried one precision below the stream's float32."""
    window_ms, n_keys = sizes["window_ms"], sizes["keys"]
    xcol = config["aggregates"]["min"]
    rows_per_batch = np.zeros(n_batches, np.int64)
    out = {k: [] for k in ("key", "n", "lo", "hi", "ts", "flush")}
    held, next_emit, flushes = [], None, 0
    for i in range(n_batches):
        ts = feed.timestamps(i)
        if ts.min() != ts.max():
            raise ValueError("this reference wants one timestamp a batch")
        t = int(ts[0])
        while next_emit is not None and t >= next_emit:
            if held:
                keys = np.concatenate([feed.batch(j).keys for j in held])
                x = _in_precision(np.concatenate(
                    [feed.batch(j).cols[xcol] for j in held]), dtype)
                tss = np.concatenate([feed.timestamps(j) for j in held])
                key, n, lo, hi, at = _flush(keys, x, tss, n_keys)
                for name, col in zip(("key", "n", "lo", "hi", "ts"),
                                     (key, n, lo, hi, at)):
                    out[name].append(col)
                out["flush"].append(np.full(len(key), flushes, np.int64))
                rows_per_batch[i] += len(key)
                flushes += 1
                held = []
            next_emit += window_ms
        if next_emit is None:
            next_emit = t + window_ms
        held.append(i)
    want = {k: (np.concatenate(v) if v else np.empty(0, np.int64))
            for k, v in out.items()}
    want["rows_per_batch"] = rows_per_batch
    want["facts"] = {
        "flushes": flushes,
        "groups_per_flush": (len(want["key"]) / flushes if flushes else 0.0),
        "batches_in_open_window": len(held)}
    return want


def compare(config, want, got):
    """The numbers compared, each with its limit. Row for row over the
    whole run for the order; a flush's rows matched by key (both sides
    sorted by key inside each of the reference's flushes) for the key
    sets, the counts and the values.

    ``minmax_max_abs_err``: min and max SELECT one of the window's float32
    values, so the program's reading is exactly 0 in whatever order it
    folds; the limit, 1e-6, is below one float32 ulp of any value from 16
    up (7.6e-6 at 100), and the control (the values carried in bfloat16,
    spaced 0.5 apart from 64 up) reads 0.19-0.25: over it."""
    n = min(len(want["key"]), len(got["key"]))
    flush = want["flush"][:n]
    w = np.lexsort((want["key"][:n], flush))
    g = np.lexsort((got["key"][:n], flush))
    err = max(
        float(np.abs(want[c][:n][w] - got[c][:n][g].astype(np.float64))
              .max(initial=0.0)) for c in ("lo", "hi"))
    return [
        ("rows_missing", abs(len(want["key"]) - len(got["key"])), 0),
        ("key_mismatch_rows",
         int(np.count_nonzero(want["key"][:n][w] != got["key"][:n][g])), 0),
        ("order_mismatch_rows",
         int(np.count_nonzero(want["key"][:n] != got["key"][:n])), 0),
        ("sum_mismatch_rows",
         int(np.count_nonzero(want["n"][:n][w] != got["n"][:n][g])), 0),
        ("minmax_max_abs_err", err, config["limits"]["minmax_max_abs_err"]),
    ]


def bytes_per_batch(config, sizes, rows):
    """The least HBM traffic one batch of ``rows`` events needs, whatever
    implements the step.
    in:    per row key id 4 (a dictionary id), price (float32) 4,
           timestamp 8; volume is never read                    = 16 B
    state: per DISTINCT key in the batch its count (8), min (4) and max
           (4) read and written: 32 B; at most min(rows, keys) keys
    out:   nothing for a batch that closes no window. A flush writes per
           key id 4, n 8, lo 4, hi 4, timestamp 8 and resets the three
           accumulators: keys * 44 B once a window. The function is not
           told how many batches share a window, so the flush is left
           out: a floor that is too low by at most keys * 44 B a batch
           (8% at 10,000 keys and four 65,536-row batches a window),
           never too high.
    """
    return rows * 16 + min(rows, sizes["keys"]) * 32
