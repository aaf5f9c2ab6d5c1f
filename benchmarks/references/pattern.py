"""Plain reference, comparison and least bytes of the family ``partition
with (k of A, k of B) begin from every e1=A -> e2=B[e2.v > e1.v] within T
select e1.v as v1, e2.v as v2 insert into M end``.

Imports nothing of ``siddhi_tpu``. Per key there is a list of pending
A's; a B consumes every pending A of its key that is still inside the
bound and below it, and emits one row for each. ``loop_reference`` is
that, event at a time (kept for the tests); ``reference`` does one BATCH
at a time: it joins the pending A's with the batch's B rows on the key
and gives each A to the first B row above it. It needs what the traffic
of this family has: one timestamp per batch, batches in time order.
"""

from __future__ import annotations

import collections

import numpy as np

from benchmarks.references import _sliding

_NEVER = np.iinfo(np.int64).max


def loop_reference(stream, key, v, ts, within_ms):
    """Rows (v1, v2, index of the B event that produced the row), the
    matches of one B oldest A first."""
    pending = collections.defaultdict(list)
    v1, v2, by = [], [], []
    for i, (st, k, x, t) in enumerate(zip(stream.tolist(), key.tolist(),
                                          v.tolist(), ts.tolist())):
        if st == 0:
            pending[k].append((t, x))
            continue
        keep = []
        for t1, x1 in pending[k]:
            if t - t1 > within_ms:
                continue
            if x > x1:
                v1.append(x1)
                v2.append(x)
                by.append(i)
            else:
                keep.append((t1, x1))
        pending[k] = keep
    return (np.asarray(v1, np.float64), np.asarray(v2, np.float64),
            np.asarray(by, np.int64))


def _consume(pk, pv, kb, vb, n_keys):
    """For every pending A (key pk, value pv) the row of this B batch
    that consumes it: the first row of its key with a value above it, or
    _NEVER."""
    order = _sliding.stable_order(kb)
    per_key = np.bincount(kb, minlength=n_keys)
    lo = (np.cumsum(per_key) - per_key)[pk]      # first sorted row of key
    cnt = per_key[pk]
    has = np.flatnonzero(cnt)
    starts = np.cumsum(cnt[has]) - cnt[has]
    total = int(cnt.sum())
    a = np.repeat(has, cnt[has])                 # pending index per pair
    within = np.arange(total) - np.repeat(starts, cnt[has])
    j = order[np.repeat(lo[has], cnt[has]) + within]   # B row per pair
    j = np.where(vb[j] > pv[a], j, _NEVER)
    consumer = np.full(len(pk), _NEVER, np.int64)
    if total:
        consumer[has] = np.minimum.reduceat(j, starts)
    return consumer


def reference(config, sizes, feed, n_batches, sample=None, dtype="float64"):
    """Every match row of batches [0, n_batches), in the order of the B
    events that produced them (one B's matches oldest A first).
    ``dtype`` float32 gives the control: the doubles carried in float32."""
    within_ms = sizes["within_ms"]
    (vcol,) = [c for c in feed.batch(0).cols if c != feed.key_attr]
    pk = np.empty(0, np.int64)
    pv = np.empty(0, np.float64)
    pt = np.empty(0, np.int64)
    v1, v2, by = [], [], []
    rows_per_batch = np.zeros(n_batches, np.int64)
    for i in range(n_batches):
        b = feed.batch(i)
        ts = feed.timestamps(i)
        if ts.min() != ts.max():
            raise ValueError("this reference wants one timestamp a batch")
        t = int(ts[0])
        v = b.cols[vcol].astype(np.float64)
        if dtype != "float64":
            v = v.astype(dtype).astype(np.float64)
        if b.stream == 0:
            pk = np.concatenate([pk, b.keys])
            pv = np.concatenate([pv, v])
            pt = np.concatenate([pt, np.full(len(v), t, np.int64)])
            continue
        alive = t - pt <= within_ms
        pk, pv, pt = pk[alive], pv[alive], pt[alive]
        consumer = _consume(pk, pv, b.keys, v, sizes["keys"])
        hit = np.flatnonzero(consumer != _NEVER)
        hit = hit[np.argsort(consumer[hit], kind="stable")]
        v1.append(pv[hit])
        v2.append(v[consumer[hit]])
        by.append(i * feed.rows + consumer[hit])
        rows_per_batch[i] = len(hit)
        keep = consumer == _NEVER
        pk, pv, pt = pk[keep], pv[keep], pt[keep]
    return {"v1": np.concatenate(v1), "v2": np.concatenate(v2),
            "by": np.concatenate(by), "rows_per_batch": rows_per_batch,
            "facts": {"pending_at_end": len(pk)}}


def _max_rel(got, want):
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1e-300))
                 .max(initial=0.0))


def compare(config, want, got):
    """v2 row for row pins which B event produced every row, in arrival
    order (two random doubles differ by far more than the limit). One B's
    matches come in slot order from the engine and oldest first from the
    reference (PR 21), so v1 is compared as a set per B: both
    sides sorted by (B event, v1). A double that only passes through the
    chip may come back an ulp off (PR 21): the limit is relative."""
    limit = config["limits"]["v_max_rel_err"]
    n = min(len(want["v2"]), len(got["v2"]))
    by = want["by"][:n]
    w1, g1 = want["v1"][:n], got["v1"][:n]
    return [
        ("rows_missing", abs(len(want["v2"]) - len(got["v2"])), 0),
        ("v2_max_rel_err", _max_rel(got["v2"][:n], want["v2"][:n]), limit),
        ("v1_max_rel_err", _max_rel(g1[np.lexsort((g1, by))],
                                    w1[np.lexsort((w1, by))]), limit),
    ]


def bytes_per_batch(config, sizes, rows):
    """The least HBM traffic one batch of ``rows`` events needs, whatever
    implements the step; the mean of an A batch and a B batch.
    in:    per row key id 8, v (double) 8, timestamp 8          = 24 B
    A row: one free slot of its key written (v 8, time 8)       = 16 B
           and the key's slot occupancy word read and written   = 16 B
    B row: its key's occupancy read and written 16 B, and every pending
           slot of the key read to test it; the traffic keeps about one
           pending A per B row, (v 8, time 8)                   = 16 B
    out:   per match row v1 8, v2 8; about 0.7 match per B row = 11 B
    So an A row moves 24 + 32 = 56 B, a B row 24 + 32 + 11 = 67 B; the
    mean batch moves rows * 61.5 B.
    """
    return int(rows * (56 + 67) / 2)
