"""Plain reference, comparison and least bytes of the family ``sequence``:
upstream's ``SequencePartitionTestCase.testSequencePartitionQuery11``,
partitioned by symbol::

    from every e1=S[price>20],
         e2=S[((e2[last].price is null) and price>=e1.price)
              or ((not (e2[last].price is null)) and price>=e2[last].price)]+,
         e3=S[price<e2[last].price]
    select e1.price, e2[0].price, e2[1].price, e3.price, e3.symbol

Imports nothing of ``siddhi_tpu``. Upstream's SEQUENCE rules, per key, as
``SURVEY.md`` section 2.6 and the docstring of ``ops/nfa.py`` cite them
(``StreamPreStateProcessor``, ``CountPreStateProcessor``):

1. An event that fails to extend a pending partial match kills it.
2. ``every`` re-arms the start on every event, except one that a pending
   match absorbed into its collection (a count still collecting: the
   event belongs to that chain alone).
3. The count ``e2+`` accumulates into ONE partial match; ``e2[last]``
   reads its newest event and is null before the first, ``e2[0]`` and
   ``e2[1]`` its first two (``e2[1]`` null after a one-event run).
4. An event that both a count's absorb and the next step's advance would
   take, takes the advance (for this query the two conditions exclude
   each other: below or not below ``e2[last]``).

``loop_reference`` is these rules event at a time, with a list of pending
partial matches a key (kept for the tests, which hold it to the rows
upstream's test expects). ``reference`` gives the same answers a batch at
a time: under the rules above a key's state after an event is "no match
pending" or "one match pending, started at the first event above 20 since
the last event that fell", so a batch is a few sorts and scans over its
rows (``_one_batch``). Prices are compared in the precision given: the
configuration's float32, or the control's bfloat16 (ties then extend a run
where float32 would break it).
"""

from __future__ import annotations

import collections

import numpy as np

MIN_START = 20.0      # e1=S[price>20]
_NULL = -1            # price3's bits where e2[1] is null


def loop_reference(key, price):
    """Rows (index of the e3 event, key, e1, e2[0], e2[1] or None, e3) in
    emission order, and the most partial matches one key held at once."""
    pending = collections.defaultdict(list)    # key -> [[e1, [e2 ...]]]
    rows, most = [], 0
    for i, (k, x) in enumerate(zip(key.tolist(), price.tolist())):
        keep, absorbed = [], False
        for e1, e2 in pending[k]:
            last = e2[-1] if e2 else None
            if last is not None and x < last:             # e3: rules 4, 3
                rows.append((i, k, e1, e2[0], e2[1] if len(e2) > 1 else None,
                             x))
                continue
            if x >= (e1 if last is None else last):       # e2 absorbs
                keep.append([e1, e2 + [x]])
                absorbed = True
            # else: killed (rule 1)
        if x > MIN_START and not absorbed:                # rule 2
            keep.append([x, []])
        pending[k] = keep
        most = max(most, len(keep))
    return rows, most


def _in_precision(x, dtype):
    """Prices as an implementation carrying them in ``dtype`` holds them,
    as float64 (every float32 and bfloat16 value is one)."""
    if dtype in (None, "float32"):
        return x.astype(np.float32).astype(np.float64)
    import ml_dtypes  # ships with jax; numpy has no bfloat16 of its own

    return x.astype(np.dtype(getattr(ml_dtypes, dtype))).astype(np.float64)


class _Carry:
    """Each key's state between batches: no match pending, or one with its
    e1, e2[0], e2[1] (NaN: not yet) and the count of its e2 events (2 and
    up alike: nothing reads further); and the key's last price."""

    def __init__(self, n_keys):
        self.alive = np.zeros(n_keys, bool)
        self.count = np.zeros(n_keys, np.int64)
        self.first3 = np.full((n_keys, 3), np.nan)
        self.last = np.full(n_keys, -np.inf)    # never seen: below any price

    def replay(self, keys):
        """For the distinct ``keys``, rows that bring an empty key to the
        carried state under the rules: a pending match replays as its e1
        and up to two e2 events (and its last, where the run was longer),
        a key with none as its last price (at most 20: above it, an event
        always leaves a match pending)."""
        alive = self.alive[keys]
        n = np.where(alive, 1 + np.minimum(self.count[keys], 2)
                     + (self.count[keys] > 2), 1)
        k = np.repeat(keys, n)
        j = np.arange(len(k)) - np.repeat(np.cumsum(n) - n, n)
        x = np.where(np.repeat(alive, n),
                     np.where(j < 3, self.first3[k, np.minimum(j, 2)],
                              self.last[k]),
                     self.last[k])
        return k, x


def _one_batch(carry, keys, price):
    """The matches of one batch in arrival order: (row of the e3 event,
    e1, e2[0], e2[1] (NaN: null), e3), and the carry moved past it."""
    uk = np.unique(keys)
    rk, rx = carry.replay(uk)
    k = np.concatenate([rk, keys])
    x = np.concatenate([rx, price])
    row = np.concatenate([np.full(len(rk), -1), np.arange(len(keys))])
    order = np.argsort(k, kind="stable")        # replayed rows first
    k, x, row = k[order], x[order], row[order]
    n = len(k)
    idx = np.arange(n)
    head = np.ones(n, bool)
    head[1:] = k[1:] != k[:-1]
    up = np.zeros(n, bool)
    up[1:] = (x[1:] >= x[:-1]) & ~head[1:]
    # a run: from an event that fell (or a key's first) on; a match is
    # pending at p iff the run holds an event above 20 up to p, and it
    # started at the first of them (rules 1-3)
    start_run = np.maximum.accumulate(np.where(up, -1, idx))
    above = np.where(x > MIN_START, idx, n)
    first_above = np.minimum.accumulate(above[::-1])[::-1]
    s = first_above[start_run]
    alive = s <= idx
    # e3 at p: p fell below p-1, and p-1 had a match with an e2 event
    e3 = np.zeros(n, bool)
    e3[1:] = (~up[1:] & ~head[1:] & alive[:-1] & (s[:-1] < idx[:-1])
              & (row[1:] >= 0))
    p = np.flatnonzero(e3)
    s3 = s[p - 1]
    p3 = np.where(s3 + 2 <= p - 1, x[np.minimum(s3 + 2, n - 1)], np.nan)
    got = (row[p], x[s3], x[s3 + 1], p3, x[p])
    by = np.argsort(got[0], kind="stable")
    # the carry: each key's last row
    last = np.flatnonzero(np.append(head[1:], True))
    lk, ls = k[last], s[last]
    carry.alive[lk] = alive[last]
    carry.count[lk] = np.where(alive[last], last - ls, 0)
    for j in range(3):
        carry.first3[lk, j] = np.where(alive[last] & (ls + j <= last),
                                       x[np.minimum(ls + j, n - 1)], np.nan)
    carry.last[lk] = x[last]
    return tuple(c[by] for c in got)


def reference(config, sizes, feed, n_batches, sample=None, dtype=None):
    """Every match of batches [0, n_batches), in the arrival order of its
    e3 event, by the output roles; ``rows_per_batch``; ``rounds_per_batch``
    (the most rows one key has in the batch: the rounds a round-per-
    occurrence engine runs). ``dtype`` bfloat16 gives the control."""
    carry = _Carry(sizes["keys"])
    parts = []
    rows_per_batch = np.zeros(n_batches, np.int64)
    rounds = np.zeros(n_batches, np.int64)
    for i in range(n_batches):
        b = feed.batch(i)
        price = _in_precision(b.cols["price"], dtype)
        r, p1, p2, p3, p4 = _one_batch(carry, b.keys, price)
        parts.append((b.keys[r], p1, p2, p3, p4))
        rows_per_batch[i] = len(r)
        rounds[i] = np.bincount(b.keys).max()
    key, p1, p2, p3, p4 = (np.concatenate(c) for c in zip(*parts))
    return {"key": key, "price1": p1, "price2": p2,
            "price3": np.nan_to_num(p3), "price3_null": np.isnan(p3),
            "price4": p4, "rows_per_batch": rows_per_batch,
            "rounds_per_batch": rounds,
            "facts": {
                # a key holds one pending match at most (see _Carry); the
                # loop's list, held to this in the tests, counts it
                "pending_per_key_max": int(carry.alive.any()),
                "pending_at_end": int(carry.alive.sum()),
                "rounds_per_batch": [int(rounds.min()), float(rounds.mean()),
                                     int(rounds.max())]}}


def _rows(side):
    """One row a match as int64 words: the key, then each price's float32
    bits (price3's: -1 where it is null)."""
    bits = [np.asarray(side[c], np.float32).view(np.int32).astype(np.int64)
            for c in ("price1", "price2", "price3", "price4")]
    null = np.asarray(side["price3_null"], bool)
    bits[2] = np.where(null, _NULL, bits[2])
    return np.stack([np.asarray(side["key"], np.int64)] + bits, axis=1)


def compare(config, want, got):
    """Bit for bit. ``rows_missing``: rows of either side that the other
    lacks, a row being its key and its four prices (a match with one price
    wrong is not the match); ``order_mismatch_rows``: positions where the
    two sides' rows differ (the engine documents the e3 event's arrival
    order), and the rows one side has beyond the other."""
    w, g = _rows(want), _rows(got)
    both = np.concatenate([w, g])
    _u, inv = np.unique(both, axis=0, return_inverse=True)
    inv = inv.ravel()
    side = np.bincount(inv[:len(w)], minlength=len(_u)) - np.bincount(
        inv[len(w):], minlength=len(_u))
    n = min(len(w), len(g))
    lims = config["limits"]
    return [
        ("rows_missing", int(np.abs(side).sum()), lims["rows_missing"]),
        ("order_mismatch_rows",
         int((w[:n] != g[:n]).any(axis=1).sum()) + abs(len(w) - len(g)),
         lims["order_mismatch_rows"]),
    ]


# least bytes -------------------------------------------------------------

_IN_ROW = 24        # key id 4, price 4, volume 4, timestamp 8, symbol id 4
_SLOT = 21          # e1, e2[0], e2[1], e2[last] prices 16, count 4, step 1
_OUT_ROW = 21       # four prices 16, symbol id 4, price3's null flag 1
_MATCHES_PER_ROW = 0.25   # a walk falls after a rise one event in four


def keys_touched(n_keys, rows, hot_share=0.2, hot_traffic=0.8):
    """Distinct keys a batch of ``rows`` touches, expected under the
    cell's key mix (YCSB's hotspot at its defaults, the traffic file's)."""
    hot = max(1, int(n_keys * hot_share))
    cold = n_keys - hot
    return (hot * -np.expm1(-rows * hot_traffic / hot)
            + cold * -np.expm1(-rows * (1 - hot_traffic) / cold))


def round_bytes_per_batch(config, sizes, rows):
    """The least HBM traffic of the round loop's work for one batch,
    whatever implements it: the batch's columns read once (24 B a row),
    the slot state of the distinct keys it touches read and written once
    (``nfa_slots`` slots of 21 B a key), and the matches written (21 B,
    one a row in four). At 16,384 rows over 10,000 keys: 4,690 keys."""
    slots = config["engine"]["nfa_slots"]
    state = keys_touched(sizes["keys"], rows) * slots * _SLOT * 2
    return int(rows * _IN_ROW + state + rows * _MATCHES_PER_ROW * _OUT_ROW)


def bytes_per_batch(config, sizes, rows):
    """The least HBM traffic of a whole step: the round loop's, and the
    matches read once more and written once as the output columns."""
    return int(round_bytes_per_batch(config, sizes, rows)
               + 2 * rows * _MATCHES_PER_ROW * _OUT_ROW)
