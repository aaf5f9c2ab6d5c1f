"""Plain reference, comparison and least bytes of the family ``join``:
``BASELINE.json`` configs[4], upstream's ``JoinTestCase`` shape::

    from cseEventStream#window.time(T sec)
         join twitterStream#window.length(L)
      on cseEventStream.symbol == twitterStream.company
    select symbol, tweet, price

Imports nothing of ``siddhi_tpu``. Upstream's join rules
(``JoinProcessor``, ``JoinInputStreamParser``):

1. A chunk enters its own side's window first; then each of its rows, in
   order, probes the OTHER side's window, oldest row first
   (``FindableProcessor.find`` walks the window's queue).
2. A tick has left ``time(T sec)`` once it is T seconds old:
   ``ts + T*1000 <= now``, ``now`` being the arriving batch's timestamp,
   whichever stream it is on.
3. ``length(L)`` is global: the last L tweets, whatever their symbol.

``loop_reference`` is these rules an event at a time (for the tests).
``reference`` gives the same rows a batch at a time: the probed window
sorted by key (a stable sort keeps the arrival order inside a key), each
trigger row's range of its key found by a search, and the ranges
expanded. Prices are compared in the precision given: the configuration's
float32, or the control's bfloat16.
"""

from __future__ import annotations

import collections
import json
import os

import numpy as np


def _in_precision(x, dtype):
    """Prices as an implementation carrying them in ``dtype`` holds them,
    as float32 (every bfloat16 value is one)."""
    if dtype in (None, "float32"):
        return np.asarray(x, np.float32)
    import ml_dtypes  # ships with jax; numpy has no bfloat16 of its own

    return np.asarray(x).astype(np.dtype(getattr(ml_dtypes, dtype))) \
        .astype(np.float32)


def loop_reference(events, window_ms, length):
    """Rows (key, tweet, price) in emission order from ``events``: tuples
    (stream, ts, key, tweet or price), stream 0 the ticks, 1 the tweets,
    each row its own chunk."""
    ticks = collections.deque()
    tweets = collections.deque(maxlen=length)
    rows = []
    for stream, ts, key, val in events:
        while ticks and ticks[0][0] + window_ms <= ts:
            ticks.popleft()
        if stream == 0:
            ticks.append((ts, key, val))
            rows += [(key, t, val) for k, t in tweets if k == key]
        else:
            tweets.append((key, val))
            rows += [(key, val, p) for _, k, p in ticks if k == key]
    return rows


def _expand(win_keys, trig_keys):
    """For each trigger key in order, the positions of the window rows of
    that key, oldest first: (trigger index, window index) of every pair.
    Key indices are small: as int16 a stable sort is a radix sort."""
    if len(win_keys) and win_keys.max() < 2 ** 15:
        win_keys = win_keys.astype(np.int16)
    order = np.argsort(win_keys, kind="stable")
    sk = win_keys[order]
    lo = np.searchsorted(sk, trig_keys, side="left")
    cnt = np.searchsorted(sk, trig_keys, side="right") - lo
    trig = np.repeat(np.arange(len(trig_keys)), cnt)
    starts = np.cumsum(cnt) - cnt
    within = np.arange(len(trig)) - np.repeat(starts, cnt)
    return trig, order[np.repeat(lo, cnt) + within]


def reference(config, sizes, feed, n_batches, sample=None, dtype=None):
    """Every pair of batches [0, n_batches) in emission order, by the
    output roles (``key`` and ``tweet`` as indices into the feed's
    tables, ``price`` float32); ``rows_per_batch``. ``dtype`` bfloat16
    gives the control. Every batch is computed (``sample`` unused)."""
    window_ms = int(sizes["time_s"]) * 1000
    length = int(sizes["length"])
    tick_parts = collections.deque()       # [ts, key, price], oldest first
    tw_key = np.zeros(0, np.int64)
    tw_code = np.zeros(0, np.int64)
    keys, tweets, prices = [], [], []
    rows_per_batch = np.zeros(n_batches, np.int64)
    most_ticks = 0
    by_stream = {0: [], 1: []}
    for i in range(n_batches):
        b = feed.batch(i)
        now = int(feed.timestamps(i)[0])
        while tick_parts and tick_parts[0][0][-1] + window_ms <= now:
            tick_parts.popleft()
        if tick_parts and tick_parts[0][0][0] + window_ms <= now:
            ts0, k0, p0 = tick_parts[0]
            keep = ts0 + window_ms > now
            tick_parts[0] = [ts0[keep], k0[keep], p0[keep]]
        if b.stream == 0:
            price = _in_precision(b.cols["price"], dtype)
            tick_parts.append([feed.timestamps(i), b.keys, price])
            trig, hit = _expand(tw_key, b.keys)
            keys.append(b.keys[trig])
            tweets.append(tw_code[hit])
            prices.append(price[trig])
        else:
            code = b.codes["tweet"]
            tw_key = np.concatenate([tw_key, b.keys])[-length:]
            tw_code = np.concatenate([tw_code, code])[-length:]
            win_key = np.concatenate([p[1] for p in tick_parts]) \
                if tick_parts else np.zeros(0, np.int64)
            win_price = np.concatenate([p[2] for p in tick_parts]) \
                if tick_parts else np.zeros(0, np.float32)
            most_ticks = max(most_ticks, len(win_key))
            trig, hit = _expand(win_key, b.keys)
            keys.append(b.keys[trig])
            tweets.append(code[trig])
            prices.append(win_price[hit])
        rows_per_batch[i] = len(keys[-1])
        by_stream[b.stream].append(len(keys[-1]))
    return {"key": np.concatenate(keys), "tweet": np.concatenate(tweets),
            "price": np.concatenate(prices),
            "rows_per_batch": rows_per_batch,
            "facts": {
                "ticks_in_window_max": most_ticks,
                "pairs_per_batch": {
                    ("ticks", "tweets")[s]: float(np.mean(v)) if v else 0.0
                    for s, v in by_stream.items()}}}


def _packed(side):
    """One int64 a row: key and tweet index above, the price's float32
    bits below (an index -1, a string never sent, packs apart)."""
    key = np.asarray(side["key"], np.int64) + 1
    tweet = np.asarray(side["tweet"], np.int64) + 1
    bits = np.asarray(side["price"], np.float32).view(np.uint32) \
        .astype(np.int64)
    return ((key << 16 | tweet) << 32) | bits


def compare(config, want, got):
    """Bit for bit. ``rows_missing``: rows of either side that the other
    lacks, a row being its key, its tweet and its price's bits;
    ``order_mismatch_rows``: positions where the two sides' rows differ
    (the engine documents the trigger row's arrival order, then the
    probed rows'), and the rows one side has beyond the other."""
    w, g = _packed(want), _packed(got)
    n = min(len(w), len(g))
    order = int(np.count_nonzero(w[:n] != g[:n])) + abs(len(w) - len(g))
    missing = 0
    if order:           # equal row for row: the same rows, none missing
        uw, cw = np.unique(w, return_counts=True)
        ug, cg = np.unique(g, return_counts=True)
        both = np.union1d(uw, ug)
        count = np.zeros((2, len(both)), np.int64)
        count[0, np.searchsorted(both, uw)] = cw
        count[1, np.searchsorted(both, ug)] = cg
        missing = int(np.abs(count[0] - count[1]).sum())
    lims = config["limits"]
    return [("rows_missing", missing, lims["rows_missing"]),
            ("order_mismatch_rows", order, lims["order_mismatch_rows"])]


# least bytes -------------------------------------------------------------

_KEY = 4            # a symbol's dictionary id
_TICK = 23          # symbol 4, price 4, volume 4, timestamp 8, 3 null masks
_TWEET = 23         # user 4, tweet 4, company 4, timestamp 8, 3 null masks
_PAIR = 17          # price read 4; symbol 4, tweet 4, price 4, valid 1 out


def _traffic_of(config):
    """The traffic of the cell that runs this configuration."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(os.path.dirname(here), "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = next(w["traffic"] for w in bench["workloads"]
                if w["config"] == config["name"])
    with open(os.path.join(here, "traffic", name + ".json")) as f:
        return json.load(f)


def _round(config, sizes):
    """(tick rows, tweet rows, tick batches and tweet batches a round,
    expected pairs of a tick batch, of a tweet batch) under the traffic's
    key mix: a key's share p of both streams makes p * p of the pairs."""
    traffic = _traffic_of(config)
    ticks, tweets = traffic["rows_by_stream"]
    n_ticks, n_tweets = traffic["ratio"]
    keys = traffic["keys"]
    n = sizes["keys"]
    hot = max(1, int(n * keys["hot_share"]))
    p2 = keys["hot_traffic"] ** 2 / hot + (1 - keys["hot_traffic"]) ** 2 \
        / (n - hot)
    window = ticks * n_ticks * sizes["time_s"] * 1000 / traffic["round_ms"]
    return (ticks, tweets, n_ticks, n_tweets,
            ticks * sizes["length"] * p2, tweets * window * p2, window)


def probe_bytes_per_batch(config, sizes, rows=None):
    """The least HBM traffic of the probe for one batch, mean over a
    round, whatever implements it: the probed window's keys read once
    (the 100 tweets for a tick batch, the 10 s of ticks for a tweet
    batch), the trigger rows' keys read once, and every pair's price read
    and its row written (17 B). At the cell's sizes: about 2,130 pairs a
    tick batch, 872,000 a tweet batch."""
    ticks, tweets, n_ticks, n_tweets, pairs_tick, pairs_tweet, window = \
        _round(config, sizes)
    tick_batch = (sizes["length"] + ticks) * _KEY + pairs_tick * _PAIR
    tweet_batch = (window + tweets) * _KEY + pairs_tweet * _PAIR
    return int((n_ticks * tick_batch + n_tweets * tweet_batch)
               / (n_ticks + n_tweets))


def bytes_per_batch(config, sizes, rows):
    """The least HBM traffic of a whole step, mean over a round: the
    probe's, and the batch's rows read once and written once into their
    window."""
    ticks, tweets, n_ticks, n_tweets, *_ = _round(config, sizes)
    ingest = 2 * (n_ticks * ticks * _TICK + n_tweets * tweets * _TWEET) \
        / (n_ticks + n_tweets)
    return int(probe_bytes_per_batch(config, sizes) + ingest)
