"""The one traffic generator: turns a configuration file and a traffic
file into batches, from ``--seed`` alone.

A traffic file (``benchmarks/traffic/<name>.json``) is parameters, never
code. Two kinds exist:

``"kind": "stream"`` — one input stream. Every batch draws ``batch_rows``
  keys from ``keys`` (``uniform``; ``hot_set``: ``hot_share`` of the keys,
  fixed for the run and drawn from the seed, take ``hot_traffic`` of the
  events; ``zipf``: exponent ``s`` over a seeded permutation of the keys)
  and every value column from the configuration's own column spec.
  Row j of batch i is stamped ``i * rows + j``: one timestamp per row.

``"kind": "rounds"`` — two input streams, a head batch then a tail batch
  per round, ``round_ms`` apart in event time, one timestamp per batch.
  Of each round's head rows ``answered_above`` get a tail row above them
  in the same round, ``answered_below`` one below them (no match: the
  head row stays pending), and the rest are answered ``late_rounds``
  rounds later, above them (outside a bound of fewer seconds). Tail
  batches are padded to ``batch_rows`` with rows below every head row.

Both draw a POOL of ``pool_batches`` batches (rounds) once, in set-up, and
the sequence the engine sees is: the warm batches (every key once over, at
the measured shape, so key capacity never grows later), then the pool,
cycled, with fresh timestamps. Every seed therefore sends the same sizes
in the same pattern; only which keys and values differ.

``loop`` is ``closed`` (the next batch goes when the last returned) or
``open`` with ``rate_batches_per_s``: batch i is DUE at ``i / rate`` and
is stamped with its due time, not the time it was sent.

Nothing here imports ``siddhi_tpu`` or ``jax``.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Batch:
    stream: int              # index into Feed.streams
    keys: np.ndarray         # int64 key indices, one per row
    cols: dict               # value columns by attribute name


class Feed:
    """The sequence of batches of one run. ``batch(i)`` gives batch i of the
    whole run (warm batches first), as ``send_columns`` wants it."""

    def __init__(self, streams, key_attr, names, warm, pool, rows,
                 first_ms=0, round_ms=None):
        self.streams = streams      # input stream names
        self.key_attr = key_attr    # name of the key attribute
        self.names = names          # object array: key index -> string
        self.warm = warm            # list[Batch]
        self.pool = pool            # list[Batch]
        self.rows = rows            # rows of every batch
        self.first_ms = first_ms    # event time of round 0 (rounds kind)
        self.round_ms = round_ms    # event time per round (rounds kind)
        # the key strings of every pool batch, made once: what a client
        # holds before it sends (the engine encodes them on every send)
        for b in warm + pool:
            b.cols[key_attr] = names[b.keys]

    def batch(self, i: int) -> Batch:
        if i < len(self.warm):
            return self.warm[i]
        return self.pool[(i - len(self.warm)) % len(self.pool)]

    def timestamps(self, i: int) -> np.ndarray:
        """Event timestamps of batch i: per row for a stream feed, one per
        batch (advancing ``round_ms`` a round) for a rounds feed."""
        if self.round_ms is None:
            return np.arange(i * self.rows, (i + 1) * self.rows,
                             dtype=np.int64)
        n = len(self.streams)
        t = (self.first_ms + (i // n) * self.round_ms
             + (i % n) * self.round_ms // n)
        return np.full(self.rows, t, np.int64)

    def history(self, lo: int, hi: int) -> dict:
        """Everything sent in batches [lo, hi), row by row in arrival
        order, for the reference: stream index, key index, event time and
        the value columns."""
        bs = [self.batch(i) for i in range(lo, hi)]
        value_cols = [c for c in bs[0].cols if c != self.key_attr]
        return {
            "stream": np.concatenate(
                [np.full(len(b.keys), b.stream, np.int8) for b in bs]),
            "key": np.concatenate([b.keys for b in bs]),
            "ts": np.concatenate(
                [self.timestamps(i) for i in range(lo, hi)]),
            "cols": {c: np.concatenate([b.cols[c] for b in bs])
                     for c in value_cols},
        }


def _value(rng, spec, n):
    """One value column from the configuration's column spec."""
    dtype = np.dtype(spec["dtype"])
    if spec["dist"] == "uniform":
        lo, hi = spec["lo"], spec["hi"]
        return (lo + rng.random(n) * (hi - lo)).astype(dtype)
    if spec["dist"] == "integers":
        return rng.integers(spec["lo"], spec["hi"], n).astype(dtype)
    raise ValueError(f"unknown value distribution {spec['dist']!r}")


def _key_sampler(rng, spec, n_keys):
    """Returns draw(n) -> int64 key indices, and the facts of the mix."""
    dist = spec["dist"]
    if dist == "uniform":
        return (lambda n: rng.integers(0, n_keys, n, dtype=np.int64)), {}
    if dist == "hot_set":
        n_hot = max(1, int(n_keys * spec["hot_share"]))
        order = rng.permutation(n_keys).astype(np.int64)
        hot, cold = order[:n_hot], order[n_hot:]

        def draw(n):
            is_hot = rng.random(n) < spec["hot_traffic"]
            return np.where(is_hot, hot[rng.integers(0, n_hot, n)],
                            cold[rng.integers(0, len(cold), n)])
        return draw, {"hot_keys": hot}
    if dist == "zipf":
        order = rng.permutation(n_keys).astype(np.int64)
        p = 1.0 / np.arange(1, n_keys + 1) ** spec["s"]
        cdf = np.cumsum(p / p.sum())
        return (lambda n: order[np.minimum(
            np.searchsorted(cdf, rng.random(n)), n_keys - 1)]), {}
    raise ValueError(f"unknown key distribution {dist!r}")


def _stream_feed(rng, config, traffic, n_keys, rows):
    inp = config["inputs"][0]
    draw, facts = _key_sampler(rng, traffic["keys"], n_keys)
    ones = {c: np.ones(rows, np.dtype(s["dtype"]))
            for c, s in inp["columns"].items()}
    warm = [Batch(0, np.arange(rows, dtype=np.int64) % n_keys, ones)]
    pool = [Batch(0, draw(rows),
                  {c: _value(rng, s, rows)
                   for c, s in inp["columns"].items()})
            for _ in range(traffic["pool_batches"])]
    return warm, pool, facts


def _rounds_feed(rng, config, traffic, n_keys, rows):
    """See the module docstring; PR 21's phase C feed made cyclic: a late
    answer of pool round r lands in pool round (r + late_rounds) mod P."""
    head, _tail = config["inputs"]
    (vcol, vspec), = head["columns"].items()
    above = traffic["answered_above"]
    now_share = above + traffic["answered_below"]
    late_rounds, n_pool = traffic["late_rounds"], traffic["pool_batches"]
    ka0 = np.arange(rows, dtype=np.int64) % n_keys
    va0 = _value(rng, vspec, rows)
    warm = [Batch(0, ka0, {vcol: va0}), Batch(1, ka0, {vcol: va0 + 1.0})]
    heads, late = [], [[] for _ in range(n_pool)]
    for r in range(n_pool):
        ka = rng.integers(0, n_keys, rows, dtype=np.int64)
        va = _value(rng, vspec, rows)
        kind = rng.random(rows)
        heads.append((ka, va, kind))
        late[(r + late_rounds) % n_pool].append(
            (ka[kind >= now_share], va[kind >= now_share] + 1.0))
    pool = []
    for r, (ka, va, kind) in enumerate(heads):
        now = kind < now_share
        kb = [ka[now]]
        vb = [np.where(kind[now] < above, va[now] + 1.0, va[now] - 1.0)]
        for lk, lv in late[r]:
            kb.append(lk)
            vb.append(lv)
        kb, vb = np.concatenate(kb)[:rows], np.concatenate(vb)[:rows]
        pad = rows - len(kb)
        if pad:
            kb = np.concatenate([kb, ka[:pad]])
            vb = np.concatenate([vb, np.full(pad, vspec["lo"] - 1.0)])
        pool.append(Batch(0, ka, {vcol: va}))
        pool.append(Batch(1, kb, {vcol: vb.astype(va.dtype)}))
    return warm, pool, {}


def make_feed(config: dict, sizes: dict, traffic: dict, seed: int) -> Feed:
    """The feed of one run, at the sizes given (``manifest.Cell.sized``)."""
    rng = np.random.default_rng(seed)
    n_keys, rows = sizes["keys"], traffic["batch_rows"]
    inputs = config["inputs"]
    names = np.array([f"{inputs[0]['key_prefix']}{i}"
                      for i in range(n_keys)], dtype=object)
    make = {"stream": _stream_feed, "rounds": _rounds_feed}[traffic["kind"]]
    warm, pool, facts = make(rng, config, traffic, n_keys, rows)
    feed = Feed([i["stream"] for i in inputs], inputs[0]["key"], names,
                warm, pool, rows, traffic.get("first_ms", 0),
                traffic.get("round_ms"))
    feed.facts = facts
    feed.fill_batches = _fill_batches(feed, traffic, sizes, facts)
    return feed


def _fill_batches(feed, traffic, sizes, facts) -> int:
    """How many batches set-up sends after the warm ones to bring the
    state to its steady shape: a fixed count, or as many as it takes for
    every hot key to have had ``window`` events (its ring has wrapped)."""
    fill = traffic["fill"]
    if "batches" in fill:
        return int(fill["batches"])
    if fill["until"] != "hot_rings_wrapped":
        raise ValueError(f"unknown fill rule {fill!r}")
    hot = facts["hot_keys"]
    counts = np.zeros(sizes["keys"], np.int64)
    for n in range(1, fill["at_most_batches"] + 1):
        counts += np.bincount(feed.pool[(n - 1) % len(feed.pool)].keys,
                              minlength=sizes["keys"])
        if counts[hot].min() >= sizes["window"]:
            return n
    raise ValueError(f"the hot rings do not wrap within "
                     f"{fill['at_most_batches']} batches")
