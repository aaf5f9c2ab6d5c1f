"""The one traffic generator: turns a configuration file and a traffic
file into batches, from ``--seed`` alone.

A traffic file (``benchmarks/traffic/<name>.json``) is parameters, never
code. Its ``kind`` names the feed kind that reads them, a file
``benchmarks/feeds/<kind>.py`` found by that name as ``manifest.py``
finds a reference or a reader; nothing here lists the kinds, and a later
PR adds one by adding its file (each says in its docstring what
parameters it reads). What every traffic file has whatever its kind:
``batch_rows``; ``pool_batches``; ``fill`` (``{"batches": n}``, or
``{"until": "hot_rings_wrapped", "at_most_batches": n}``); a
``rehearsal`` entry laid over the file's top level for a CPU rehearsal;
``loop``, which is ``closed`` (the next batch goes when the last
returned) or ``open`` with ``rate_batches_per_s``: batch i is DUE at
``i / rate`` and is stamped with its due time, not the time it was sent.

**The contract a kind fulfils.** Its module has one function,
``make(rng, config, traffic, sizes) -> Feed``, which makes every draw of
the run from ``rng`` (seeded with ``--seed`` and nothing else) and
returns a ``Feed`` below, or a subclass of it. ``run.py``, ``drive.py``
and the references use this of a feed and nothing beyond it:

- ``streams``: the input stream names; ``warm`` and ``pool``: the
  sequence the engine sees is the warm batches (every key once over, at
  the measured shape, so key capacity never grows later), then the pool,
  cycled, with fresh timestamps. Every seed therefore sends the same
  sizes in the same pattern; only which keys and values differ.
- ``batch(i)``: batch i of the whole run as ``send_columns`` wants it:
  the index of its stream, its key indices, and its columns by THAT
  stream's attribute names (each entry of ``config["inputs"]`` has its
  own ``key`` attribute and its own ``columns``), the key strings and
  string payloads as object arrays. Its rows are ``len(batch(i).keys)``;
  ``rows`` is the traffic file's ``batch_rows``, which every batch of a
  feed that has one size has.
- ``timestamps(i)``: the event time of every row of batch i.
- ``history(lo, hi)``: everything sent in batches [lo, hi), row by row in
  arrival order, for the reference. Streams of one schema give their
  value columns under ``cols`` by name; a kind whose streams differ gives
  them by stream name, each stream's rows alone. A string payload is
  there as indices into its table.
- ``tables``: for each column of strings the table that turns an index
  into the string sent, ``"key"`` (also ``names``) for the one key space
  all streams share. ``run.py`` decodes every output role that the
  configuration's ``output["strings"]`` maps to a table (none given: the
  role ``key``) through the app's dictionary and the table back to
  indices.
- ``key_attr`` (the first stream's key attribute), ``facts`` (what the
  kind knows of its mix: the hot keys) and ``fill_batches`` (set by
  ``make_feed``): how many batches set-up sends after the warm ones.

Nothing here imports ``siddhi_tpu`` or ``jax``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks import manifest


@dataclasses.dataclass
class Batch:
    stream: int              # index into Feed.streams
    keys: np.ndarray         # int64 key indices, one per row
    cols: dict               # the columns sent, by attribute name
    codes: dict = dataclasses.field(default_factory=dict)
    # ^ a string payload column as indices into Feed.tables[its name]


class Feed:
    """The sequence of batches of one run. ``batch(i)`` gives batch i of the
    whole run (warm batches first), as ``send_columns`` wants it."""

    def __init__(self, streams, key_attrs, names, warm, pool, rows,
                 first_ms=0, round_ms=None, round_batches=None, tables=None,
                 facts=None):
        self.streams = streams      # input stream names
        self.key_attrs = key_attrs  # each stream's key attribute
        self.key_attr = key_attrs[0]
        self.names = names          # object array: key index -> string
        self.tables = {"key": names, **(tables or {})}
        self.warm = warm            # list[Batch]
        self.pool = pool            # list[Batch]
        self.rows = rows            # the traffic file's batch_rows
        self.first_ms = first_ms    # event time of round 0
        self.round_ms = round_ms    # event time per round; None: per row
        self.round_batches = round_batches or len(streams)  # batches a round
        self.facts = facts or {}
        # the key strings of every pool batch, made once: what a client
        # holds before it sends (the engine encodes them on every send)
        for b in warm + pool:
            b.cols[key_attrs[b.stream]] = names[b.keys]

    def batch(self, i: int) -> Batch:
        if i < len(self.warm):
            return self.warm[i]
        return self.pool[(i - len(self.warm)) % len(self.pool)]

    def timestamps(self, i: int) -> np.ndarray:
        """Event timestamps of batch i: one per row (row j of batch i is
        stamped ``i * rows + j``) where the feed has no ``round_ms``, else
        one per batch, advancing ``round_ms`` a round of
        ``round_batches`` batches (none given: one batch a stream)."""
        if self.round_ms is None:
            return np.arange(i * self.rows, (i + 1) * self.rows,
                             dtype=np.int64)
        n = self.round_batches
        t = (self.first_ms + (i // n) * self.round_ms
             + (i % n) * self.round_ms // n)
        return np.full(self.rows, t, np.int64)

    def history(self, lo: int, hi: int) -> dict:
        """Everything sent in batches [lo, hi), row by row in arrival
        order, for the reference: stream index, key index, event time and
        the value columns."""
        bs = [self.batch(i) for i in range(lo, hi)]
        return {
            "stream": np.concatenate(
                [np.full(len(b.keys), b.stream, np.int8) for b in bs]),
            "key": np.concatenate([b.keys for b in bs]),
            "ts": np.concatenate(
                [self.timestamps(i) for i in range(lo, hi)]),
            "cols": self.value_columns(bs),
        }

    def value_columns(self, bs) -> dict:
        """The value columns of these batches by name (one schema: those
        of the first); a kind whose streams differ gives them by stream."""
        return {c: np.concatenate([b.cols[c] for b in bs])
                for c in bs[0].cols if c != self.key_attr}


def key_names(config, n_keys):
    """The one key space of a configuration's streams: index -> string."""
    prefix = config["inputs"][0]["key_prefix"]
    return np.array([f"{prefix}{i}" for i in range(n_keys)], dtype=object)


def warm_batch(stream, inp, rows, n_keys):
    """A warm batch of one stream: every key once over at the measured
    shape (so key capacity never grows later), every value 1."""
    ones = {c: np.ones(rows, np.dtype(s["dtype"]))
            for c, s in inp["columns"].items()}
    return Batch(stream, np.arange(rows, dtype=np.int64) % n_keys, ones)


def draw_value(rng, spec, n):
    """One value column from the configuration's column spec."""
    dtype = np.dtype(spec["dtype"])
    if spec["dist"] == "uniform":
        lo, hi = spec["lo"], spec["hi"]
        return (lo + rng.random(n) * (hi - lo)).astype(dtype)
    if spec["dist"] == "integers":
        return rng.integers(spec["lo"], spec["hi"], n).astype(dtype)
    raise ValueError(f"unknown value distribution {spec['dist']!r}")


def string_table(spec):
    """The table of a string payload column, whose spec is ``{"dtype":
    "str", "prefix": p, "distinct": n}``: the n strings ``p0 .. p<n-1>``, of
    which a kind draws indices. None for a column of numbers."""
    if spec["dtype"] != "str":
        return None
    return np.array([f"{spec['prefix']}{i}"
                     for i in range(spec["distinct"])], dtype=object)


def key_sampler(rng, spec, n_keys):
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


def make_feed(config: dict, sizes: dict, traffic: dict, seed: int) -> Feed:
    """The feed of one run, at the sizes given (``manifest.Cell.sized``),
    made by the kind the traffic file names."""
    kind = manifest.feed_kind(traffic["kind"])
    feed = kind.make(np.random.default_rng(seed), config, traffic, sizes)
    feed.fill_batches = _fill_batches(feed, traffic, sizes)
    return feed


def _fill_batches(feed, traffic, sizes) -> int:
    """How many batches set-up sends after the warm ones to bring the
    state to its steady shape: a fixed count, or as many as it takes for
    every hot key to have had ``window`` events (its ring has wrapped)."""
    fill = traffic["fill"]
    if "batches" in fill:
        return int(fill["batches"])
    if fill["until"] != "hot_rings_wrapped":
        raise ValueError(f"unknown fill rule {fill!r}")
    hot = feed.facts["hot_keys"]
    counts = np.zeros(sizes["keys"], np.int64)
    for n in range(1, fill["at_most_batches"] + 1):
        counts += np.bincount(feed.pool[(n - 1) % len(feed.pool)].keys,
                              minlength=sizes["keys"])
        if counts[hot].min() >= sizes["window"]:
            return n
    raise ValueError(f"the hot rings do not wrap within "
                     f"{fill['at_most_batches']} batches")
