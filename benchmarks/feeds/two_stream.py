"""Feed kind ``two_stream``: two input streams with a schema each, over
one key space: ``config["inputs"][0]`` and ``[1]``, each with its own
``key`` attribute and its own ``columns`` (a join's two sides).

A column spec ``{"dtype": "str", "prefix": p, "distinct": n}`` is a string
payload: drawn uniformly from the n strings ``p0 .. p<n-1>``, sent as an
object array, kept as indices (``Batch.codes``, ``history``) with its
table under ``Feed.tables[<column>]``. Keys come from ``keys`` as the
``stream`` kind draws them (``uniform``, ``hot_set``, ``zipf``), one
sampler for both sides. A round is ``ratio[0]`` batches of the first
stream, then ``ratio[1]`` of the second (none given: one each, the sides
alternating); ``pool_batches`` is a whole number of rounds. One timestamp
a batch, ``round_ms`` a round (``first_ms``: the first), as the ``Feed``
base stamps it.
The warm batches are one a stream, every key once over.
``history`` gives ``cols`` by stream name, each stream's rows alone.
"""

import numpy as np

from benchmarks.generator import (Batch, Feed, draw_value, key_names,
                                  key_sampler, string_table)


class TwoStreamFeed(Feed):
    def value_columns(self, bs):
        cols = {}
        for n, (name, attr) in enumerate(zip(self.streams, self.key_attrs)):
            mine = [b for b in bs if b.stream == n]
            cols[name] = {
                c: np.concatenate([b.codes.get(c, b.cols[c]) for b in mine])
                for c in (mine[0].cols if mine else ()) if c != attr}
        return cols


def _batch(rng, stream, inp, keys, tables):
    cols, codes = {}, {}
    for c, spec in inp["columns"].items():
        if spec["dtype"] == "str":
            codes[c] = rng.integers(0, len(tables[c]), len(keys))
            cols[c] = tables[c][codes[c]]
        else:
            cols[c] = draw_value(rng, spec, len(keys))
    return Batch(stream, keys, cols, codes)


def make(rng, config, traffic, sizes):
    inputs = config["inputs"]
    n_keys, rows = sizes["keys"], traffic["batch_rows"]
    ratio = traffic.get("ratio", [1, 1])
    if len(inputs) != 2 or traffic["pool_batches"] % sum(ratio):
        raise ValueError(
            f"a two_stream feed has two inputs (not {len(inputs)}) and its "
            f"pool of {traffic['pool_batches']} batches is a whole number "
            f"of rounds of {sum(ratio)}")
    tables = {}
    for inp in inputs:
        for c, spec in inp["columns"].items():
            table = string_table(spec)
            if table is None:
                continue
            if c in tables:
                raise ValueError(f"two string columns named {c!r}")
            tables[c] = table
    draw, facts = key_sampler(rng, traffic["keys"], n_keys)
    once_over = np.arange(rows, dtype=np.int64) % n_keys
    warm = [_batch(rng, n, inp, once_over, tables)
            for n, inp in enumerate(inputs)]
    sides = [0] * ratio[0] + [1] * ratio[1]
    pool = []
    for i in range(traffic["pool_batches"]):
        side = sides[i % len(sides)]
        pool.append(_batch(rng, side, inputs[side], draw(rows), tables))
    return TwoStreamFeed(
        [i["stream"] for i in inputs], [i["key"] for i in inputs],
        key_names(config, n_keys), warm, pool, rows,
        traffic.get("first_ms", 0), traffic["round_ms"], sum(ratio),
        tables=tables, facts=facts)
