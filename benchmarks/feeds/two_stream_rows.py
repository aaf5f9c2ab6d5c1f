"""Feed kind ``two_stream_rows``: ``two_stream`` (two input streams with a
schema each, over one key space, a round of ``ratio[0]`` batches of the
first stream and ``ratio[1]`` of the second) where each stream has its own
batch rows: ``rows_by_stream`` ``[first, second]``. ``batch_rows`` is the
first's. The warm batches are one a stream at its own rows, keys once
over from 0; ``timestamps(i)`` gives one stamp for every row of batch
``i``, at that batch's own length, ``round_ms`` a round as ``two_stream``
stamps them.
"""

import numpy as np

from benchmarks import manifest
from benchmarks.generator import key_names, key_sampler, string_table

_two_stream = manifest.feed_kind("two_stream")    # found by name, as any kind
_batch = _two_stream._batch


class TwoStreamRowsFeed(_two_stream.TwoStreamFeed):
    def timestamps(self, i: int) -> np.ndarray:
        t = super().timestamps(i)[0]
        return np.full(len(self.batch(i).keys), t, np.int64)


def make(rng, config, traffic, sizes):
    inputs = config["inputs"]
    rows = traffic["rows_by_stream"]
    ratio = traffic.get("ratio", [1, 1])
    if (len(inputs) != 2 or len(rows) != 2 or rows[0] != traffic["batch_rows"]
            or traffic["pool_batches"] % sum(ratio)):
        raise ValueError(
            f"a two_stream_rows feed has two inputs (not {len(inputs)}), "
            f"rows for each (not {rows}), the first's being batch_rows, and "
            f"a pool of {traffic['pool_batches']} batches that is a whole "
            f"number of rounds of {sum(ratio)}")
    tables = {}
    for inp in inputs:
        for c, spec in inp["columns"].items():
            table = string_table(spec)
            if table is not None:
                tables[c] = table
    n_keys = sizes["keys"]
    draw, facts = key_sampler(rng, traffic["keys"], n_keys)
    warm = [_batch(rng, n, inp, np.arange(rows[n], dtype=np.int64) % n_keys,
                   tables)
            for n, inp in enumerate(inputs)]
    sides = [0] * ratio[0] + [1] * ratio[1]
    pool = []
    for i in range(traffic["pool_batches"]):
        side = sides[i % len(sides)]
        pool.append(_batch(rng, side, inputs[side], draw(rows[side]), tables))
    return TwoStreamRowsFeed(
        [i["stream"] for i in inputs], [i["key"] for i in inputs],
        key_names(config, n_keys), warm, pool, rows[0],
        traffic.get("first_ms", 0), traffic["round_ms"], sum(ratio),
        tables=tables, facts=facts)
