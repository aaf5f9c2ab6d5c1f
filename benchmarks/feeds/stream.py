"""Feed kind ``stream``: one input stream, ``config["inputs"][0]``.

Every batch draws ``batch_rows`` keys from ``keys`` (``uniform``;
``hot_set``: ``hot_share`` of the keys, fixed for the run and drawn from
the seed, take ``hot_traffic`` of the events; ``zipf``: exponent ``s``
over a seeded permutation of the keys) and every value column from the
configuration's own column spec. Row j of batch i is stamped
``i * rows + j``: one timestamp per row; with ``round_ms`` (and
``first_ms``) one timestamp per batch, ``round_ms`` apart.
"""

from benchmarks.generator import (Batch, Feed, draw_value, key_names,
                                  key_sampler, warm_batch)


def make(rng, config, traffic, sizes):
    inp = config["inputs"][0]
    n_keys, rows = sizes["keys"], traffic["batch_rows"]
    draw, facts = key_sampler(rng, traffic["keys"], n_keys)
    warm = [warm_batch(0, inp, rows, n_keys)]
    pool = [Batch(0, draw(rows),
                  {c: draw_value(rng, s, rows)
                   for c, s in inp["columns"].items()})
            for _ in range(traffic["pool_batches"])]
    return Feed([inp["stream"]], [inp["key"]], key_names(config, n_keys),
                warm, pool, rows, traffic.get("first_ms", 0),
                traffic.get("round_ms"), facts=facts)
