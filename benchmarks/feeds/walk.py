"""Feed kind ``walk``: one input stream, ``config["inputs"][0]``, keys
drawn as the kind ``stream`` draws them (``keys``: ``uniform``,
``hot_set`` or ``zipf``) and one timestamp a row (row j of batch i is
stamped ``i * rows + j``).

Every seed does the same work. The pattern of the keys, which rows of
the pool share a key, is drawn once from ``key_pattern_seed`` (a number
of the traffic file), and the seed relabels it: a permutation of the
keys drawn from the seed says which symbol each key of the pattern is,
and so which symbols are hot. A batch's most rows of one key, which is
the rounds the generic NFA engine runs and what a batch's latency
follows, are then the same for every seed; drawn afresh per seed they
moved a run's p95 by a whole round.

A value column whose spec is ``{"dist": "walk", "start_lo", "start_hi",
"step_rel"}`` is every key's own price path: geometric Brownian motion,
the textbook model of a stock's price. Each key starts at a price drawn
uniformly from ``[start_lo, start_hi)``; each of its events moves the
log of the price by a Gaussian step of standard deviation ``step_rel``
(so up and down are equally likely). The walk is carried across the pool
in row order: a key's next event, in this batch or a later one, goes on
from its last price. It is formed in float64 and sent in the column's
dtype, unrounded otherwise. Every other column is drawn from its own spec
as the kind ``stream`` draws it. Draw order from the seed: the
relabelling, then the starts, then the steps, then the other columns
batch by batch.
"""

import numpy as np

from benchmarks.generator import (Batch, Feed, draw_value, key_names,
                                  key_sampler, warm_batch)


def walk_column(rng, spec, keys, n_keys):
    """One key's price after each of its events, for every row of
    ``keys`` (the pool's keys in row order)."""
    start = spec["start_lo"] + rng.random(n_keys) * (
        spec["start_hi"] - spec["start_lo"])
    step = rng.standard_normal(len(keys)) * spec["step_rel"]
    order = np.argsort(keys, kind="stable")
    k, step = keys[order], step[order]
    run = np.cumsum(step)
    counts = np.bincount(keys, minlength=n_keys)
    first = (np.cumsum(counts) - counts)[k]    # the key's first sorted row
    logp = np.empty(len(keys))
    logp[order] = np.log(start[k]) + run - run[first] + step[first]
    return np.exp(logp).astype(np.dtype(spec["dtype"]))


def make(rng, config, traffic, sizes):
    inp = config["inputs"][0]
    n_keys, rows, n_pool = sizes["keys"], traffic["batch_rows"], \
        traffic["pool_batches"]
    draw, facts = key_sampler(
        np.random.default_rng(traffic["key_pattern_seed"]), traffic["keys"],
        n_keys)
    pattern = [draw(rows) for _ in range(n_pool)]
    relabel = rng.permutation(n_keys).astype(np.int64)
    keys = [relabel[k] for k in pattern]
    facts = {name: relabel[k] for name, k in facts.items()}
    flat = np.concatenate(keys)
    walks = {c: walk_column(rng, s, flat, n_keys).reshape(n_pool, rows)
             for c, s in inp["columns"].items() if s["dist"] == "walk"}
    pool = []
    for i, k in enumerate(keys):
        cols = {c: (walks[c][i] if c in walks else draw_value(rng, s, rows))
                for c, s in inp["columns"].items()}
        pool.append(Batch(0, k, cols))
    warm = [warm_batch(0, inp, rows, n_keys)]
    return Feed([inp["stream"]], [inp["key"]], key_names(config, n_keys),
                warm, pool, rows, facts=facts)
