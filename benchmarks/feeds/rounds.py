"""Feed kind ``rounds``: two input streams of one schema (one value
column), a head batch then a tail batch per round, ``round_ms`` apart in
event time, one timestamp per batch.

Of each round's head rows ``answered_above`` get a tail row above them
in the same round, ``answered_below`` one below them (no match: the
head row stays pending), and the rest are answered ``late_rounds``
rounds later, above them (outside a bound of fewer seconds). Tail
batches are padded to ``batch_rows`` with rows below every head row.
PR 21's phase C feed made cyclic: a late answer of pool round r lands in
pool round (r + late_rounds) mod P.
"""

import numpy as np

from benchmarks.generator import Batch, Feed, draw_value, key_names


def make(rng, config, traffic, sizes):
    head, tail = config["inputs"]
    n_keys, rows = sizes["keys"], traffic["batch_rows"]
    (vcol, vspec), = head["columns"].items()
    above = traffic["answered_above"]
    now_share = above + traffic["answered_below"]
    late_rounds, n_pool = traffic["late_rounds"], traffic["pool_batches"]
    ka0 = np.arange(rows, dtype=np.int64) % n_keys
    va0 = draw_value(rng, vspec, rows)
    warm = [Batch(0, ka0, {vcol: va0}), Batch(1, ka0, {vcol: va0 + 1.0})]
    heads, late = [], [[] for _ in range(n_pool)]
    for r in range(n_pool):
        ka = rng.integers(0, n_keys, rows, dtype=np.int64)
        va = draw_value(rng, vspec, rows)
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
    return Feed([head["stream"], tail["stream"]], [head["key"], tail["key"]],
                key_names(config, n_keys), warm, pool, rows,
                traffic.get("first_ms", 0), traffic.get("round_ms"))
