"""The benchmark's feeds in tier-1: the numpy-only cases of
``benchmarks/tests/test_feeds.py`` (every older cell's feed bit for bit
as recorded, kinds found by name, the Zipf cell's mix, ``two_stream``),
run here as they are written there, and the kind ``walk`` of the sequence
cell. What starts the engine stays with the benchmark's own tests.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

from benchmarks import generator, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks", "tests")
WALK = "sequence_peak_10k.hot20_walk16k"
JOIN = "join_time10s_len100.hot20_tweets1k"


def _load(name):
    """A module of ``benchmarks/tests`` with that directory's own
    ``conftest`` under its import name, as pytest gives it there."""
    def module(path, as_name):
        spec = importlib.util.spec_from_file_location(as_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    saved = sys.modules.get("conftest")
    sys.modules["conftest"] = module(os.path.join(HERE, "conftest.py"),
                                     "benchmarks_tests_conftest")
    try:
        return module(os.path.join(HERE, name + ".py"),
                      "benchmarks_tests_" + name)
    finally:
        if saved is None:
            sys.modules.pop("conftest")
        else:
            sys.modules["conftest"] = saved


_feeds = _load("test_feeds")

test_an_older_cells_feed_is_bit_for_bit_what_it_was = \
    _feeds.test_an_older_cells_feed_is_bit_for_bit_what_it_was
test_a_kind_is_its_files_name_and_an_unknown_one_names_those_there = \
    _feeds.test_a_kind_is_its_files_name_and_an_unknown_one_names_those_there
test_no_statement_of_the_harness_names_a_kind = \
    _feeds.test_no_statement_of_the_harness_names_a_kind
test_a_kind_dropped_into_a_copy_is_found_with_no_other_edit = \
    _feeds.test_a_kind_dropped_into_a_copy_is_found_with_no_other_edit
test_zipf_gives_rank_r_to_the_rth_key_of_a_seeded_permutation = \
    _feeds.test_zipf_gives_rank_r_to_the_rth_key_of_a_seeded_permutation
test_zipf_cycles_its_pool_and_only_the_seed_changes_it = \
    _feeds.test_zipf_cycles_its_pool_and_only_the_seed_changes_it
test_at_real_sizes_zipf_is_the_mix_the_cell_says = \
    _feeds.test_at_real_sizes_zipf_is_the_mix_the_cell_says
test_two_stream_has_a_schema_a_key_attribute_and_strings_a_side = \
    _feeds.test_two_stream_has_a_schema_a_key_attribute_and_strings_a_side


def test_every_cell_before_the_sequence_cell_is_recorded():
    """The record holds the six cells that were there before kinds were
    files; the Zipf cell, the sequence cell and the join cell came after
    it (their feeds are pinned below and in ``test_feeds.py``)."""
    cells = {w["name"] for w in manifest.Cell(WALK).bench["workloads"]}
    recorded = {r.split("|")[0] for r in _feeds.RECORDED}
    assert recorded == cells - {_feeds.ZIPF, WALK, JOIN}
    assert len(_feeds.RECORDED) == 6 * 2 * 2


# ------------------------------------------------------- two_stream_rows

JOIN_DIGESTS = {   # rehearsal sizes, by seed: ``_feeds.digest``
    5: "7e58b6539e030bae6f3b5281897c0b67ccb3107a555bea84cfb1fcd2d5db6fb5",
    3_000_000_017:
        "143f335d10818465655280d0f9f984094f47db8d0d8c0a7df596a136f3f8d66e",
}


def _join(rehearsal, seed):
    cell = manifest.Cell(JOIN)
    sizes, traffic = cell.sized(rehearsal)
    return generator.make_feed(cell.config, sizes, traffic, seed), traffic


@pytest.mark.parametrize("seed", sorted(JOIN_DIGESTS))
def test_the_join_cells_feed_is_bit_for_bit_what_it_was(seed):
    feed, traffic = _join(True, seed)
    assert manifest.feed_kind(traffic["kind"]).__name__.endswith(
        "feeds.two_stream_rows")
    assert _feeds.digest(feed) == JOIN_DIGESTS[seed]


def test_two_stream_rows_gives_each_stream_its_rows_and_a_stamp_a_row():
    feed, traffic = _join(True, 5)
    ticks, tweets = traffic["rows_by_stream"]
    assert (ticks, tweets) == (1024, 32) and feed.rows == ticks
    assert [len(b.keys) for b in feed.warm] == [ticks, tweets]
    assert [b.stream for b in feed.pool[:5]] == [0, 0, 0, 0, 1]
    for i in range(len(feed.warm) + len(feed.pool) + 3):
        b = feed.batch(i)
        assert len(b.keys) == (ticks, tweets)[b.stream]
        assert all(len(c) == len(b.keys) for c in b.cols.values())
        ts = feed.timestamps(i)
        assert len(ts) == len(b.keys) and len(set(ts.tolist())) == 1
        # a batch every 200 ms of event time: four of ticks, one of tweets
        assert int(ts[0]) == traffic["first_ms"] + 200 * i
    hist = feed.history(0, 7)
    assert len(hist["cols"]["cseEventStream"]["price"]) == 5 * ticks
    assert len(hist["cols"]["twitterStream"]["tweet"]) == 2 * tweets
    # at real sizes: 65,536 ticks and 1,024 tweets a batch
    real, _ = _join(False, 5)
    assert [len(real.batch(i).keys) for i in (0, 1, 2, 6)] == [
        65536, 1024, 65536, 1024]


# ------------------------------------------------------------------- walk

def _walk(rehearsal, seed):
    cell = manifest.Cell(WALK)
    sizes, traffic = cell.sized(rehearsal)
    return generator.make_feed(cell.config, sizes, traffic, seed), sizes


def test_walk_is_found_by_its_files_name():
    kind = manifest.feed_kind(manifest.Cell(WALK).traffic["kind"])
    assert kind.__name__.endswith("feeds.walk")
    assert "walk" in {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmarks", "feeds"))}


def test_walk_draws_the_same_bytes_for_one_seed_and_others_for_another():
    feed, _ = _walk(True, 5)
    assert _feeds.digest(_walk(True, 5)[0]) == _feeds.digest(feed)
    assert _feeds.digest(_walk(True, 6)[0]) != _feeds.digest(feed)
    assert _feeds.digest(_walk(False, 5)[0]) == _feeds.digest(
        _walk(False, 5)[0])


def test_walk_carries_each_symbols_price_across_batches():
    """Every key's prices in row order, across the pool, are ONE walk: the
    step from an event to the key's next event (in the batch or a later
    one) is a Gaussian move of the log price, 0.1%, as likely up as down;
    the starts spread over [5, 200)."""
    feed, sizes = _walk(False, 11)
    spec = feed.pool[0].cols
    assert spec["price"].dtype == np.float32
    assert spec["volume"].dtype == np.int32
    keys = np.concatenate([b.keys for b in feed.pool])
    logp = np.log(np.concatenate([b.cols["price"] for b in feed.pool])
                  .astype(np.float64))
    order = np.argsort(keys, kind="stable")
    same = keys[order][1:] == keys[order][:-1]
    step = np.diff(logp[order])[same]
    assert len(step) > 1_000_000
    assert abs(step.std() - 0.001) < 2e-5
    assert abs(step.mean()) < 1e-5
    assert 0.49 < (step > 0).mean() < 0.51
    # a key's first and last price of the pool lie one walk apart:
    # sqrt(events) steps, not a fresh draw (which would spread 5..200)
    first = np.full(sizes["keys"], -1)
    first[keys[order][~np.append(False, same)]] = order[~np.append(False,
                                                                   same)]
    hot = feed.facts["hot_keys"]
    last = np.zeros(sizes["keys"], np.int64)
    last[keys] = np.arange(len(keys))
    drift = np.abs(logp[last[hot]] - logp[first[hot]])
    assert np.median(drift) < 0.05
    starts = np.exp(logp[first[first >= 0]])
    assert 5.0 <= starts.min() and starts.max() < 200.0 * 1.01
    assert 0.9 < (starts > 20).mean() < 0.95
    # the warm batch: every key once over, price 1 (below every e1)
    (warm,) = feed.warm
    assert set(warm.keys.tolist()) == set(range(sizes["keys"]))
    assert (warm.cols["price"] == 1).all()
    # one timestamp a row
    assert np.array_equal(np.diff(feed.timestamps(3)),
                          np.ones(feed.rows - 1))


@pytest.mark.parametrize("seed", [24, 2 ** 31 + 24])
def test_at_real_sizes_a_batch_needs_the_rounds_the_cell_says(seed):
    """The figures of the cell's ``why`` and of the traffic file: 16,384
    rows over 10,000 symbols, the hot 2,000 taking 80%, a batch's most
    rows of one symbol (the generic engine's rounds) 15-21, 17.0 on
    average over the pool of 128, the same batch by batch for every seed;
    the window's (about 86 batches from the fill on) five longest need 20
    or 21 rounds and the next five 19."""
    feed, sizes = _walk(False, seed)
    assert (len(feed.pool), feed.fill_batches, feed.rows) == (128, 8, 16_384)
    most = [int(np.bincount(b.keys, minlength=sizes["keys"]).max())
            for b in feed.pool]
    assert (min(most), max(most)) == (15, 21)
    assert 16.95 < np.mean(most) < 17.05
    window = sorted(most[8:8 + 86])
    assert window[-5:] == [20, 20, 20, 20, 21] and window[-10:-5] == [19] * 5
    assert most == [int(np.bincount(b.keys).max())
                    for b in _walk(False, seed + 1)[0].pool]
    hot = np.isin(feed.pool[0].keys, feed.facts["hot_keys"])
    assert 0.78 < hot.mean() < 0.82


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 3])
def test_walk_relabels_one_pattern_of_keys_by_the_seed(seed):
    """Every seed sends the same pattern of keys (which rows of a batch
    share a key), relabelled by a permutation of the keys drawn from the
    seed: the same work, other symbols hot, other keys and prices."""
    feed, sizes = _walk(True, seed)
    other, _ = _walk(True, seed + 1)
    hot, hot2 = feed.facts["hot_keys"], other.facts["hot_keys"]
    assert len(hot) == len(hot2) == int(sizes["keys"] * 0.2)
    assert set(hot.tolist()) != set(hot2.tolist())
    for a, b in zip(feed.pool, other.pool):
        assert not np.array_equal(a.keys, b.keys)
        assert not np.array_equal(a.cols["price"], b.cols["price"])
        # one map from the first seed's keys to the second's, row by row
        pairs = set(zip(a.keys.tolist(), b.keys.tolist()))
        assert len(pairs) == len(set(a.keys.tolist())) == len(
            set(b.keys.tolist()))
    # and that map takes the first seed's hot set to the second's
    m = dict(set(zip(np.concatenate([b.keys for b in feed.pool]).tolist(),
                     np.concatenate([b.keys for b in other.pool]).tolist())))
    assert {m[k] for k in hot.tolist() if k in m} <= set(hot2.tolist())
