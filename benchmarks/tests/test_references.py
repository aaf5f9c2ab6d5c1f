"""The fast references against the event-at-a-time loops they stand for,
and the control: the reference computed in the precision below the
configuration's has to FAIL the comparison (the chip readings at the
cells' own sizes are in PERF.md section 2)."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmarks import generator, manifest
from benchmarks.references import global_window, keyed_window, pattern


def _cell(name):
    return manifest.Cell(name)


def _tiny_feed(cell, seed):
    sizes, traffic = cell.sized(rehearsal=True)
    return sizes, generator.make_feed(cell.config, sizes, traffic, seed)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_keyed_window_equals_its_loop(seed):
    cell = _cell("partition_len1k_10k.hot20_bulk")
    sizes, feed = _tiny_feed(cell, seed)
    want = keyed_window.reference(cell.config, sizes, feed, 30)
    hist = feed.history(0, 30)
    avg, total = keyed_window.loop_reference(
        hist["key"], hist["cols"]["price"], hist["cols"]["volume"],
        sizes["window"])
    assert np.array_equal(want["sum"], total)
    assert np.abs(want["avg"] - avg).max() < 1e-12
    assert want["facts"]["rings_wrapped"] > 0


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_global_window_equals_its_loop_on_sampled_batches(seed):
    cell = _cell("groupby_len1k_10k.uniform_bulk")
    sizes, feed = _tiny_feed(cell, seed)
    sample = np.array([0, 3, 4, 19])
    want = global_window.reference(cell.config, sizes, feed, 20, sample)
    hist = feed.history(0, 20)
    avg, total = global_window.loop_reference(
        hist["key"], hist["cols"]["price"], hist["cols"]["volume"],
        sizes["window"])
    assert len(want["rows"]) == len(sample) * feed.rows
    assert np.array_equal(want["sum"], total[want["rows"]])
    assert np.abs(want["avg"] - avg[want["rows"]]).max() < 1e-12
    assert np.array_equal(want["key"], hist["key"])


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_pattern_equals_its_loop(seed):
    cell = _cell("pattern_ab_10k.rounds_bulk")
    sizes, feed = _tiny_feed(cell, seed)
    want = pattern.reference(cell.config, sizes, feed, 90)
    hist = feed.history(0, 90)
    v1, v2, by = pattern.loop_reference(
        hist["stream"], hist["key"], hist["cols"]["v"], hist["ts"],
        sizes["within_ms"])
    assert len(v1) > 1000
    assert np.array_equal(want["v1"], v1)
    assert np.array_equal(want["v2"], v2)
    assert np.array_equal(want["by"], by)
    assert want["rows_per_batch"].sum() == len(v1)
    assert not want["rows_per_batch"][0::2].any()     # A batches: no rows
    # the traffic does what its file says: some A's expire unanswered
    assert len(v1) < (hist["stream"] == 0).sum()


@pytest.mark.parametrize("workload", [
    "partition_len1k_10k.hot20_bulk", "groupby_len1k_10k.uniform_bulk",
    "pattern_ab_10k.rounds_bulk", "partition_len1k_10k.zipf_scrambled"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_fails_the_comparison(workload, seed):
    """One precision below the configuration's, at the most favourable
    (sums exact, rounded once), still reads over the limit."""
    cell = _cell(workload)
    cfg = cell.config
    sizes, feed = _tiny_feed(cell, seed)
    want = cell.family.reference(cfg, sizes, feed, 60)
    ctl = cell.family.reference(cfg, sizes, feed, 60,
                                dtype=cfg["control_precision"])
    same = cell.family.compare(cfg, want, want)
    assert all(v <= lim for _, v, lim in same)
    numbers = cell.family.compare(cfg, want, ctl)
    over = [n for n, v, lim in numbers if v > lim]
    assert over, numbers
    # ... by a number about values, never by rows or keys
    assert not {"rows_missing", "key_mismatch_rows"} & set(over)


def test_configuration_files_state_what_the_issue_asks():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for entry in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, entry["file"])))
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        for key in ("assumed", "guarantees", "family", "limits",
                    "control_precision"):
            assert cfg[key], (entry["name"], key)
        family = os.path.join(ROOT, "benchmarks", "references",
                              cfg["family"] + ".py")
        assert os.path.isfile(family)


def test_bytes_per_batch_at_the_cells_sizes():
    """The reckoning PERF.md shows, pinned."""
    part = _cell("partition_len1k_10k.hot20_bulk")
    assert part.family.bytes_per_batch(
        part.config, part.config["sizes"], 65536) == 65536 * 76 + 10000 * 48
    grp = _cell("groupby_len1k_10k.uniform_bulk")
    assert grp.family.bytes_per_batch(
        grp.config, grp.config["sizes"], 65536) == (
            65536 * 52 + 2 * 1000 * 20 + 10000 * 40)
    pat = _cell("pattern_ab_10k.rounds_bulk")
    assert pat.family.bytes_per_batch(
        pat.config, pat.config["sizes"], 16384) == 16384 * 123 // 2
