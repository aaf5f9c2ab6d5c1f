"""Feed kinds are found by name (``benchmarks/feeds/<kind>.py``), and the
six cells that were there before kinds were files still get the bytes they
got: ``feed_digests_parent.json`` holds the SHA-256 of every cell's feed
at seeds 0 and 24, at rehearsal and at real sizes, recorded on the tree of
PR 36 (commit 48d0789) by ``digest`` below, before ``generator.py`` was
touched. Then what PR 37 brought: the cell whose keys are YCSB's scrambled
Zipfian (kind ``stream``) and the kind ``two_stream`` (a join's two
schemas through ``drive.Sender``, no cell).
"""

import ast
import collections
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import CELLS, ROOT

from benchmarks import drive, generator, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ZIPF = "partition_len1k_10k.zipf_scrambled"
with open(os.path.join(HERE, "feed_digests_parent.json")) as _f:
    RECORDED = json.load(_f)


def digest(feed) -> str:
    """SHA-256 over everything a run sends: for the warm and the pool
    batches the stream index, the keys, every column's bytes (a column of
    strings joined) and ``timestamps(i)``; ``fill_batches``; the facts."""
    h = hashlib.sha256()

    def put(a):
        a = np.asarray(a)
        if a.dtype == object:
            h.update("\0".join(a.tolist()).encode())
        else:
            h.update(str(a.dtype).encode() + str(a.shape).encode())
            h.update(np.ascontiguousarray(a).tobytes())

    h.update(f"{len(feed.warm)} {len(feed.pool)} {feed.fill_batches}"
             .encode())
    for i in range(len(feed.warm) + len(feed.pool)):
        b = feed.batch(i)
        h.update(f"|{i} {b.stream}".encode())
        put(b.keys)
        for c in sorted(b.cols):
            h.update(c.encode())
            put(b.cols[c])
        put(feed.timestamps(i))
    for k in sorted(feed.facts):
        h.update(k.encode())
        put(feed.facts[k])
    return h.hexdigest()


def _feed(workload, rehearsal, seed):
    cell = manifest.Cell(workload)
    sizes, traffic = cell.sized(rehearsal)
    return generator.make_feed(cell.config, sizes, traffic, seed), sizes, \
        traffic


@pytest.mark.parametrize("recorded", sorted(RECORDED))
def test_an_older_cells_feed_is_bit_for_bit_what_it_was(recorded):
    workload, size, seed = recorded.split("|")
    feed, _, _ = _feed(workload, size == "rehearsal", int(seed))
    assert digest(feed) == RECORDED[recorded]


def test_every_older_cell_is_recorded():
    assert {r.split("|")[0] for r in RECORDED} == set(CELLS) - {ZIPF}
    assert len(RECORDED) == 6 * 2 * 2


# ---------------------------------------------------------- kinds by name

def _kinds():
    return sorted(f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmarks", "feeds")) if f.endswith(".py")
        and not f.startswith("_"))


def test_a_kind_is_its_files_name_and_an_unknown_one_names_those_there():
    assert {"stream", "rounds", "two_stream"} <= set(_kinds())
    for kind in _kinds():
        assert callable(manifest.feed_kind(kind).make)
    with pytest.raises(manifest.ManifestError) as err:
        manifest.feed_kind("no_such_kind")
    assert all(kind in str(err.value) for kind in _kinds())
    assert "benchmarks/feeds/no_such_kind.py" in str(err.value)
    # through the one way a kind is named: a traffic file's "kind"
    cell = manifest.Cell(CELLS[0])
    sizes, traffic = cell.sized(True)
    with pytest.raises(manifest.ManifestError):
        generator.make_feed(cell.config, sizes,
                            dict(traffic, kind="no_such_kind"), 0)


@pytest.mark.parametrize("module", ["generator", "run", "drive", "manifest"])
def test_no_statement_of_the_harness_names_a_kind(module):
    """A kind is a file under ``benchmarks/feeds/`` and a ``kind`` in a
    traffic file: no string of the harness is one, but where it is a
    docstring's or the key of a field (``hist["stream"]``, a batch's
    stream index: a field that has a kind's name, not the kind)."""
    path = os.path.join(ROOT, "benchmarks", module + ".py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    fields = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef)):
            first = node.body[0]
            if isinstance(first, ast.Expr):
                fields.add(id(first.value))               # a docstring
        elif isinstance(node, ast.Subscript):
            fields.add(id(node.slice))
        elif isinstance(node, ast.Dict):
            fields.update(id(k) for k in node.keys)
    strings = {node.value for node in ast.walk(tree)
               if isinstance(node, ast.Constant)
               and isinstance(node.value, str) and id(node) not in fields}
    assert not strings & set(_kinds())


ECHO = '''"""A kind no file of the benchmark knows: every key in turn."""
import numpy as np
from benchmarks.generator import Batch, Feed, key_names


def make(rng, config, traffic, sizes):
    inp = config["inputs"][0]
    n, rows = sizes["keys"], traffic["batch_rows"]
    cols = lambda: {c: np.full(rows, traffic["echo"], s["dtype"])
                    for c, s in inp["columns"].items()}
    turn = [Batch(0, (np.arange(rows) + i) % n, cols())
            for i in range(1 + traffic["pool_batches"])]
    return Feed([inp["stream"]], [inp["key"]], key_names(config, n),
                turn[:1], turn[1:], rows)
'''


def test_a_kind_dropped_into_a_copy_is_found_with_no_other_edit(tmp_path):
    """As a later PR would: one file under ``feeds/``, one traffic file,
    one entry in ``BENCHMARK.json``; no file that was there is touched."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    here = tmp_path / "benchmarks"
    (here / "feeds/echo.py").write_text(ECHO)
    (here / "traffic/echo7.json").write_text(json.dumps({
        "kind": "echo", "loop": "closed", "batch_rows": 64, "echo": 7,
        "pool_batches": 3, "fill": {"batches": 2},
        "rehearsal": {"batch_rows": 32}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "partition_len1k_10k.echo7", "config": "partition_len1k_10k",
        "traffic": "echo7", "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "from benchmarks import generator, manifest\n"
        "cell = manifest.Cell('partition_len1k_10k.echo7')\n"
        "sizes, traffic = cell.sized(True)\n"
        "feed = generator.make_feed(cell.config, sizes, traffic, 5)\n"
        "b = feed.batch(2)\n"
        "kind = manifest.feed_kind(cell.traffic['kind'])\n"
        "print(kind.__doc__.split(':')[0], len(feed.pool),\n"
        "      feed.fill_batches, b.keys[:3].tolist(), b.cols['symbol'][0],\n"
        "      b.cols['price'][0], manifest.ROOT)\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split() == (
        "A kind no file of the benchmark knows 3 2 [2, 3, 4] S2 7.0 "
        + str(tmp_path)).split()
    assert all(p.read_bytes() == b for p, b in before.items()
               if p.name != "BENCHMARK.json")
    # and a cell whose kind has no file fails as one whose reference has
    # none: when the cell is loaded, before JAX is
    (here / "feeds/echo.py").unlink()
    with pytest.raises(manifest.ManifestError, match="no feed kind 'echo'"):
        manifest.Cell("partition_len1k_10k.echo7", str(tmp_path))


# ------------------------------------------------------------------- zipf

def _zipf_share(n_keys, s, rank=1):
    return rank ** -s / (1.0 / np.arange(1, n_keys + 1) ** s).sum()


def test_zipf_gives_rank_r_to_the_rth_key_of_a_seeded_permutation():
    feed, sizes, traffic = _feed(ZIPF, True, 5)
    n_keys, s = sizes["keys"], traffic["keys"]["s"]
    assert (s, len(feed.pool), feed.fill_batches, feed.rows) == (
        0.99, 6, 16, 256)
    counts = sum(np.bincount(b.keys, minlength=n_keys) for b in feed.pool)
    order = np.argsort(-counts)
    # one leader for the whole run: popularity is fixed, as in YCSB's
    # zipfian workloads, and its share is the distribution's
    assert all(np.bincount(b.keys, minlength=n_keys).argmax() == order[0]
               for b in feed.pool)
    total = counts.sum()
    assert abs(counts[order[0]] / total - _zipf_share(n_keys, s)) < 0.03
    assert abs(counts[order[1]] / total - _zipf_share(n_keys, s, 2)) < 0.03
    # the warm batch: every key once over
    (warm,) = feed.warm
    assert set(warm.keys.tolist()) == set(range(n_keys))
    assert len(warm.keys) == feed.rows


def test_zipf_cycles_its_pool_and_only_the_seed_changes_it():
    feed, _, _ = _feed(ZIPF, True, 5)
    n = len(feed.warm)
    for i in (0, 3, 5):
        assert feed.batch(n + i) is feed.batch(n + 6 + i) is feed.pool[i]
    # fresh timestamps all the same: one per row, i * rows + j
    assert feed.timestamps(n + 6)[0] == (n + 6) * feed.rows
    assert np.array_equal(np.diff(feed.timestamps(3)), np.ones(255))
    assert digest(_feed(ZIPF, True, 5)[0]) == digest(feed)
    other = _feed(ZIPF, True, 6)[0]
    assert digest(other) != digest(feed)
    # another seed scatters the ranks otherwise: another leader
    lead = lambda f: np.bincount(f.pool[0].keys).argmax()
    assert {lead(_feed(ZIPF, True, k)[0]) for k in range(5, 11)} != {
        lead(feed)}


def test_at_real_sizes_zipf_is_the_mix_the_cell_says():
    """The figures of the cell's ``why``, of the traffic file's and of
    PERF.md section 4."""
    feed, sizes, traffic = _feed(ZIPF, False, 24)
    assert (len(feed.pool), feed.fill_batches, feed.rows) == (32, 16, 65_536)
    total = np.zeros(sizes["keys"], np.int64)
    for b in feed.pool:
        counts = np.bincount(b.keys, minlength=sizes["keys"])
        total += counts
        assert 0.090 < counts.max() / feed.rows < 0.106        # 9.8%
        assert 5 <= np.count_nonzero(counts >= sizes["window"]) <= 7
        assert 2_200 < np.count_nonzero(counts == 0) < 2_500
    # after the fill the leading keys' rings have wrapped: about 107 keys,
    # which carry about 52% of the events
    filled = sum(np.bincount(feed.pool[i].keys, minlength=sizes["keys"])
                 for i in range(feed.fill_batches))
    wrapped = filled >= sizes["window"]
    assert 95 <= wrapped.sum() <= 120
    assert 0.50 < total[wrapped].sum() / total.sum() < 0.55


def test_the_rehearsal_laps_a_ring_many_times_inside_a_batch(run_cell):
    """What no other cell does: one key's rows in ONE batch are six times
    its window and more (on the chip 6,400 rows against 1,000), so most of
    them are expired by a later row of the same batch."""
    feed, sizes, _ = _feed(ZIPF, True, 11)
    most = max(np.bincount(b.keys).max() for b in feed.pool)
    assert most >= 6 * sizes["window"]
    rc, last, cap = run_cell(ZIPF, "--trace", "0", "--cpu-rehearsal")
    assert rc == 0 and last["correct"] is True, cap.err[-2000:]
    assert last["compared"]["avg_max_abs_err"]["value"] < 1e-9
    assert last["attempted"] > 6          # the pool went round


# ------------------------------------------------------------- two_stream

JOIN = {
    # upstream's JoinTestCase: two schemas, a key attribute a side, string
    # payloads. No configuration file: the cell is a later PR's.
    "app": (
        "@app:playback\n"
        "define stream cseEventStream (symbol string, price float, "
        "volume int);\n"
        "define stream twitterStream (user string, tweet string, "
        "company string);\n"
        "@info(name = 'join')\n"
        "from cseEventStream#window.time({time_s} sec) "
        "join twitterStream#window.length({length})\n"
        "  on cseEventStream.symbol == twitterStream.company\n"
        "select cseEventStream.symbol as symbol, twitterStream.tweet as "
        "tweet, cseEventStream.price as price\n"
        "insert into outputStream;\n"),
    "inputs": [
        {"stream": "cseEventStream", "key": "symbol", "key_prefix": "S",
         "columns": {
             "price": {"dtype": "float32", "dist": "uniform", "lo": 0.0,
                       "hi": 100.0},
             "volume": {"dtype": "int32", "dist": "integers", "lo": 1,
                        "hi": 1000}}},
        {"stream": "twitterStream", "key": "company", "key_prefix": "S",
         "columns": {
             "user": {"dtype": "str", "prefix": "user", "distinct": 16},
             "tweet": {"dtype": "str", "prefix": "tweet ", "distinct": 200}}}],
    "output": {"stream": "outputStream",
               "columns": {"key": "symbol", "tweet": "tweet",
                           "price": "price"},
               "strings": {"key": "key", "tweet": "tweet"}},
}
JOIN_SIZES = {"keys": 40, "time_s": 10, "length": 8, "batch_rows": 64}
JOIN_TRAFFIC = {
    "kind": "two_stream", "loop": "closed", "batch_rows": 64,
    "keys": {"dist": "hot_set", "hot_share": 0.2, "hot_traffic": 0.8},
    "first_ms": 10_000, "round_ms": 1_400, "pool_batches": 12,
    "fill": {"batches": 0}}


def test_two_stream_has_a_schema_a_key_attribute_and_strings_a_side():
    feed = generator.make_feed(JOIN, JOIN_SIZES, JOIN_TRAFFIC, 7)
    assert feed.streams == ["cseEventStream", "twitterStream"]
    assert feed.key_attrs == ["symbol", "company"]
    assert [b.stream for b in feed.warm] == [0, 1]
    assert [b.stream for b in feed.pool] == [0, 1] * 6
    stock, tweets = feed.pool[0], feed.pool[1]
    assert set(stock.cols) == {"symbol", "price", "volume"}
    assert set(tweets.cols) == {"company", "user", "tweet"}
    assert stock.cols["price"].dtype == np.float32
    assert stock.cols["volume"].dtype == np.int32
    # one key space under two attribute names
    assert stock.cols["symbol"].tolist() == feed.names[stock.keys].tolist()
    assert tweets.cols["company"].tolist() == feed.names[tweets.keys].tolist()
    # a string payload is sent as strings, kept as indices, and its table
    # turns the one into the other
    assert tweets.cols["tweet"].dtype == object
    assert set(feed.tables) == {"key", "user", "tweet"}
    assert len(feed.tables["tweet"]) == 200 and len(feed.tables["user"]) == 16
    for c in ("user", "tweet"):
        assert feed.tables[c][tweets.codes[c]].tolist() == \
            tweets.cols[c].tolist()
    assert feed.tables["tweet"][3] == "tweet 3"
    # one timestamp a batch, half a round apart, whichever side it is
    assert [int(feed.timestamps(i)[0]) for i in range(4)] == [
        10_000, 10_700, 11_400, 12_100]
    assert len(set(feed.timestamps(3).tolist())) == 1
    # the history: by stream, each stream's rows alone, strings as indices
    hist = feed.history(0, 6)
    assert hist["stream"].tolist() == [0] * 64 + [1] * 64 + ([0] * 64
                                                             + [1] * 64) * 2
    assert set(hist["cols"]) == set(feed.streams)
    assert set(hist["cols"]["twitterStream"]) == {"user", "tweet"}
    assert len(hist["cols"]["cseEventStream"]["price"]) == 3 * 64
    assert hist["cols"]["twitterStream"]["tweet"][64:128].tolist() == \
        tweets.codes["tweet"].tolist()
    # a ratio: three batches of the first side to one of the second
    by_ratio = generator.make_feed(
        JOIN, JOIN_SIZES, dict(JOIN_TRAFFIC, ratio=[3, 1]), 7)
    assert [b.stream for b in by_ratio.pool] == [0, 0, 0, 1] * 3
    # a round is then four batches, round_ms from one round to the next
    assert [int(by_ratio.timestamps(i)[0]) for i in (0, 1, 4, 6)] == [
        10_000, 10_350, 11_400, 12_100]
    zipf = generator.make_feed(
        JOIN, JOIN_SIZES, dict(JOIN_TRAFFIC, keys={"dist": "zipf", "s": 1.1}),
        7)
    assert digest(zipf) != digest(feed)
    with pytest.raises(ValueError):
        generator.make_feed(JOIN, JOIN_SIZES,
                            dict(JOIN_TRAFFIC, pool_batches=11), 7)


def test_a_join_of_two_schemas_goes_through_the_sender_and_comes_back():
    """configs[4]'s shape at rehearsal size, through the normal path:
    ``build_app``, ``Sender``, the collector, ``run._delivered`` with the
    tweet decoded through the app's dictionary; held to upstream's rules an
    event at a time (a chunk enters its own window, then every row of it
    probes the OTHER side's window, oldest first; a stock event has left
    ``time(10 sec)`` once it is 10 s old; ``length(8)`` is global)."""
    from benchmarks import run

    feed = generator.make_feed(JOIN, JOIN_SIZES, JOIN_TRAFFIC, 7)
    manager, rt, collector = drive.build_app(JOIN, JOIN_SIZES, 1)
    try:
        sender = drive.Sender(rt, feed)
        n = len(feed.warm) + 2 * len(feed.pool)
        for i in range(n):
            sender.send(i)
        got = run._delivered(JOIN, collector, rt, feed)
    finally:
        manager.shutdown()
    hist = feed.history(0, n)
    price = iter(hist["cols"]["cseEventStream"]["price"].tolist())
    tweet = iter(hist["cols"]["twitterStream"]["tweet"].tolist())
    stock, tweets, want = collections.deque(), collections.deque(maxlen=8), []
    for side, key, ts in zip(hist["stream"].tolist(), hist["key"].tolist(),
                             hist["ts"].tolist()):
        while stock and stock[0][0] + 10_000 <= ts:
            stock.popleft()
        if side == 0:
            stock.append((ts, key, p := next(price)))
            want += [(key, t, p) for k, t in tweets if k == key]
        else:
            tweets.append((key, t := next(tweet)))
            want += [(key, t, p) for _, k, p in stock if k == key]
    assert sender.failed == 0 and len(want) > 10_000
    assert -1 not in got["tweet"] and -1 not in got["key"]
    assert list(zip(got["key"].tolist(), got["tweet"].tolist(),
                    got["price"].tolist())) == want
