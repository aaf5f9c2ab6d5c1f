"""The launch reader (``benchmarks/metrics/_launch.py``): on ``XSpace``
files written field by field (read back through ``ProfileData``, as a real
trace is), on made-up events whose answer is plain, and on a cut of a real
trace: the first sends of ``timebatch_1s_10k.hot20_tick250`` on the v5e
from PR 35's traced chip run, as ``_launch.load`` gives them (times from
the first send), kept beside this file."""

import gzip
import json
import os

import pytest

from benchmarks.metrics import _launch
from benchmarks.tests.test_spans import _msg

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e3      # the trace's clock is in nanoseconds
DATA, TIMER = "jit_siddhi_query_step(11)", "jit_siddhi_query_step(22)"
LEAF = "jit_grow_leaf(33)"


def _plane(number, name, lines):
    """An ``XPlane`` of ``xplane.proto``: ``lines`` = {line name: [(event
    name, start_us, duration_us)]}, each event name a metadata entry."""
    ids = {}
    for events in lines.values():
        for ev_name, _s, _d in events:
            ids.setdefault(ev_name, len(ids) + 1)
    return _msg(
        (1, number), (2, name),
        *[(3, _msg((1, k + 1), (2, line), (3, 0), *[
            (4, _msg((1, ids[n]), (2, int(s * 1e6)), (3, int(d * 1e6))))
            for n, s, d in events]))
          for k, (line, events) in enumerate(lines.items())],
        *[(4, _msg((1, i), (2, _msg((1, i), (2, n)))))
          for n, i in ids.items()])


def _write(tmp_path, *planes):
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(b"".join(_msg((1, p)) for p in planes))
    return str(path)


def _host(sends, spans):
    return _plane(9, "/host:CPU", {"python": (
        [("bench.send_columns", s, d) for s, d in sends] + list(spans))})


def test_a_written_trace_is_read_in_one_pass(tmp_path):
    """Two step programs alternating on one plane, as a tumbling cell's
    TIMER and data steps; a growth's leaf program between a launch and its
    step; spans that are not this reader's; ``XLA Ops`` left unread."""
    device = _plane(1, "/device:TPU:0", {
        "XLA Modules": [(TIMER, 140, 1900), (LEAF, 5_050, 10),
                        (DATA, 5_100, 2400)],
        "XLA Ops": [("%fusion.1 = ...", 140, 1900)]})
    host = _host([(0, 9_000)], [
        ("siddhi.query.step", 90, 200), ("siddhi.launch", 100, 150),
        ("siddhi.meta_pull", 300, 2_000), ("siddhi.key", 4_900, 50),
        ("siddhi.launch", 5_000, 160), ("siddhi.meta_pull", 5_200, 2_450)])
    events = _launch.load(_write(tmp_path, device, host))
    assert events["host"] == [["bench.send_columns", 0.0, 9_000 * US]]
    assert sorted(n for n, _s, _d in events["spans"]) == [
        "siddhi.key", "siddhi.launch", "siddhi.launch",
        "siddhi.meta_pull", "siddhi.meta_pull"]
    assert [m[0] for m in events["modules"]["/device:TPU:0"]] == [
        TIMER, DATA]
    got = _launch.attribute(events)
    assert got["launches"] == 2
    # 140 - 100 and 5,100 - 5,000 (not the leaf program's 5,050)
    assert got["launch_to_start_s"] == pytest.approx(70e-6)
    # (300 + 2,000) - (140 + 1,900) and (5,200 + 2,450) - (5,100 + 2,400)
    assert got["done_to_meta_s"] == pytest.approx(205e-6)


def _events(launches, modules, pulls, sends=((0.0, 100_000.0),)):
    return {"host": [["bench.send_columns", s * US, d * US]
                     for s, d in sends],
            "spans": [["siddhi.launch", s * US, d * US] for s, d in launches]
            + [["siddhi.meta_pull", s * US, d * US] for s, d in pulls],
            "modules": {plane: [[DATA, s * US, d * US] for s, d in ms]
                        for plane, ms in modules.items()}}


def test_four_planes_give_the_mean_start_and_the_last_end():
    starts = (1_300, 1_100, 1_200, 1_400)       # mean 1,250
    got = _launch.attribute(_events(
        [(1_000, 150)],
        {f"/device:TPU:{n}": [(s, 80_000 + 10 * n)]
         for n, s in enumerate(starts)},
        [(1_200, 80_500)]))
    assert got["launches"] == 1
    assert got["launch_to_start_s"] == pytest.approx(250e-6)
    # the last plane to end: 1,400 + 80,030; the pull closes at 81,700
    assert got["done_to_meta_s"] == pytest.approx(270e-6)
    assert _launch.pairs(_events(
        [(1_000, 150)], {"/device:TPU:0": [(1_300, 80_000)],
                         "/device:TPU:1": []}, [(1_200, 80_500)]))[0][1] \
        == 1_300 * US              # a plane that ran nothing is no plane


def test_a_launch_is_paired_by_time_inside_the_window():
    """Left out: a launch before the window; one whose module ends after
    it; one whose module begins only after the next launch opened (a
    second piece dispatched behind the first); one plane of two without
    the module. A launch with no meta pull closing after its module
    counts for the launch gap alone."""
    sends = ((10_000.0, 30_000.0), (40_000.0, 30_000.0))   # to 70,000
    launches = [(9_000, 100), (11_000, 100), (41_000, 100), (42_000, 100),
                (60_000, 100)]
    one = {"/device:TPU:0": [(9_050, 500), (11_200, 5_000),
                             (42_300, 5_000), (60_100, 20_000)]}
    pulls = [(11_150, 5_250), (42_150, 5_450)]      # close 16,400, 47,600
    got = _launch.pairs(_events(launches, one, pulls, sends))
    assert [[t / US, s / US, e / US, c and c / US]
            for t, s, e, c in got] == [
        [11_000, 11_200, 16_200, 16_400], [42_000, 42_300, 47_300, 47_600]]
    two = dict(one, **{"/device:TPU:1": [(11_400, 5_000)]})
    got = _launch.attribute(_events(launches, two, pulls[:1], sends))
    assert got == {"launch_to_start_s": pytest.approx(300e-6),
                   "done_to_meta_s": pytest.approx(0.0), "launches": 1}
    got = _launch.attribute(_events(launches, one, [], sends))
    assert got["launches"] == 2 and got["done_to_meta_s"] is None
    assert got["launch_to_start_s"] == pytest.approx(250e-6)


@pytest.mark.parametrize("lacking", ["host", "spans", "modules"])
def test_nothing_to_read_gives_nothing(lacking):
    """The parent of PR 35 opens no ``siddhi.launch``; a CPU trace has no
    device plane: the readers return None and the line leaves them out."""
    events = _events([(1_000, 100)], {"/device:TPU:0": [(1_100, 500)]},
                     [(1_050, 700)])
    assert _launch.attribute(events)["launches"] == 1
    events[lacking] = type(events[lacking])()
    assert _launch.attribute(events) is None


def test_the_readers_return_none_without_a_trace(monkeypatch, tmp_path):
    from benchmarks import manifest
    from benchmarks.metrics import _spans

    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    here = os.path.join(manifest.ROOT, "benchmarks", "metrics")
    for name in ("launch_to_start_ms", "done_to_meta_ms"):
        reader = manifest._module(os.path.join(here, name + ".py"))
        assert reader.read({"journeys": []}) is None
    ctx = {"journeys": [
        {"key_ms": 0.5, "launch_ms": 1.0, "h2d_bytes": 100},
        {"key_ms": 1.5, "launch_ms": 2.0, "h2d_bytes": 300},
        {"key_ms": None, "launch_ms": None, "h2d_bytes": None}]}
    got = {name: manifest._module(os.path.join(here, name + ".py")).read(ctx)
           for name in ("key_ms_per_batch", "launch_ms_per_batch",
                        "h2d_bytes_per_batch")}
    assert got == {"key_ms_per_batch": 1.0, "launch_ms_per_batch": 1.5,
                   "h2d_bytes_per_batch": 200}
    assert manifest._module(os.path.join(
        here, "key_ms_per_batch.py")).read({"journeys": [{}]}) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_v5e_launch_cut.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_the_recorded_trace_reads_as_it_was_read_by_hand(recorded):
    """By hand (PERF.md section 5, cell 5): nine sends, two of which
    close a window and so launch twice: the TIMER step's program first
    (1.92 ms on the device for a launch of 272 B), then the data step's
    (2.38 ms for 2,162,696 B). Every launch, whatever it carries, is
    followed by its program 1.49-1.65 ms later, and the meta is on the
    host 1.35-1.49 ms after the program has ended."""
    got = _launch.pairs(recorded)
    assert len(got) == 11 == sum(
        n == "siddhi.launch" for n, _s, _d in recorded["spans"])
    (modules,) = recorded["modules"].values()
    by_start = {s: (name, d) for name, s, d in modules}
    timer, _data = sorted({m[0] for m in modules},       # the rarer first
                          key=lambda n: sum(m[0] == n for m in modules))
    programs = [by_start[start][0] for _t, start, _e, _c in got]
    assert [p == timer for p in programs] == [
        False, True, False, False, False, False, True, False, False,
        False, False]
    for t, start, end, close in got:
        name, dur = by_start[start]
        assert end == start + dur
        assert dur / 1e6 == pytest.approx(1.92 if name == timer else 2.38,
                                          abs=0.01)
        assert 1.48 < (start - t) / 1e6 < 1.66
        assert 1.35 < (close - end) / 1e6 < 1.49
    att = _launch.attribute(recorded)
    assert att["launches"] == 11
    assert att["launch_to_start_s"] * 1e3 == pytest.approx(1.570, abs=1e-3)
    assert att["done_to_meta_s"] * 1e3 == pytest.approx(1.413, abs=1e-3)
