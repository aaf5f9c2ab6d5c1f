"""The span reader (``benchmarks/metrics/_spans.py``) on a cut of a real
trace: the first four sends (A, B, A, B) of ``pattern_ab_10k.rounds_bulk``
on the v5e from PR 25's first traced chip run, as ``_spans.load`` gives
them (operation names shortened, times from the first send), kept beside
this file; and on made-up events whose answer is plain."""

import gzip
import json
import os

import pytest

from benchmarks import tracereduce
from benchmarks.metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6      # the trace's clock is in nanoseconds


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_v5e_pattern_spans_cut.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_the_exposed_parts_sum_to_the_reducers_idle(recorded):
    """Every idle instant goes to one layer or to ``other``: the six sum
    to the idle that ``tracereduce.reduce`` reads off the same events."""
    r = tracereduce.reduce(recorded)
    got = _spans.attribute(recorded)
    assert got["sends"] == r["sends"] and got["window_s"] == r["window_s"]
    idle = r["window_s"] - r["busy_s"]
    assert sum(got["idle_s"].values()) == pytest.approx(idle, rel=1e-9)
    # the reducer's own coarse split agrees: what it puts inside the
    # callback is the engine's emit + pull; under no engine span are the
    # generator between sends and the instants of a send between spans
    coarse = dict(r["idle_gaps"][:3])
    assert got["idle_s"]["emit"] + got["idle_s"]["pull"] == pytest.approx(
        coarse["total.in_callback"], rel=0.02)
    assert coarse["total.between_sends"] < got["idle_s"]["other"] \
        < 0.5e-3 * got["sends"]


def test_the_recorded_trace_reads_as_it_was_read_by_hand(recorded):
    """By hand (PERF.md section 5): sends of 12.4, 21.9, 15.1 and 21.9
    ms; an A send runs ``jit_siddhi_nfa_step_AStream`` (7.82 ms), arms
    and pulls nothing; a B send runs ``..._BStream`` (0.97 ms) and its
    callback pulls the padded match columns for 14.2-14.5 ms, nearly all
    of it with the device idle."""
    got = _spans.attribute(recorded)
    assert got["sends"] == 4
    ms = {k: v * 1e3 for k, v in got["idle_s"].items()}
    assert ms == pytest.approx({"pack": 3.916, "dispatch": 10.748,
                                "meta_pull": 5.173, "emit": 4.024,
                                "pull": 28.664, "other": 1.274}, abs=1e-3)
    pulls = [[d / 1e6, b] for n, _s, d, b in recorded["spans"]
             if n == "siddhi.pull"]
    assert [round(d, 1) for d, _ in pulls] == [14.5, 14.2]
    assert ms["pull"] > 0.99 * sum(d for d, _ in pulls)
    # the two step programs are told apart by name, and each send is one
    # batch: the spans of a send share the id given at pack
    programs = dict(tracereduce.reduce(recorded)["programs"])
    step_a, step_b = sorted(programs)
    assert step_a.startswith("jit_siddhi_nfa_step_AStream(")
    assert step_b.startswith("jit_siddhi_nfa_step_BStream(")
    assert programs[step_a] / 2 == pytest.approx(7.82e-3, rel=1e-2)
    assert programs[step_b] / 2 == pytest.approx(0.97e-3, rel=1e-2)
    by_batch = {}
    for name, _s, _d, batch in recorded["spans"]:
        by_batch.setdefault(batch, set()).add(name)
    packed = sorted(b for b, names in by_batch.items()
                    if "siddhi.pack" in names)
    assert packed == [75, 76, 77, 78]
    assert all({"siddhi.query.step", "siddhi.meta_pull"} <= by_batch[b]
               for b in packed)
    assert [b for _d, b in pulls] == [76, 78]       # the B batches


def test_the_recorded_steps_time_goes_to_the_scopes_it_was_traced_in(
        recorded):
    """The four steps of the cut are busy for 17.6 ms; 16.0 of them in
    operations whose ``tf_op`` names ``siddhi.state`` (the NFA stage's
    scatters and gathers), 0.04 in ``siddhi.meta``, none in
    ``siddhi.select`` (this pattern's selector is not on the device);
    the rest names no scope (copies, parameters' own ops)."""
    got = _spans.attribute(recorded)
    busy = tracereduce.reduce(recorded)["busy_s"]
    assert busy == pytest.approx(17.58e-3, rel=1e-3)
    assert got["scope_s"] == pytest.approx(
        {"state": 16.015e-3, "select": 0.0, "meta": 0.039e-3}, abs=1e-6)
    assert 0.9 * busy < sum(got["scope_s"].values()) <= busy
    # every scoped operation is one of the reducer's operations
    starts = {int(s) for _n, s, _d in
              recorded["devices"]["/device:TPU:0"]["XLA Ops"]}
    assert all(int(s) in starts
               for _k, s, _d in recorded["scoped"]["/device:TPU:0"])


def _varint(x):
    out = bytearray()
    while x > 0x7F:
        out.append(x & 0x7F | 0x80)
        x >>= 7
    return bytes(out + bytes([x]))


def _msg(*fields):
    """A protobuf message from (number, int | bytes | str) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_scopes_are_read_from_the_event_metadata_of_the_raw_file(tmp_path):
    """An ``XSpace`` written field by field, as ``xplane.proto`` numbers
    them: a device plane whose event metadata names the scope in a
    ``tf_op`` stat, by value and by reference; an operation without one;
    a line that is not ``XLA Ops``; a host plane."""
    stat_names = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
                  _msg((1, 8), (2, _msg((1, 8), (2, "flops")))),
                  _msg((1, 9), (2, _msg(
                      (1, 9), (2, "jit(siddhi_query_step)/siddhi.select/"
                                  "reduce_sum:"))))]
    by_value = _msg((1, 1), (2, "%fusion.1 = ..."), (5, _msg((1, 8), (3, 4))),
                    (5, _msg((1, 7), (5, "jit(siddhi_nfa_step_A)/"
                                         "siddhi.state/scatter:"))))
    by_ref = _msg((1, 2), (2, "%fusion.2 = ..."), (5, _msg((1, 7), (7, 9))))
    unscoped = _msg((1, 3), (2, "%copy.3 = ..."),
                    (5, _msg((1, 7), (5, "state['sel']['a0']:"))))
    events = [_msg((1, 1), (2, 5_000_000), (3, 2_000_000)),
              _msg((1, 3), (2, 7_000_000), (3, 1_000_000)),
              _msg((1, 2), (2, 8_000_000), (3, 500_000))]
    device = _msg(
        (1, 1), (2, "/device:TPU:0"),
        (3, _msg((1, 1), (2, "XLA Modules"), (3, 1000),
                 (4, _msg((1, 1), (2, 0), (3, 9_000_000))))),
        (3, _msg((1, 2), (2, "XLA Ops"), (3, 1000),
                 *[(4, e) for e in events])),
        *[(4, _msg((1, k), (2, m))) for k, m in
          ((1, by_value), (2, by_ref), (3, unscoped))],
        *[(5, s) for s in stat_names])
    host = _msg((1, 2), (2, "/host:CPU"),
                (3, _msg((1, 1), (2, "XLA Ops"), (3, 0),
                         (4, _msg((1, 1), (2, 0), (3, 1))))),
                (4, _msg((1, 1), (2, by_value))), (5, stat_names[0]))
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host), (4, "a-hostname")))
    assert _spans.scoped_ops(str(path)) == {"/device:TPU:0": [
        ["state", 1000 + 5000.0, 2000.0], ["select", 1000 + 8000.0, 500.0]]}
    # the scopes' seconds: inside the window, over the planes that have any
    spans = [["siddhi.pack", 0.0, 1000.0, 1]]
    got = _spans.attribute({
        "devices": {"/device:TPU:0": {"XLA Ops": [["a", 6000.0, 3500.0]]}},
        "host": [["bench.send_columns", 0.0, 9200.0]], "spans": spans,
        "scoped": _spans.scoped_ops(str(path))})
    assert got["scope_s"] == pytest.approx(
        {"state": 2000e-9, "select": 200e-9, "meta": 0.0})


def _events(ops, spans, sends=((0.0, 100 * MS),)):
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops}},
            "host": [["bench.send_columns", s, e - s] for s, e in sends],
            "spans": spans}


def test_an_idle_gap_is_split_among_the_innermost_spans():
    """One send of 100 ms; the device runs from 10 to 40 ms. The host:
    pack 0-8; the input junction 8-50 holding a step 10-14; then the
    drain: meta pull 50-60, emit 60-95 holding the output junction 62-94
    and in it the pull 70-90. The junction inside the emit is emit."""
    ops = [["%fusion.1", 10 * MS, 30 * MS]]
    spans = [["siddhi.pack", 0, 8 * MS, 1],
             ["siddhi.junction.dispatch", 8 * MS, 42 * MS, 1],
             ["siddhi.query.step", 10 * MS, 4 * MS, 1],
             ["siddhi.meta_pull", 50 * MS, 10 * MS, 1],
             ["siddhi.emit", 60 * MS, 35 * MS, 1],
             ["siddhi.junction.dispatch", 62 * MS, 32 * MS, None],
             ["siddhi.pull", 70 * MS, 20 * MS, 1]]
    got = _spans.attribute(_events(ops, spans))
    want = {"pack": 8, "dispatch": 2 + 10, "meta_pull": 10,
            "emit": 10 + 5, "pull": 20, "other": 5}
    assert {k: v * 1e3 for k, v in got["idle_s"].items()} == \
        pytest.approx(want)
    assert sum(want.values()) == 100 - 30
    assert got["sends"] == 1


def test_idle_is_averaged_over_chips_and_clipped_to_the_window():
    spans = [["siddhi.pack", 0.0, 50 * MS, 1]]
    events = _events([["a", 0.0, 20 * MS]], spans)
    events["devices"]["/device:TPU:1"] = {
        "XLA Ops": [["b", 90 * MS, 30 * MS]]}          # clipped at 100
    got = _spans.attribute(events)
    assert got["idle_s"]["pack"] == pytest.approx((0.030 + 0.050) / 2)
    assert got["idle_s"]["other"] == pytest.approx((0.050 + 0.040) / 2)


def test_nothing_to_read_gives_nothing(monkeypatch, tmp_path):
    ops = [["a", 0.0, 1 * MS]]
    # a program without the spans (the parent of PR 25), a CPU trace
    assert _spans.attribute(_events(ops, [])) is None
    # the spans without the scopes: no scoped time, the idle all the same
    unscoped = _spans.attribute(_events(ops, [["siddhi.pack", 0, 1, 1]]))
    assert unscoped["scope_s"] is None and unscoped["idle_s"]["other"] > 0
    assert _spans.attribute({"devices": {}, "spans": [["siddhi.pack", 0, 1, 1]],
                             "host": [["bench.send_columns", 0, 9]]}) is None
    # no trace directory at all
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    assert _spans.of_run() is None and _spans.exposed_ms("pull") is None
    assert _spans.scoped_ms("state") is None


def test_the_new_readers_are_found_by_name_and_read_the_journeys():
    from benchmarks import manifest

    cell = manifest.Cell("pattern_ab_10k.rounds_bulk")
    readers = {e["name"]: r for e, r in cell.per_layer()}
    assert {"exposed_pack_ms", "exposed_dispatch_ms", "exposed_meta_pull_ms",
            "exposed_emit_ms", "exposed_pull_ms", "pull_ms_per_batch",
            "pull_useful_pct", "step_state_ms",
            "step_select_ms"} <= set(readers)
    ring = [{"pull_ms": None, "rows_out": 0, "rows_padded": 0},
            {"pull_ms": 18.0, "rows_out": 15_800, "rows_padded": 524_288},
            {"pull_ms": 16.0, "rows_out": 15_600, "rows_padded": 524_288}]
    ctx = {"journeys": ring}
    assert readers["pull_ms_per_batch"].read(ctx) == pytest.approx(17.0)
    assert readers["pull_useful_pct"].read(ctx) == pytest.approx(
        100 * 31_400 / 1_048_576)
    # the parent's ring has neither key: nothing returned, never a 0
    old = {"journeys": [{"pack_ms": 1.0, "emit_ms": 2.0}]}
    assert readers["pull_ms_per_batch"].read(old) is None
    assert readers["pull_useful_pct"].read(old) is None


def test_a_cpu_trace_holds_the_engines_spans_and_no_device_plane(tmp_path):
    """The reader itself (``jax.profiler.ProfileData``) on a trace made
    here through the engine's own primitive: the spans are found with
    their batch id; with no device plane there is nothing to attribute."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu.observability import journey, tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    journey.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.SEND):
            with tracing.span("pack", batch=7):
                jnp.ones(8).sum().block_until_ready()
            with tracing.span("persist", app="not a layer's span"):
                pass
    finally:
        jax.profiler.stop_trace()
        journey.disable()
    events = _spans.load(tracereduce.find_xplane(str(tmp_path)))
    assert [(n, b) for n, _s, _d, b in events["spans"]] == [
        ("siddhi.pack", 7)]
    assert [e[0] for e in events["host"]] == [tracereduce.SEND]
    assert events["devices"] == {} and events["scoped"] == {}
    assert _spans.attribute(events) is None
    assert tracereduce.reduce(events) is None
