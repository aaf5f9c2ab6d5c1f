"""The trace reducer on a cut of a real trace: the first three sends of
``partition_len1k_10k.hot20_bulk`` on the v5e (PR 24's first chip run;
operation names shortened as ``tracereduce.load`` shortens them), kept
beside this file, and on made-up events whose answer is plain."""

import gzip
import json
import os

import pytest

from benchmarks import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "trace_v5e_partition_cut.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_what_was_read_by_hand(recorded):
    """By hand: three ``bench.send_columns`` spans from 43.917 ms to
    369.952 ms; three whole ``jit_step`` programs of 91.59 ms each on
    ``/device:TPU:0`` inside them; the callback takes 6-9 ms of each."""
    r = tracereduce.reduce(recorded)
    assert r["device_planes"] == 1 and r["sends"] == 3
    assert r["window_s"] == pytest.approx(0.326035332, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.274757063, rel=1e-6)
    assert r["busy_s"] / r["sends"] == pytest.approx(0.09159, rel=1e-3)
    assert r["programs"][0][0].startswith("jit_step(")
    assert r["programs"][0][1] == pytest.approx(r["busy_s"], rel=1e-3)
    # the breakdown: the program first, then the operations inside it
    assert r["device_ops"][0][0].startswith("jit_step(")
    assert r["device_ops"][1][0].startswith("%fusion.")
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    gaps = dict(r["idle_gaps"][:3])
    idle = r["window_s"] - r["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # the device waits on the engine's host work and on the callback,
    # hardly ever on the generator
    assert gaps["total.between_sends"] < 0.01 * idle
    assert gaps["total.in_callback"] > 0.3 * idle
    assert all(seconds >= 1e-6 for _, seconds in r["idle_gaps"][3:])


def test_the_readers_take_their_numbers_from_the_reduction(recorded):
    from benchmarks import manifest, peaks

    cell = manifest.Cell("partition_len1k_10k.hot20_bulk")
    readers = {e["name"]: r for e, r in cell.per_layer()}
    nbytes = cell.family.bytes_per_batch(cell.config, cell.config["sizes"],
                                         65536)
    ctx = {"trace": tracereduce.reduce(recorded), "chips": 1,
           "bytes_per_batch": nbytes, "peaks": peaks.of("TPU v5 lite")}
    assert readers["step_device_ms"].read(ctx) == pytest.approx(91.59, 1e-3)
    assert readers["device_idle_pct"].read(ctx) == pytest.approx(
        100 * (1 - 0.274757063 / 0.326035332), rel=1e-6)
    # 5,460,736 B at 819 GB/s is 6.67 us; over 91.59 ms: 0.0073%
    assert readers["step_hbm_roofline"].read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / 0.09159, rel=1e-3)
    # nothing to read, nothing returned: never a 0
    empty = dict(ctx, trace=None)
    for name in ("step_device_ms", "device_idle_pct", "step_hbm_roofline"):
        assert readers[name].read(empty) is None
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")


def _events(devices, host):
    return {"devices": {p: {"XLA Ops": ops} for p, ops in devices.items()},
            "host": host}


def test_busy_is_a_union_and_is_averaged_over_chips():
    host = [["bench.send_columns", 0.0, 1e9], ["bench.callback", 8e8, 1e8]]
    ops0 = [["a", 1e8, 2e8], ["b", 2e8, 2e8], ["c", 9e8, 5e8]]  # c clipped
    ops1 = [["a", 0.0, 1e9]]
    r = tracereduce.reduce(_events({"/device:TPU:0": ops0,
                                    "/device:TPU:1": ops1}, host))
    assert r["window_s"] == 1.0
    assert r["busy_s"] == pytest.approx((0.3 + 0.1 + 1.0) / 2)
    gaps = dict(r["idle_gaps"])
    assert gaps["total.in_callback"] == pytest.approx(0.1 / 2)
    assert gaps["total.in_send_columns"] == pytest.approx(0.5 / 2)
    assert gaps["total.between_sends"] == pytest.approx(0.0)


def test_nothing_to_read_gives_nothing():
    host = [["bench.send_columns", 0.0, 1e9]]
    assert tracereduce.reduce(_events({}, host)) is None          # CPU run
    assert tracereduce.reduce(_events({"/device:TPU:0": []}, host)) is None
    assert tracereduce.reduce(
        _events({"/device:TPU:0": [["a", 0.0, 1.0]]}, [])) is None


def test_a_cpu_trace_is_read_and_has_no_device_plane(tmp_path):
    """The reader itself (``jax.profiler.ProfileData``) on a trace made
    here: the benchmark's spans are found, no device plane is."""
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracereduce.SEND):
        jnp.ones(8).sum().block_until_ready()
        with jax.profiler.TraceAnnotation(tracereduce.CALLBACK):
            pass
    jax.profiler.stop_trace()
    events = tracereduce.load(tracereduce.find_xplane(str(tmp_path)))
    assert sorted(e[0] for e in events["host"]) == [
        tracereduce.CALLBACK, tracereduce.SEND]
    assert events["devices"] == {}
    assert tracereduce.reduce(events) is None
