"""The runner end to end at rehearsal sizes: the contract's last line,
the refusal without a chip, and that cells, configurations, traffic and
metrics are found by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import CELLS, ROOT


def _copy_benchmark(to):
    """``BENCHMARK.json`` and ``benchmarks/`` alone, as a later PR or the
    driver's bare directory holds them."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), to / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), to / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_contract_line(run_cell, workload, trace):
    rc, last, cap = run_cell(workload, "--trace", trace, "--cpu-rehearsal")
    assert rc == 0
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    # a rehearsal says so, and never under a TPU's name
    assert last["device"]["platform"] == "cpu"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    if trace == "0":
        assert set(last["metrics"]) == {
            m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
        assert {"events_per_s", "setup_s"} < set(last["metrics"])
        assert all(m["value"] > 0 for m in last["metrics"].values())
    else:
        # no device plane in a CPU trace: the trace's readers return
        # nothing and the harness leaves those metrics out
        assert {"compile_s", "compiles_in_window"} <= set(last["metrics"])
        assert "step_hbm_roofline" not in last["metrics"]
        assert "device_idle_pct" not in last["metrics"]
    # every number compared stands beside its limit, on stderr too
    for name, pair in last["compared"].items():
        assert pair["value"] <= pair["limit"]
        assert f"compared {name}:" in cap.err


def test_refuses_to_measure_without_a_tpu(run_cell):
    rc, last, cap = run_cell(CELLS[0], "--trace", "0")
    assert rc != 0
    assert last is None and '"metrics"' not in cap.out
    assert "needs 1 tpu device" in cap.err


def test_unknown_workload_is_an_error():
    from benchmarks import manifest

    with pytest.raises(manifest.ManifestError):
        manifest.Cell("no_such.cell")


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later PR adds files and entries, and edits no file that is
    there: a copy of the benchmark gets a new configuration, a new
    traffic mix, a new per-layer metric and a cell of them, and runs."""
    _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    here = tmp_path / "benchmarks"
    config = json.loads((here / "configs/groupby_len1k_10k.json").read_text())
    config["name"] = "groupby_len2k_5k"
    config["sizes"] = {"keys": 5000, "window": 2000}
    config["rehearsal"] = {"keys": 24, "window": 16}
    (here / "configs/groupby_len2k_5k.json").write_text(json.dumps(config))
    traffic = json.loads((here / "traffic/uniform_bulk.json").read_text())
    traffic["keys"] = {"dist": "zipf", "s": 1.1}
    (here / "traffic/zipf_bulk.json").write_text(json.dumps(traffic))
    (here / "metrics/rows_per_send.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "groupby_len2k_5k", "source": "a test",
        "file": "benchmarks/configs/groupby_len2k_5k.json",
        "reduced": [], "why": "a test"})
    bench["workloads"].append({
        "name": "groupby_len2k_5k.zipf_bulk", "config": "groupby_len2k_5k",
        "traffic": "zipf_bulk", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "rows_per_send", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "ingest + pack",
        "moves": "events_per_s",
        "workloads": ["groupby_len2k_5k.zipf_bulk"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "groupby_len2k_5k.zipf_bulk", "--seed", "5", "--seconds", "0.5",
         "--trace", "1", "--cpu-rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["rows_per_send"] == {"value": 7.0, "unit": "rows"}
    # and an old cell does not report the new cell's metric
    unchanged = {p for p, b in before.items()
                 if p.name != "BENCHMARK.json" and p.read_bytes() != b}
    assert not unchanged


def test_a_four_chip_cell_is_data_too(tmp_path):
    """``chips`` is data: a cell that says 4 gets the configuration's
    query routed over a mesh (four virtual CPU devices here), with no new
    code. PERF.md's Open questions #1 is such a cell."""
    _copy_benchmark(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "partition_len1k_10k.uniform_bulk_x4",
        "config": "partition_len1k_10k", "traffic": "uniform_bulk",
        "chips": 4, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "partition_len1k_10k.uniform_bulk_x4", "--seed", "5", "--seconds",
         "0.5", "--trace", "0", "--cpu-rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    facts = json.loads(done.stdout.splitlines()[-2])
    assert "query.bench.routed_step" in facts["engine_jit"]


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    """``BENCHMARK.json`` and ``benchmarks/`` alone, with no program
    beside them: another exit code than 0, and no result line."""
    _copy_benchmark(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "5", "--seconds", "0.5", "--trace", "0",
         "--cpu-rehearsal"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
