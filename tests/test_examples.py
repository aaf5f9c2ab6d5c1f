"""Every example under examples/ must run clean (user-facing quick start)."""

import os
import pathlib
import subprocess
import sys

import pytest

EXAMPLES = sorted(
    (pathlib.Path(__file__).parent.parent / "examples").glob("*.py"))


@pytest.mark.parametrize("path", EXAMPLES, ids=lambda p: p.name)
def test_example_runs(path):
    env = dict(os.environ)
    root = str(path.parent.parent)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    # examples are correctness smoke tests: a chip belongs to one
    # process, so the children are forced onto the CPU platform
    wrapper = (
        "import sys; "
        "from siddhi_tpu.parallel.mesh import force_host_devices; "
        "force_host_devices(1); "
        "import runpy; runpy.run_path(sys.argv[1], run_name='__main__')")
    r = subprocess.run(
        [sys.executable, "-c", wrapper, str(path)], capture_output=True,
        text=True, timeout=240, cwd=root, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
