"""The 4-chip cell's path at a tiny size on 4 virtual CPU devices, in a
subprocess: correct as it is, not correct with the exchange left out."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mesh_run(*extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, "-m", "bench.tests.mesh_run",
                        *extra], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_mesh_cell(fault):
    out = mesh_run(*(["--fault", fault] if fault else []))
    assert out["device"]["count"] == 4
    bad = sum(out["checks"].values())
    if fault is None:
        assert bad == 0, out["checks"]
    else:
        assert bad > 0, out["checks"]
