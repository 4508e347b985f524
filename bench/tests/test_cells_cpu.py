"""Every cell end to end at the reduced size on the CPU, through the same
functions ``run.py`` calls, and ``run.py`` itself refusing the CPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from harness import cell, spec
from repro.configs import reduced

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs(name):
    sp = spec.resolve(name)
    out = cell.run(name, 2 ** 33 + 7, 4.0, False, time.perf_counter(),
                   shrink=reduced, allow_cpu=True)
    assert set(out["metrics"]) <= {m["name"] for m in sp["end_to_end"]}
    assert "setup_s" in out["metrics"]
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["served_tokens_checked"]["value"] > 0
    for k in ("requests_miscounted", "tokens_outside_vocab",
              "compiles_in_window", "retraces_in_window"):
        assert out["checks"][k]["value"] == 0, k
    assert list(out)[-1] == "checks"


def test_traced_run_reads_per_layer_metrics():
    name = CELLS[0]
    out = cell.run(name, 5, 2.0, True, time.perf_counter(), shrink=reduced,
                   allow_cpu=True)
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] >= 2.0
    listed = {e["name"] for e, _ in spec.resolve(name)["per_layer"]}
    assert set(out["metrics"]) <= listed
    assert "device_idle.batch" in out["metrics"]     # peaks (mfu) need a TPU
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_run_py_refuses_the_cpu():
    r = subprocess.run(
        [sys.executable, str(spec.ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
