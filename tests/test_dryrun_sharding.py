"""Distribution-layer tests.

In-process tests use a small host-device mesh via a subprocess (jax locks the
device count at first init, so the 8-device cases run in a child python).
Sharding-rule unit tests run in-process.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import repro.configs as CFG
from repro.configs.base import ParallelConfig
from repro.launch.sharding import param_spec, spec_for, axis_rules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _fake_mesh(shape, axes):
    """Abstract mesh for rule tests (no devices needed).  The AbstractMesh
    constructor signature changed across jax releases: older takes
    (shape, axis_names), newer takes a ((name, size), ...) tuple."""
    from jax.sharding import AbstractMesh

    try:
        return AbstractMesh(tuple(zip(axes, shape)))
    except TypeError:
        return AbstractMesh(shape, axes)


def test_param_spec_rules():
    mesh = _fake_mesh((4, 2), ("data", "model"))
    par = ParallelConfig(fsdp=True)
    # 2-D weight sharding: embed over data, ff over model
    assert param_spec(("embed", "ff"), mesh, par, (64, 32)) == P("data", "model")
    # non-divisible dims are dropped to None
    assert param_spec(("embed", "ff"), mesh, par, (63, 32)) == P(None, "model")
    # duplicate mesh axes: first wins
    assert param_spec(("experts", "embed", "ff"), mesh, par, (8, 64, 32)) == P(
        "model", "data", None
    )
    # fsdp off -> embed replicated
    par2 = ParallelConfig(fsdp=False)
    assert param_spec(("embed", "ff"), mesh, par2, (64, 32)) == P(None, "model")


def test_batch_axes_multi_pod():
    mesh3 = _fake_mesh((2, 4, 2), ("pod", "data", "model"))
    rules = axis_rules(mesh3, ParallelConfig())
    assert rules["batch"] == ("pod", "data")
    mesh2 = _fake_mesh((4, 2), ("data", "model"))
    rules2 = axis_rules(mesh2, ParallelConfig())
    assert rules2["batch"] == "data"


_SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import json
import jax
from repro.configs.base import ParallelConfig
from repro.launch import dryrun
from repro.launch.mesh import make_mesh

mesh = make_mesh({mesh_shape}, {mesh_axes})
par = ParallelConfig()
row = dryrun.run_cell("{arch}", "{shape}", False, par, verbose=False,
                      extrapolate=False, mesh=mesh)
print("RESULT:" + json.dumps({{k: row[k] for k in ("status", "arch", "shape")}}))
"""


def _run_sub(arch, shape, ndev=8, mesh_shape=(4, 2), mesh_axes=("data", "model")):
    code = _SUBPROC_SCRIPT.format(ndev=ndev, arch=arch, shape=shape,
                                  mesh_shape=mesh_shape, mesh_axes=mesh_axes)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(out.stdout[-2000:])


@pytest.mark.parametrize("arch,shape", [
    ("whisper-base", "train_4k"),
    ("mamba2-370m", "decode_32k"),
])
def test_dryrun_cell_small_mesh(arch, shape):
    """A full-config cell lowers+compiles on an 8-device host mesh (the
    production-mesh run is exercised by launch/dryrun.py --all)."""
    r = _run_sub(arch, shape)
    assert r["status"] == "ok", r


def test_dryrun_multipod_axes_small():
    """The 'pod' axis shards: (2,2,2) pod/data/model mesh compiles."""
    r = _run_sub("granite-moe-1b-a400m", "train_4k", ndev=8,
                 mesh_shape=(2, 2, 2), mesh_axes=("pod", "data", "model"))
    assert r["status"] == "ok", r


def test_long_context_skip_policy():
    from repro.launch.dryrun import skip_reason

    assert skip_reason("qwen2-72b", "long_500k") is not None
    assert skip_reason("gemma3-27b", "long_500k") is None
    assert skip_reason("mamba2-370m", "long_500k") is None
    assert skip_reason("qwen2-72b", "train_4k") is None
