"""Compile-only checks of the main-path kernels for a TPU v5e chip that is
described, not attached (no chip time): the Pallas kernels at a qwen2-72b
MLP projection shape with ``interpret=False``, the tuning sweep, and the
``mxu`` adaptive projection the serving path runs.  A pass says the TPU
compiler accepts the program; it says nothing about results or speed.

The topology is described inside a module fixture (only the worker that
runs these tests loads the TPU compiler), and the persistent compilation
cache is off around the compiles: their entries could not be read back
without a chip."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core as C
import repro.kernels as K
from repro.configs.base import AxPolicy
from repro.kernels.tuning_sweep import tuning_sweep_pallas
from repro.quant.ax import ax_dense_dyn

# qwen2-72b MLP in-projection: 128 token rows, d_model 8192 -> d_ff 29568
M, D, F = 128, 8192, 29568
MULT = "mul8s_trunc0_4"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tile_hist", [False, True])
def test_ax_matmul_compiles(one_chip, no_persistent_cache, tile_hist):
    mult = C.get(MULT)
    a, b = _sds((M, D), jnp.int8, one_chip), _sds((D, F), jnp.int8, one_chip)
    c = _compile(lambda a, b: K.ax_matmul(a, b, mult, C.SwapConfig("A", 3, 0),
                                          tile_hist=tile_hist,
                                          interpret=False), a, b)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tile_hist", [False, True])
def test_ax_matmul_grid_compiles(one_chip, no_persistent_cache, tile_hist):
    mult = C.get(MULT)
    a, b = _sds((M, D), jnp.int8, one_chip), _sds((D, F), jnp.int8, one_chip)
    grid = _sds((M // 128, F // 128, 3), jnp.int32, one_chip)
    c = _compile(lambda a, b, g: K.ax_matmul_grid(a, b, mult, g,
                                                  tile_hist=tile_hist,
                                                  interpret=False), a, b, grid)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("name", ["mul8u_trunc0_4", "mul8s_drum3_4"])
def test_tuning_sweep_compiles(one_chip, no_persistent_cache, name):
    mult = C.get(name)
    vals = _sds((256,), jnp.int32, one_chip)
    c = _compile(lambda v: tuning_sweep_pallas(mult, v, tile=128,
                                               interpret=False), vals)
    assert "tpu_custom_call" in c.as_text()


def test_ax_dense_dyn_mxu_compiles(one_chip, no_persistent_cache):
    """The decode-step projection of the serving path (8 slots, bf16)."""
    pol = AxPolicy(mult_name=MULT, backend="mxu")
    x = _sds((8, D), jnp.bfloat16, one_chip)
    w = _sds((D, F), jnp.bfloat16, one_chip)
    dyn = _sds((3,), jnp.int32, one_chip)
    c = _compile(lambda x, w, d: ax_dense_dyn(x, w, pol, d), x, w, dyn)
    assert c.memory_analysis().output_size_in_bytes == 8 * F * 2
