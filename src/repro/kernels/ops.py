"""Public kernel API: schedule-resolving wrappers around the Pallas kernels.

This module is the supported kernel API surface (``repro.kernels``).  Every
wrapper resolves a :class:`~repro.kernels.schedule.KernelSchedule` host-side
*before* entering jit — explicit ``schedule=`` argument first, then the
process-installed autotuner table (``schedule.install_table``), then the
historical defaults — and pins it (plus multiplier and flags) into the jit
key, so one schedule == one compiled program while everything the adaptive
runtime changes at run time (operands, per-tile swap-config grids) enters
as ordinary traced arrays.  Because resolution happens at trace time,
installing a new schedule table never invalidates already-compiled
programs: entries take effect only on signatures traced afterwards (the
zero-retrace adoption contract).

Deprecated surface: the pre-schedule ``block_m=``/``block_n=``/``block_k=``
/``k_slab=`` kwargs still work through a one-release shim that maps them to
a schedule and emits a ``DeprecationWarning`` — pass ``schedule=`` instead.

``ref.py`` holds the bit-exact host oracles every wrapper is tested
against.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.multipliers import AxMult
from repro.core.swapper import SwapConfig
from repro.core.tuning import (
    ComponentResult,
    accs_from_row_stats,
    operand_values,
    result_from_accs,
)

from . import schedule as SCHED
from .ax_matmul import ax_matmul_grid_pallas, ax_matmul_pallas, default_interpret
from .schedule import KernelSchedule
from .tuning_sweep import tuning_sweep_pallas

__all__ = ["ax_matmul", "ax_matmul_dequant", "ax_matmul_grid",
           "component_sweep_pallas", "KernelSchedule"]

_LEGACY_MSG = (
    "kernels.%s: block_m/block_n/block_k/k_slab kwargs are deprecated; "
    "pass schedule=KernelSchedule(...) (or install an autotuned table). "
    "The kwarg shim will be removed next release.")


def _sched_for(fname: str, op: str, a, b, mult: AxMult,
               schedule: Optional[KernelSchedule],
               block_m, block_n, block_k, k_slab) -> KernelSchedule:
    """Shared resolution order: explicit schedule > deprecated kwargs >
    installed table > defaults (see module docstring)."""
    if schedule is not None:
        assert block_m is None and block_n is None and block_k is None \
            and k_slab is None, "pass either schedule= or legacy kwargs, not both"
        return SCHED.resolve_for(a.shape, b.shape, "kernel", mult.name, op,
                                 override=schedule)
    if any(v is not None for v in (block_m, block_n, block_k, k_slab)):
        warnings.warn(_LEGACY_MSG % fname, DeprecationWarning, stacklevel=3)
        return SCHED.from_legacy_kwargs("kernel", block_m, block_n, block_k,
                                        k_slab)
    return SCHED.resolve_for(a.shape, b.shape, "kernel", mult.name, op)


# ---------------------------------------------------------------------------
# ax_matmul
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("mult", "swap", "schedule", "tile_hist", "interpret"))
def _ax_matmul_jit(a, b, mult: AxMult, swap, schedule: KernelSchedule,
                   tile_hist: bool, interpret: bool):
    return ax_matmul_pallas(
        a, b, mult, swap,
        block_m=schedule.bm, block_n=schedule.bn, block_k=schedule.bk,
        k_slab=schedule.k_slab, grid_order=schedule.grid_order,
        tile_hist=tile_hist, interpret=interpret,
    )


def ax_matmul(
    a: jax.Array,
    b: jax.Array,
    mult: AxMult,
    swap: Optional[SwapConfig] = None,
    *,
    schedule: Optional[KernelSchedule] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    k_slab: Optional[int] = None,
    tile_hist: bool = False,
    interpret: Optional[bool] = None,
):
    """int8 x int8 -> int32 approximate matmul with fused SWAPPER.

    ``(M, K) @ (K, N) -> (M, N)`` where every scalar product goes through
    ``mult`` with the single-bit ``swap`` decision applied ahead of it.
    The dispatch configuration comes from ``schedule`` (explicit >
    installed autotuner table > defaults); the ``block_*``/``k_slab``
    kwargs are the deprecated pre-schedule surface.

    ``tile_hist=True`` returns ``(out, hist)`` where ``hist`` is the
    (M/bm, N/bn, 2, bits+1) int32 tile-local bit-occupancy histogram
    accumulated inside the K reduction (bit-exact vs ``ref.tile_hist_ref``;
    see ``runtime/telemetry.py`` for how the adaptive controller consumes
    the per-tile statistic)."""
    sched = _sched_for("ax_matmul", "matmul", a, b, mult, schedule,
                       block_m, block_n, block_k, k_slab)
    return _ax_matmul_jit(a, b, mult, swap, sched, tile_hist,
                          default_interpret(interpret))


ax_matmul._cache_size = _ax_matmul_jit._cache_size


# ---------------------------------------------------------------------------
# ax_matmul_dequant
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("mult", "swap", "schedule", "interpret", "out_dtype"))
def _ax_matmul_dequant_jit(a, b, scale_a, scale_b, mult: AxMult, swap,
                           schedule: KernelSchedule, interpret: bool,
                           out_dtype):
    acc = ax_matmul_pallas(
        a, b, mult, swap,
        block_m=schedule.bm, block_n=schedule.bn, block_k=schedule.bk,
        k_slab=schedule.k_slab, grid_order=schedule.grid_order,
        interpret=interpret,
    )
    return (acc.astype(jnp.float32) * scale_a * scale_b).astype(out_dtype)


def ax_matmul_dequant(
    a: jax.Array,               # (M, K) int8
    b: jax.Array,               # (K, N) int8
    scale_a: jax.Array,         # (M, 1) f32 per-row
    scale_b: jax.Array,         # (1, N) f32 per-col
    mult: AxMult,
    swap: Optional[SwapConfig] = None,
    *,
    schedule: Optional[KernelSchedule] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Quantized approximate matmul with dequantization epilogue."""
    sched = _sched_for("ax_matmul_dequant", "matmul", a, b, mult, schedule,
                       block_m, block_n, block_k, None)
    return _ax_matmul_dequant_jit(a, b, scale_a, scale_b, mult, swap, sched,
                                  default_interpret(interpret), out_dtype)


ax_matmul_dequant._cache_size = _ax_matmul_dequant_jit._cache_size


# ---------------------------------------------------------------------------
# ax_matmul_grid
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("mult", "schedule", "tile_hist", "interpret"))
def _ax_matmul_grid_jit(a, b, mult: AxMult, cfg_grid,
                        schedule: KernelSchedule, tile_hist: bool,
                        interpret: bool):
    return ax_matmul_grid_pallas(
        a, b, mult, cfg_grid,
        block_m=schedule.bm, block_n=schedule.bn, block_k=schedule.bk,
        k_slab=schedule.k_slab, grid_order=schedule.grid_order,
        tile_hist=tile_hist, interpret=interpret,
    )


def ax_matmul_grid(
    a: jax.Array,                 # (M, K) int8
    b: jax.Array,                 # (K, N) int8
    mult: AxMult,
    cfg_grid: jax.Array,          # (M/bm, N/bn, 3) int32 swap triples
    *,
    schedule: Optional[KernelSchedule] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    k_slab: Optional[int] = None,
    tile_hist: bool = False,
    interpret: Optional[bool] = None,
):
    """Approximate matmul with a per-output-tile SWAPPER config grid.

    ``cfg_grid[ti, tj]`` is the (op_is_a, bit, value) triple applied to
    output tile (ti, tj); ``value == 2`` encodes NoSwap.  The grid is a
    *traced* operand (scalar prefetch, SMEM-resident before the body runs),
    so the adaptive runtime re-tunes tile configs — down to a different
    triple per row tile — without triggering a recompile.  The dispatch
    configuration is a :class:`KernelSchedule` resolved exactly like
    :func:`ax_matmul` (signature op "matmul_grid").

    ``tile_hist=True`` returns ``(out, hist)`` with the same per-tile
    bit-occupancy histogram as :func:`ax_matmul`: one dispatch both applies
    the current per-tile policy and emits the per-tile operand statistics
    the controller uses to compute the next one (the closed per-tile loop)."""
    sched = _sched_for("ax_matmul_grid", "matmul_grid", a, b, mult, schedule,
                       block_m, block_n, block_k, k_slab)
    return _ax_matmul_grid_jit(a, b, mult, cfg_grid, sched, tile_hist,
                               default_interpret(interpret))


ax_matmul_grid._cache_size = _ax_matmul_grid_jit._cache_size


def component_sweep_pallas(
    mult: AxMult,
    tile: int = 128,
    sample_bits: Optional[int] = None,
    seed: int = 0,
    interpret: Optional[bool] = None,
) -> ComponentResult:
    """Component-level tuning driven by the Pallas sweep kernel — a drop-in
    replacement for ``repro.core.tuning.component_sweep`` (cross-checked in
    tests/test_kernels.py)."""
    vals = operand_values(mult.bits, mult.signed, sample_bits, seed)
    stats = jax.device_get(
        tuning_sweep_pallas(mult, jnp.asarray(vals), tile=tile, interpret=interpret)
    )
    r0, r1, orc = accs_from_row_stats(vals, stats)
    return result_from_accs(mult, vals, r0, r1, orc)
