"""Pallas TPU kernel: int8 approximate matmul with fused SWAPPER swapping.

``C[m, n] = sum_k axmul(A[m, k], B[k, n])`` where ``axmul`` is a closed-form
approximate-multiplier family from ``repro.core.multipliers`` and the SWAPPER
single-bit decision is fused *ahead of* each scalar multiply as a pair of
vector selects (the TPU-idiomatic form of the paper's ``xchg``; DESIGN.md §4).

TPU adaptation notes
--------------------
* The MXU computes exact products, so an approximate-multiplier inner product
  is a **VPU** workload: int8 loads -> int32 lanes, shifts/masks/mul/select,
  int32 accumulation.  Block shapes are chosen so the (bm, bn) accumulator,
  the (bm, bk) / (bk, bn) operand tiles and the (bm, bn) broadcast temporary
  fit VMEM with MXU-aligned (multiple-of-128) lane dims.
* The K reduction runs as the innermost grid dimension with output-block
  revisiting (init at k==0, accumulate after), the standard Pallas matmul
  reduction pattern.  Within a tile the reduction is slab-blocked: K is
  processed in (bm, k_slab, bn) slabs with one select/multiply/reduce per
  slab instead of ``bk`` rank-1 steps, unrolled at trace time so every
  slice offset is static (see ``_accumulate_tile``).
* The LUT path (arbitrary 8-bit circuits, EvoApprox compatibility) keeps the
  64 Ki-entry table resident in VMEM (256 KiB as int32) and gathers per
  element; on real TPUs a VMEM gather lowers slowly, so the closed-form path
  is the production path (see DESIGN.md).

The kernels are tested bit-exact against ``ref.py`` in interpret mode on
the CPU, and compiled for a described v5e chip in tests/test_tpu_compile.py.
``interpret=None`` (the default) interprets only where the default backend
is the CPU (``default_interpret``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.multipliers import AxMult
from repro.core.swapper import SwapConfig, swap_mask_dyn

__all__ = ["ax_matmul_pallas", "ax_matmul_grid_pallas", "HIST_WIDTH",
           "default_interpret"]


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in the interpreter: an explicit flag wins,
    otherwise only where the default backend is the CPU (no Mosaic there).
    On a TPU the kernels always compile."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def _swap_select(a, b, swap: Optional[SwapConfig]):
    """Branch-free SWAPPER front-end on int32 lanes (broadcasts ok)."""
    if swap is None:
        return a, b
    src = a if swap.operand == "A" else b
    sel = ((src >> swap.bit) & 1) == swap.value
    aa = jnp.where(sel, b, a)
    bb = jnp.where(sel, a, b)
    return aa, bb


DEFAULT_K_SLAB = 8   # sublanes per reduction slab (one VPU register of int32)


def HIST_WIDTH(bits: int) -> int:
    """Columns of a tile histogram row: one count per magnitude-bit position
    plus a trailing negative-sign count — the same layout as the streaming
    telemetry's ``bit_probs`` statistic (``runtime.telemetry._bit_counts``)."""
    return bits + 1


def _hist_row(blk_i32, bits: int):
    """(bits+1,) int32 occupancy counts of one operand block: per-position
    set **magnitude** bits, then the negative count (raw two's-complement
    bits are a poor drift statistic for signed operands — see telemetry)."""
    shifts = jnp.arange(bits, dtype=jnp.int32)
    mag = jnp.abs(blk_i32)
    cnt = jnp.sum((mag[:, :, None] >> shifts) & 1, axis=(0, 1), dtype=jnp.int32)
    neg = jnp.sum((blk_i32 < 0).astype(jnp.int32), dtype=jnp.int32)
    return jnp.concatenate([cnt, neg[None]])


def _pick_k_slab(bk: int, k_slab: Optional[int]) -> int:
    """Largest divisor of ``bk`` that is <= ``k_slab`` (None = default)."""
    from repro.core.tiling import largest_divisor_leq

    return largest_divisor_leq(bk, DEFAULT_K_SLAB if k_slab is None else k_slab)


def _accumulate_tile(a_ref, b_ref, o_ref, select, mult: AxMult, bk: int,
                     k_slab: Optional[int] = None, hist_ref=None):
    """Shared (bm, bn) output-tile accumulation (K innermost, output-block
    revisiting): ``select(a, b)`` applies the SWAPPER front-end — static
    config for ``_ax_matmul_kernel``, scalar-prefetched triple for the grid
    kernel.

    ``hist_ref`` — optional (1, 1, 2, bits+1) int32 output block: tile-local
    bit-occupancy histograms, accumulated here at the existing per-tile
    reduction point (the operand blocks are already VMEM-resident for the
    reduction, so the counts cost a handful of extra VPU reductions and no
    additional loads).  Row 0 counts the A tile (bm x K elements over the
    whole reduction), row 1 the B tile (K x bn); the layout matches the
    telemetry drift statistic (magnitude-bit counts + sign count).  This is
    what lets the adaptive controller see *within-matmul* operand structure
    and populate per-row-tile swap grids from live traffic.

    The K reduction is slab-blocked sublane vectorization: instead of ``bk``
    rank-1 VPU steps (one (bm, 1) x (1, bn) broadcast multiply per k), each
    step materializes a (bm, ks, bn) slab — ks lanes of A against ks rows of
    B — and performs ONE select/multiply/reduce over the slab, keeping the
    slab temporary VMEM-resident (bm * ks * bn * 4 B = 512 KiB at the
    default 128/8/128).  The ``bk // ks`` slab steps are unrolled in Python
    so every slice offset is static: Mosaic lowers no ``dynamic_slice`` of a
    loaded value, and a dynamic lane offset that is not a multiple of 128
    is refused.  ``k_slab=1`` reproduces the rank-1 schedule."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        if hist_ref is not None:
            hist_ref[...] = jnp.zeros_like(hist_ref)

    a_blk = a_ref[...].astype(jnp.int32)          # (bm, bk)
    b_blk = b_ref[...].astype(jnp.int32)          # (bk, bn)
    if hist_ref is not None:
        bits = mult.bits
        hist_ref[0, 0, 0, :] += _hist_row(a_blk, bits)
        hist_ref[0, 0, 1, :] += _hist_row(b_blk, bits)
    ks = _pick_k_slab(bk, k_slab)

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for s in range(bk // ks):
        # (bm, ks, bn) slab: ks consecutive rank-1 products, one dispatch
        a_slab = a_blk[:, s * ks:(s + 1) * ks]                            # (bm, ks)
        b_slab = b_blk[s * ks:(s + 1) * ks, :]                            # (ks, bn)
        aa, bb = select(a_slab[:, :, None], b_slab[None, :, :])
        prod = mult.fn(aa, bb).astype(jnp.int32)                          # (bm, ks, bn)
        acc = acc + jnp.sum(prod, axis=1, dtype=jnp.int32)
    o_ref[...] += acc


def _ax_matmul_kernel(a_ref, b_ref, o_ref, *rest, mult: AxMult, swap, bk: int,
                      k_slab: Optional[int] = None):
    """One (bm, bn) output tile; grid = (M/bm, N/bn, K/bk), K innermost.
    With ``tile_hist`` the histogram block arrives as a second output ref."""
    _accumulate_tile(a_ref, b_ref, o_ref,
                     lambda a, b: _swap_select(a, b, swap), mult, bk,
                     k_slab=k_slab, hist_ref=rest[0] if rest else None)


def _grid_layout(gm: int, gn: int, gk: int, grid_order: str):
    """(grid, a_map, b_map, o_map, hist_map, tile_ids) for the requested
    iteration order of the two *parallel* grid dimensions.  The K reduction
    stays innermost in both orders (output-block revisiting requires it),
    so "mn" vs "nm" changes only the revisit order of output tiles — the
    per-tile accumulation is identical and outputs are bit-exact
    (tests/test_autotune.py).  ``tile_ids()`` maps program ids back to the
    logical (tile_i, tile_j) pair for kernels that index per-tile state
    (the scalar-prefetched config grid)."""
    assert grid_order in ("mn", "nm"), grid_order
    if grid_order == "mn":
        return ((gm, gn, gk),
                lambda i, j, k: (i, k), lambda i, j, k: (k, j),
                lambda i, j, k: (i, j), lambda i, j, k: (i, j, 0, 0),
                lambda: (pl.program_id(0), pl.program_id(1)))
    return ((gn, gm, gk),
            lambda j, i, k: (i, k), lambda j, i, k: (k, j),
            lambda j, i, k: (i, j), lambda j, i, k: (i, j, 0, 0),
            lambda: (pl.program_id(1), pl.program_id(0)))


def ax_matmul_pallas(
    a: jax.Array,                 # (M, K) int8
    b: jax.Array,                 # (K, N) int8
    mult: AxMult,
    swap: Optional[SwapConfig] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    k_slab: Optional[int] = None,
    grid_order: str = "mn",
    tile_hist: bool = False,
    interpret: Optional[bool] = None,
):
    """Blocked approximate matmul; returns int32 (M, N).  ``k_slab`` sets
    the sublane depth of the vectorized K reduction (None = auto; 1 = the
    legacy rank-1 schedule, kept for benchmarking); ``grid_order`` the
    iteration order of the two parallel grid dims ("mn"/"nm" — bit-exact
    either way, a pure locality knob for the autotuner).

    ``tile_hist=True`` additionally returns a (M/bm, N/bn, 2, bits+1) int32
    tile-local bit-occupancy histogram (per output tile: magnitude-bit +
    sign counts of the A and B operand tiles), accumulated inside the K
    reduction — the kernel-side feed of the per-tile adaptive loop."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape, (bm, bn, bk))
    gm, gn = M // bm, N // bn
    grid, a_map, b_map, o_map, h_map, _ = _grid_layout(gm, gn, K // bk,
                                                       grid_order)

    kernel = functools.partial(_ax_matmul_kernel, mult=mult, swap=swap, bk=bk,
                               k_slab=k_slab)
    out_shape = jax.ShapeDtypeStruct((M, N), jnp.int32)
    out_specs = pl.BlockSpec((bm, bn), o_map)
    if tile_hist:
        hw = HIST_WIDTH(mult.bits)
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((gm, gn, 2, hw), jnp.int32)]
        out_specs = [out_specs, pl.BlockSpec((1, 1, 2, hw), h_map)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(a, b)


# ---------------------------------------------------------------------------
# granular (per-tile) swap-config grids — the adaptive-runtime kernel
# ---------------------------------------------------------------------------

def _ax_matmul_grid_kernel(cfg_ref, a_ref, b_ref, o_ref, *rest, mult: AxMult,
                           bk: int, k_slab: Optional[int] = None,
                           tile_ids=None):
    """Like ``_ax_matmul_kernel`` but the swap decision comes from a
    scalar-prefetched (grid_m, grid_n, 3) int32 triple grid indexed by the
    output-tile coordinates: op_is_a / bit / value are runtime values, so the
    policy (down to per-row-tile granularity) changes without recompiling.
    ``tile_ids`` maps program ids to logical (tile_i, tile_j) under the
    schedule's grid order (see ``_grid_layout``)."""
    i, j = (pl.program_id(0), pl.program_id(1)) if tile_ids is None else tile_ids()
    op_is_a = cfg_ref[i, j, 0]
    bit = cfg_ref[i, j, 1]
    value = cfg_ref[i, j, 2]

    def select(a, b):
        # core.swapper owns the triple semantics; pure jnp, fine in-kernel
        sel = swap_mask_dyn(a, b, op_is_a, bit, value)    # slab broadcast
        return jnp.where(sel, b, a), jnp.where(sel, a, b)

    _accumulate_tile(a_ref, b_ref, o_ref, select, mult, bk, k_slab=k_slab,
                     hist_ref=rest[0] if rest else None)


def ax_matmul_grid_pallas(
    a: jax.Array,                 # (M, K) int8
    b: jax.Array,                 # (K, N) int8
    mult: AxMult,
    cfg_grid: jax.Array,          # (M/bm, N/bn, 3) int32 (op_is_a, bit, value)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    k_slab: Optional[int] = None,
    grid_order: str = "mn",
    tile_hist: bool = False,
    interpret: Optional[bool] = None,
):
    """Blocked approximate matmul with a per-output-tile swap-config grid
    (scalar prefetch: the grid is resident in SMEM before the body runs).

    ``tile_hist=True`` additionally returns the (M/bm, N/bn, 2, bits+1)
    int32 tile-local bit-occupancy histogram (see :func:`ax_matmul_pallas`)
    — the same compiled program both *applies* the per-tile policy and
    *observes* the per-tile operand distribution that drives its next
    re-tune, which is the whole per-tile adaptive loop in one dispatch."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape, (bm, bn, bk))
    gm, gn = M // bm, N // bn
    assert cfg_grid.shape == (gm, gn, 3), (cfg_grid.shape, (gm, gn))
    grid, a_map, b_map, o_map, h_map, tile_ids = _grid_layout(
        gm, gn, K // bk, grid_order)

    # scalar-prefetch index maps receive the prefetched cfg ref as a
    # trailing argument the block mapping ignores
    def _drop_cfg(m):
        return lambda *args: m(*args[:3])

    kernel = functools.partial(_ax_matmul_grid_kernel, mult=mult, bk=bk,
                               k_slab=k_slab, tile_ids=tile_ids)
    out_shape = jax.ShapeDtypeStruct((M, N), jnp.int32)
    out_specs = pl.BlockSpec((bm, bn), _drop_cfg(o_map))
    if tile_hist:
        hw = HIST_WIDTH(mult.bits)
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((gm, gn, 2, hw), jnp.int32)]
        out_specs = [out_specs, pl.BlockSpec((1, 1, 2, hw), _drop_cfg(h_map))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), _drop_cfg(a_map)),
            pl.BlockSpec((bk, bn), _drop_cfg(b_map)),
        ],
        out_specs=out_specs,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(cfg_grid.astype(jnp.int32), a, b)
