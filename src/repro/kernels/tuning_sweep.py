"""Pallas TPU kernel: component-level SWAPPER tuning sweep.

Computes, over the full (a, b) operand grid, per-a row statistics of the two
error surfaces E0(a,b) = |m(a,b) - ab| and E1(a,b) = |m(b,a) - ab| and of the
pointwise oracle min(E0, E1):

    lo/hi  — exact 16-bit limb sums of the absolute error (int32)
    mx     — row maximum (WCE, uint32)
    cnt    — nonzero count (EP)
    sq     — float32 sum of squared error (MSE)
    rel    — float32 sum of relative error (ARE)

Column statistics are *not* computed: E1 is the transpose of E0, so the
per-b column stats equal the other surface's row stats (DESIGN.md §4 rank-1
reduction).  Every one of the paper's 4M swap configurations and all five
error metrics are then scored from these vectors by the host driver — the
whole tuning phase is O(2^(2M)) work instead of the paper's O(4M * 2^(2M))
circuit stimulations.

Grid: (N/T, N/T) with the b-tile dimension innermost; the (T,) row-stat
output blocks are indexed by the a-tile only and are revisited across the
inner dimension with init-at-j==0 accumulation (the standard Pallas reduction
pattern).  Validated in interpret mode against ``ref.py``.

Every in-kernel statistic is int32 or float32: Mosaic has no reduction over
unsigned integers and no uint32 -> float32 cast.  The uint32 error splits
into 16-bit limbs (exact in int32 and float32), and the row maximum runs in
an order-preserving int32 image of the uint32 value, mapped back on output.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ax_matmul import default_interpret

from repro.core.metrics import abs_err
from repro.core.multipliers import AxMult

__all__ = ["tuning_sweep_pallas", "STAT_NAMES", "SURF_NAMES"]

STAT_NAMES = ("lo", "hi", "mx", "cnt", "sq", "rel")
SURF_NAMES = ("r0", "r1", "orc")


_SIGN = 0x80000000


def _u32_limbs(x):
    """(hi, lo) 16-bit limbs of a uint32 array as int32."""
    return ((x >> jnp.uint32(16)).astype(jnp.int32),
            (x & jnp.uint32(0xFFFF)).astype(jnp.int32))


def _u32_to_f32(x):
    """uint32 -> float32, rounded once (both limbs are exact in float32)."""
    hi, lo = _u32_limbs(x)
    return hi.astype(jnp.float32) * 65536.0 + lo.astype(jnp.float32)


def _row_stats_tuple(e, exact_abs_f):
    """(T, 1) row statistics of a (T, T) uint32 error tile."""
    hi_e, lo_e = _u32_limbs(e)
    kw = dict(axis=1, keepdims=True)
    lo = jnp.sum(lo_e, dtype=jnp.int32, **kw)
    hi = jnp.sum(hi_e, dtype=jnp.int32, **kw)
    # flipping the sign bit maps uint32 order onto int32 order
    mx = jnp.max(jax.lax.bitcast_convert_type(e ^ jnp.uint32(_SIGN), jnp.int32),
                 **kw)
    cnt = jnp.sum((e != 0).astype(jnp.int32), dtype=jnp.int32, **kw)
    ef = _u32_to_f32(e)
    sq = jnp.sum(ef * ef, dtype=jnp.float32, **kw)
    rel = jnp.sum(ef / jnp.maximum(exact_abs_f, 1.0), dtype=jnp.float32, **kw)
    return lo, hi, mx, cnt, sq, rel


def _sweep_kernel(a_ref, b_ref, *out_refs, mult: AxMult):
    j = pl.program_id(1)

    A = a_ref[...].astype(jnp.int32)          # (T, 1) column
    B = b_ref[...].astype(jnp.int32)          # (1, T) row
    p0 = mult.fn(A, B)
    p1 = mult.fn(B, A)
    exact = mult.exact_product(A, B)
    e0 = abs_err(p0, exact, mult.signed)
    e1 = abs_err(p1, exact, mult.signed)
    emin = jnp.where(e0 <= e1, e0, e1)      # Mosaic has no unsigned min
    if mult.signed:
        exact_abs = jnp.abs(exact.astype(jnp.float32))
    else:
        exact_abs = _u32_to_f32(exact)

    stats = (
        _row_stats_tuple(e0, exact_abs)
        + _row_stats_tuple(e1, exact_abs)
        + _row_stats_tuple(emin, exact_abs)
    )

    @pl.when(j == 0)
    def _init():
        for idx, ref in enumerate(out_refs):
            if STAT_NAMES[idx % 6] == "mx":   # the int32 image of uint32 0
                ref[...] = jnp.full(ref.shape, -_SIGN, ref.dtype)
            else:
                ref[...] = jnp.zeros_like(ref)

    for idx, (ref, val) in enumerate(zip(out_refs, stats)):
        if STAT_NAMES[idx % 6] == "mx":
            ref[...] = jnp.maximum(ref[...], val)
        else:
            ref[...] += val


def tuning_sweep_pallas(mult: AxMult, vals: jax.Array, tile: int = 128,
                        interpret: Optional[bool] = None):
    """Full-grid sweep over ``vals x vals``.  Returns
    ``{surf: {stat: (N,) array}}`` for surf in (r0, r1, orc)."""
    n = vals.shape[0]
    tile = min(tile, n)
    assert n % tile == 0
    grid = (n // tile, n // tile)

    dtypes = dict(lo=jnp.int32, hi=jnp.int32, mx=jnp.int32,
                  cnt=jnp.int32, sq=jnp.float32, rel=jnp.float32)
    # 2-D blocks whose unit dim spans the whole array: a 1-D block of a
    # longer vector gets an XLA tiling Mosaic refuses
    out_shape = [
        jax.ShapeDtypeStruct((n, 1), dtypes[s]) for _ in SURF_NAMES for s in STAT_NAMES
    ]
    out_specs = [
        pl.BlockSpec((tile, 1), lambda i, j: (i, 0)) for _ in range(len(out_shape))
    ]

    kernel = functools.partial(_sweep_kernel, mult=mult)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile), lambda i, j: (0, j)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(vals.reshape(n, 1), vals.reshape(1, n))

    it = iter(o.reshape(n) for o in outs)
    res = {surf: {s: next(it) for s in STAT_NAMES} for surf in SURF_NAMES}
    for st in res.values():
        st["mx"] = (jax.lax.bitcast_convert_type(st["mx"], jnp.uint32)
                    ^ jnp.uint32(_SIGN))
    return res
