"""SWAPPER approximate matmul as a first-class LM projection (DESIGN.md §5).

Three backends:

* ``mxu`` — **beyond-paper production path**.  For *separable* multiplier
  families, m(a, b) = f(a) * g(b) elementwise (operand truncation zeroes low
  bits of each operand; partial-product perforation zeroes rows of B), so the
  approximate inner product factorizes into exact matmuls of transformed int8
  operands — which run on the MXU.  The two-limb factorization

      NoSwap:        C = f(A) @ g(B)
      swap on A bit: C = (s⊙g(A)) @ f(B) + ((1-s)⊙f(A)) @ g(B)
      swap on B bit: C = g(A) @ (s⊙f(B)) + f(A) @ ((1-s)⊙g(B))

  (s = the SWAPPER bit mask of the decision operand) is dispatched as a
  **single K-stacked int8 matmul** over a concatenated 2K inner dimension,
  ``[X1|X2] @ [Y1;Y2]`` — int32 accumulation makes the stacked reduction
  bit-identical to ``X1@Y1 + X2@Y2`` while halving the dispatch count and
  doubling MXU occupancy per call.  The pre-stacking 2-matmul forms are kept
  (``ax_matmul_int_2mm`` / ``ax_matmul_int_dyn_2mm``) as bit-identity oracles
  and benchmark baselines.  This turns the paper's per-multiply mechanism
  into MXU-rate compute instead of a VPU elementwise pipeline — bit-identical
  to the Pallas kernel (tested).

* ``kernel`` — the Pallas ``ax_matmul`` VPU kernel (arbitrary families,
  including LUT circuits).

* ``emul`` — pure-jnp reference (small shapes / tests).

Training uses a straight-through estimator: forward = approximate quantized
matmul, backward = exact matmul gradients.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AxPolicy
from repro.core import multipliers as M
from repro.core.swapper import SwapConfig, apply_swapper_dyn
from repro.core.tiling import (largest_divisor_leq, rowtile_count,
                               rowtile_index, rowtile_span)
from repro.kernels.schedule import KernelSchedule, resolve_for

__all__ = [
    "ax_dense",
    "ax_dense_dyn",
    "ax_dense_prepared",
    "prepare_params",
    "prepared_projections",
    "take_layer",
    "quantize_rows",
    "separable_transforms",
    "ax_matmul_int",
    "ax_matmul_int_dyn",
    "ax_matmul_int_dyn_hist",
    "ax_matmul_int_2mm",
    "ax_matmul_int_dyn_2mm",
]


# ---------------------------------------------------------------------------
# separable closed forms
# ---------------------------------------------------------------------------

def _identity(x):
    return x


def _trunc_t(k):
    if k == 0:
        return _identity
    mask = jnp.int32(~((1 << k) - 1))

    def f(x):  # sign-magnitude low-bit truncation (matches multipliers.trunc)
        neg = x < 0
        mag = jnp.where(neg, -x, x) & mask
        return jnp.where(neg, -mag, mag)

    return f


def separable_transforms(mult_name: str) -> Optional[Tuple[Callable, Callable]]:
    """(f, g) with m(a,b) = f(a)*g(b), or None if the family is inseparable."""
    base = mult_name.split("_", 1)[1] if "_" in mult_name else mult_name
    if base.startswith("trunc"):
        ka, kb = (int(v) for v in base[len("trunc"):].split("_"))
        return _trunc_t(ka), _trunc_t(kb)
    if base.startswith("perf"):
        rows = tuple(int(v) for v in base[len("perf"):].split("_"))
        rowmask = 0
        for r in rows:
            rowmask |= 1 << r
        inv = jnp.int32(~rowmask)

        def g(x):
            neg = x < 0
            mag = jnp.where(neg, -x, x) & inv
            return jnp.where(neg, -mag, mag)

        return _identity, g
    return None


# ---------------------------------------------------------------------------
# int8 quantization
# ---------------------------------------------------------------------------

def quantize_rows(x, axis=-1):
    """Symmetric per-row int8 quantization along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _swap_mask(x_i32, cfg: SwapConfig):
    return (((x_i32 >> cfg.bit) & 1) == cfg.value)


def _int_mm(a, b):
    """Exact int8 matmul with int32 accumulation (MXU-native on TPU)."""
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


def _stacked_mm(*limbs):
    """``sum_i Xi @ Yi`` as ONE int8 matmul over a concatenated inner
    dimension: ``[X1|X2|...] @ [Y1;Y2;...]`` (``limbs`` alternates Xi, Yi).
    int32 accumulation is exact, so the stacked reduction is bit-identical
    to the matmul sum while collapsing the dispatch count to one (one MXU
    pass over 2K for the scalar swap factorization, 4K for the per-row-tile
    form)."""
    x = jnp.concatenate(limbs[0::2], axis=-1)
    y = jnp.concatenate(limbs[1::2], axis=0)
    return _int_mm(x, y)


def _mxu_limbs(ai, bi, f, g, swap: SwapConfig):
    """The (X1, Y1, X2, Y2) int8 limbs of the static swap factorization."""
    if swap.operand == "A":
        s = _swap_mask(ai, swap).astype(jnp.int32)
        return ((s * g(ai)).astype(jnp.int8), f(bi).astype(jnp.int8),
                ((1 - s) * f(ai)).astype(jnp.int8), g(bi).astype(jnp.int8))
    s = _swap_mask(bi, swap).astype(jnp.int32)
    return (g(ai).astype(jnp.int8), (s * f(bi)).astype(jnp.int8),
            f(ai).astype(jnp.int8), ((1 - s) * g(bi)).astype(jnp.int8))


def _mxu_limbs_dyn(ai, bi, f, g, op_is_a, bit, value):
    """The (X1, Y1, X2, Y2) limbs with the swap decision as traced scalars.

    With row mask sa (decision on A) / column mask sb (decision on B), each
    gated by op_is_a, ``X1 @ Y1 + X2 @ Y2`` equals the A-form or B-form
    static factorization for every triple.

    The ``value == 2`` NoSwap limb-zeroing encoding: no int8 operand has a
    bit equal to 2, so ``((x >> bit) & 1) == 2`` is identically False —
    sa and sb collapse to all-zero masks, which zeroes one limb entirely
    (``x1 = 0`` in the A form / ``y1 = 0`` in the B form) and reduces the
    K-stacked product to the plain ``f(A) @ g(B)``.  That is the traced
    NoSwap fast path: ONE compiled program is config-agnostic over all
    4M+1 triples, and NoSwap rides it with a zero limb contributing nothing
    to the stacked int32 reduction (bit-identical to the static NoSwap
    matmul; a structured-sparsity backend could skip the zero limb — see
    ROADMAP)."""
    is_a = op_is_a == 1
    sa = ((((ai >> bit) & 1) == value) & is_a).astype(jnp.int32)
    sb = ((((bi >> bit) & 1) == value) & ~is_a).astype(jnp.int32)
    x1 = jnp.where(is_a, sa * g(ai), g(ai)).astype(jnp.int8)
    y1 = jnp.where(is_a, f(bi), sb * f(bi)).astype(jnp.int8)
    x2 = jnp.where(is_a, (1 - sa) * f(ai), f(ai)).astype(jnp.int8)
    y2 = jnp.where(is_a, g(bi), (1 - sb) * g(bi)).astype(jnp.int8)
    return x1, y1, x2, y2


def _mxu_limbs_rowtile(ai, bi, f, g, row_triples, b_rep):
    """K-stacked limbs with a *per-row* swap decision (``row_triples`` is a
    traced (M, 3) int32 array, one triple per row of the 2-D ``ai``;
    ``b_rep`` the traced representative B-side triple — see
    ``_bside_representative``).

    Per-row decisions on the A operand are elementwise: the row's
    (bit, value) broadcasts down its K lanes, so the A-form factorization
    ``sa*g(A) @ f(B) + (1-sa)*f(A) @ g(B)`` holds row-wise.  Rows whose
    triple is a NoSwap encoding (``value == 2``, either operand) zero
    their slice of ``sa`` and ride the A-form pair (see
    ``_mxu_limbs_dyn``).

    A per-row *B-side* decision masks the weight operand, which cannot
    vary per output row inside a factorized matmul — but a B-side decision
    *shared by every B-side row* can: its column mask ``sb`` comes from the
    representative triple and B-side rows are routed to a second limb pair
    ``g(A) @ (sb*f(B)) + f(A) @ ((1-sb)*g(B))`` gated by a row indicator.
    The four pairs stack into ONE int8 ``dot_general`` over a 4K inner
    dimension, so the program stays single-dispatch and config-agnostic:
    A-side / NoSwap / uniform-B-side grids are all exact (the broadcast of
    any scalar config into a tile grid in particular).  The generality
    costs a 4K inner dimension even when the grid is A-side-only and the
    B-form limbs are runtime zeros — a deliberate correctness-first
    tradeoff: a static "A-side-only" program variant would be silently
    wrong the moment a B-side scalar config broadcasts into tile mode,
    so selecting it needs a host-side guard (ROADMAP follow-on).  Grids
    mixing
    *different* B-side triples are the one inexpressible case — rejected
    host-side by ``SwapPolicy.set_tile_grid``; the Pallas grid kernel
    executes them when wanted (``backend='kernel'``).  The controller's
    tile re-tune space (``controller.tile_triples``) is A-side/NoSwap only,
    which keeps its published grids exact on every backend.
    """
    op = row_triples[:, 0:1]
    bit = row_triples[:, 1:2]
    value = row_triples[:, 2:3]
    is_b = (op == 0) & (value <= 1)            # live B-side decision rows
    sa = ((((ai >> bit) & 1) == value) & (op == 1)).astype(jnp.int32)
    ib = is_b.astype(jnp.int32)
    ia = 1 - ib                                # A-side AND NoSwap rows
    sb = (((bi >> b_rep[1]) & 1) == b_rep[2]).astype(jnp.int32)
    return ((sa * g(ai)).astype(jnp.int8), f(bi).astype(jnp.int8),
            (ia * (1 - sa) * f(ai)).astype(jnp.int8), g(bi).astype(jnp.int8),
            (ib * g(ai)).astype(jnp.int8), (sb * f(bi)).astype(jnp.int8),
            (ib * f(ai)).astype(jnp.int8), ((1 - sb) * g(bi)).astype(jnp.int8))


def _bside_representative(flat_triples):
    """The (traced) B-side triple of a tile grid: ``set_tile_grid``
    guarantees at most one distinct B-side triple per grid, so the first
    B-side row is THE representative wherever it sits (grids with no
    B-side rows return an arbitrary row — its mask is then gated out by
    the all-zero ``ib`` indicator)."""
    is_b = (flat_triples[:, 0] == 0) & (flat_triples[:, 2] <= 1)
    return flat_triples[jnp.argmax(is_b)]


def _block_of(span: int, cap: int = 128) -> int:
    """Kernel block size aligned to a logical tile span, so no block
    straddles a tile."""
    return largest_divisor_leq(span, cap)


def _kernel_grid_tiled(a_i8, b_i8, mult, dyn, sched: KernelSchedule,
                       tile_hist: bool = False):
    """Pallas grid-kernel dispatch of a logical (gm, gn, 3) config grid.

    The scalar-prefetch kernel applies one triple per *physical* block, so
    the block shape is chosen to align with the logical tile spans
    (``_block_of`` under the schedule's caps: each block lies inside
    exactly one logical tile) and the logical grid is gathered onto the
    block grid with static indices — bit-exact per-tile semantics at any
    granularity, still zero recompiles across grid-value updates.  On a
    real TPU a production deployment picks ``gm`` so the tile span stays a
    multiple of the 128-lane MXU block (the alignment here then reduces to
    the schedule blocks).

    ``tile_hist=True`` additionally returns the kernel's in-reduction
    bit-occupancy statistic aggregated back to the LOGICAL row tiles: a
    ``(tile_bits (g, bits) f32, tile_neg (g,) f32, tile_n (g,) i32)``
    triple with full per-tile counts (every real element of the tile, not
    a sample — zero-padding contributes zero counts and is excluded from
    ``tile_n``, so counts/n is exact over the real data).  Physical row
    blocks are tile-aligned, so the block→tile aggregation is a static
    segment sum."""
    import dataclasses as _dc

    from repro.kernels import ax_matmul_grid

    lead = a_i8.shape[:-1]
    a2d = a_i8.reshape(-1, a_i8.shape[-1])
    m0, k0 = a2d.shape
    n0 = b_i8.shape[-1]
    g_m = rowtile_count(m0, int(dyn.shape[0]))
    g_n = rowtile_count(n0, int(dyn.shape[1]))
    rows_per = rowtile_span(m0, int(dyn.shape[0]))
    cols_per = rowtile_span(n0, int(dyn.shape[1]))
    bm, bn = _block_of(rows_per, sched.bm), _block_of(cols_per, sched.bn)
    bk = min(sched.bk, k0)
    a2d = _pad_to_multiple(_pad_to_multiple(a2d, bm, 0), bk, 1)
    bp = _pad_to_multiple(_pad_to_multiple(b_i8, bk, 0), bn, 1)
    gmk, gnk = a2d.shape[0] // bm, bp.shape[1] // bn
    ri = np.minimum((np.arange(gmk) * bm) // rows_per, g_m - 1)
    ci = np.minimum((np.arange(gnk) * bn) // cols_per, g_n - 1)
    grid = dyn.astype(jnp.int32)[ri][:, ci]
    ksched = _dc.replace(sched, bm=bm, bn=bn, bk=bk)
    res = ax_matmul_grid(a2d, bp, mult, grid, schedule=ksched,
                         tile_hist=tile_hist)
    if not tile_hist:
        return res[:m0, :n0].reshape(*lead, n0)
    out, hist = res
    bits = mult.bits
    # A-side counts are identical across a row of output tiles — column 0
    # suffices; aggregate physical row blocks onto logical tiles with the
    # static (g_m, gmk) assignment built from ``ri``
    a_rows = hist[:, 0, 0, :].astype(jnp.float32)            # (gmk, bits+1)
    assign = jnp.asarray(np.equal.outer(np.arange(g_m), ri)
                         .astype(np.float32))                # (g_m, gmk)
    agg = assign @ a_rows
    rows_t = np.full(g_m, rows_per, np.int64)
    rows_t[-1] = m0 - (g_m - 1) * rows_per                   # absorbed remainder
    tile_n = jnp.asarray(rows_t * k0, jnp.int32)
    return (out[:m0, :n0].reshape(*lead, n0),
            (agg[:, :bits], agg[:, bits], tile_n))


def _pad_to_multiple(v, mult_, axis):
    """Zero-pad ``v`` along ``axis`` up to the next multiple of ``mult_``
    (the Pallas kernels require block-divisible shapes; callers crop the
    output back)."""
    pad = (-v.shape[axis]) % mult_
    if pad == 0:
        return v
    widths = [(0, 0)] * v.ndim
    widths[axis] = (0, pad)
    return jnp.pad(v, widths)


def _pad_for_kernel(a_i8, b_i8, sched: KernelSchedule):
    """Flatten leading dims and zero-pad both operands to block multiples for
    the Pallas kernels (blocks = the schedule's caps clamped to the logical
    dims).  Returns (a2d, b, lead_shape, m0, n0, (bm, bn, bk)); callers crop
    ``out[:m0, :n0]`` and reshape to ``(*lead, n0)``."""
    lead = a_i8.shape[:-1]
    a2d = a_i8.reshape(-1, a_i8.shape[-1])
    m0, k0 = a2d.shape
    n0 = b_i8.shape[-1]
    bm, bn, bk = min(sched.bm, m0), min(sched.bn, n0), min(sched.bk, k0)
    a2d = _pad_to_multiple(_pad_to_multiple(a2d, bm, 0), bk, 1)
    bp = _pad_to_multiple(_pad_to_multiple(b_i8, bk, 0), bn, 1)
    return a2d, bp, lead, m0, n0, (bm, bn, bk)


def ax_matmul_int(a_i8, b_i8, policy: AxPolicy, schedule=None) -> jax.Array:
    """Approximate int matmul (..., K) @ (K, N) -> (..., N) int32.

    The dispatch configuration is a :class:`KernelSchedule` resolved as
    explicit ``schedule=`` > process-installed autotuner table > defaults
    (signature op "int_static" for mxu, "matmul" for the Pallas kernel) —
    resolution is host-side at trace time, so table adoption never
    retraces a compiled program.

    The mxu backend with the default ``limbs="stacked"`` schedule
    dispatches exactly one int8 ``dot_general`` per call: NoSwap is the
    plain ``f(A) @ g(B)``, a swap config K-stacks the two factorization
    limbs into a single matmul over the 2K inner dimension
    (``limbs="split"`` selects the pre-stacking 2-matmul form when the
    autotuner measures it faster on a host)."""
    mult = M.get(policy.mult_name)
    swap = policy.swap
    if policy.backend == "mxu":
        sched = resolve_for(a_i8.shape, b_i8.shape, "mxu", policy.mult_name,
                            "int_static", override=schedule)
        sep = separable_transforms(policy.mult_name)
        assert sep is not None, f"{policy.mult_name} is not separable; use backend='kernel'"
        f, g = sep
        ai = a_i8.astype(jnp.int32)
        bi = b_i8.astype(jnp.int32)
        if swap is None:
            return _int_mm(f(ai).astype(jnp.int8), g(bi).astype(jnp.int8))
        limbs = _mxu_limbs(ai, bi, f, g, swap)
        if sched.limbs == "split":
            return _int_mm(*limbs[:2]) + _int_mm(*limbs[2:])
        return _stacked_mm(*limbs)
    if policy.backend == "kernel":
        import dataclasses as _dc

        from repro.kernels import ax_matmul as kernel_mm

        sched = resolve_for(a_i8.shape, b_i8.shape, "kernel",
                            policy.mult_name, "matmul", override=schedule)
        a2d, bp, lead, m0, n0, (bm, bn, bk) = _pad_for_kernel(a_i8, b_i8, sched)
        out = kernel_mm(a2d, bp, mult, swap,
                        schedule=_dc.replace(sched, bm=bm, bn=bn, bk=bk))
        return out[:m0, :n0].reshape(*lead, n0)
    # 'emul'
    from repro.kernels.ref import ax_matmul_ref

    lead = a_i8.shape[:-1]
    a2d = a_i8.reshape(-1, a_i8.shape[-1])
    return ax_matmul_ref(a2d, b_i8, mult, swap).reshape(*lead, b_i8.shape[-1])


def ax_matmul_int_2mm(a_i8, b_i8, policy: AxPolicy) -> jax.Array:
    """The pre-K-stacking 2-matmul mxu factorization, retained as the
    bit-identity oracle and the old-path benchmark baseline (see
    ``benchmarks/perf_table.py``).  mxu backend only."""
    assert policy.backend == "mxu", policy.backend
    sep = separable_transforms(policy.mult_name)
    assert sep is not None, f"{policy.mult_name} is not separable"
    f, g = sep
    ai = a_i8.astype(jnp.int32)
    bi = b_i8.astype(jnp.int32)
    if policy.swap is None:
        return _int_mm(f(ai).astype(jnp.int8), g(bi).astype(jnp.int8))
    x1, y1, x2, y2 = _mxu_limbs(ai, bi, f, g, policy.swap)
    return _int_mm(x1, y1) + _int_mm(x2, y2)


# ---------------------------------------------------------------------------
# dynamic-config variants (the adaptive-runtime zero-recompile path)
# ---------------------------------------------------------------------------

def _mxu_dyn_scalar(ai, bi, f, g, dyn, sched: KernelSchedule):
    """mxu dispatch of a traced scalar triple under a schedule: the limb
    strategy ("stacked" 2K single matmul vs "split" 2-matmul) comes from
    the schedule, and ``noswap_fast=True`` adds the K-limb sparsity branch
    — a ``lax.cond`` on the traced ``value == 2`` NoSwap encoding that
    routes to a K-inner-dim single matmul (half the stacked inner dim)
    while swap configs take the limb path.  Both branches live in ONE
    compiled program (the config stays a traced input — zero recompiles),
    at the cost of an extra dot_general in the jaxpr, which is why the
    branch is an opt-in schedule knob rather than the default (the default
    path must keep its 1-dot_general jaxpr gate)."""

    def _limb_path(_):
        limbs = _mxu_limbs_dyn(ai, bi, f, g, dyn[0], dyn[1], dyn[2])
        if sched.limbs == "split":
            return _int_mm(*limbs[:2]) + _int_mm(*limbs[2:])
        return _stacked_mm(*limbs)

    if not sched.noswap_fast:
        return _limb_path(None)

    def _noswap_path(_):
        return _int_mm(f(ai).astype(jnp.int8), g(bi).astype(jnp.int8))

    return jax.lax.cond(dyn[2] == 2, _noswap_path, _limb_path, None)


def ax_matmul_int_dyn(a_i8, b_i8, policy: AxPolicy, dyn, schedule=None) -> jax.Array:
    """``ax_matmul_int`` with the swap decision as a *traced* int32 input,
    so the adaptive controller can re-tune a serving step without
    recompiling it.  ``dyn`` is either

    * a (3,) (op_is_a, bit, value) triple — one decision for the whole
      projection (value=2 encodes NoSwap; see ``_mxu_limbs_dyn`` for the
      limb-zeroing encoding), or
    * a (gm, gn, 3) per-tile config grid (``SwapPolicy.tile_grid``) — the
      gm row tiles of the flattened token dimension each apply their own
      triple.  The grid is resampled to each backend's physical tiling with
      *static* indices, so tile-grid updates stay zero-recompile.

    Backends: mxu dispatches ONE K-stacked int8 ``dot_general`` for every
    scalar triple and for per-row-tile grids (A-side/NoSwap per tile; see
    ``_mxu_limbs_rowtile`` — gn must be 1); ``kernel`` routes the
    scalar-prefetch Pallas grid kernel (fully general grids); ``emul`` is
    the pure-jnp reference for both.

    ``schedule`` — optional explicit :class:`KernelSchedule`; otherwise the
    installed autotuner table is consulted (op "int_dyn" for mxu scalars,
    "matmul_grid" for the kernel backend) with a defaults fallback.  The
    mxu scalar path honors ``limbs`` and ``noswap_fast`` (see
    ``_mxu_dyn_scalar``); per-row-tile mxu grids always use the K-stacked
    4-limb form (the only single-dispatch factorization for them)."""
    mult = M.get(policy.mult_name)
    dyn = jnp.asarray(dyn)
    tiled = dyn.ndim == 3
    if policy.backend == "mxu":
        sep = separable_transforms(policy.mult_name)
        assert sep is not None, f"{policy.mult_name} is not separable; use backend='kernel'"
        f, g = sep
        ai = a_i8.astype(jnp.int32)
        bi = b_i8.astype(jnp.int32)
        if tiled:
            assert dyn.shape[1] == 1, (
                f"mxu per-tile grids are row-granular (gn must be 1, got "
                f"{dyn.shape}); use backend='kernel' for column tiles")
            lead = a_i8.shape[:-1]
            a2 = ai.reshape(-1, ai.shape[-1])
            row_triples = dyn[:, 0, :][rowtile_index(a2.shape[0], dyn.shape[0])]
            out = _stacked_mm(*_mxu_limbs_rowtile(
                a2, bi, f, g, row_triples, _bside_representative(dyn[:, 0, :])))
            return out.reshape(*lead, b_i8.shape[-1])
        sched = resolve_for(a_i8.shape, b_i8.shape, "mxu", policy.mult_name,
                            "int_dyn", override=schedule)
        return _mxu_dyn_scalar(ai, bi, f, g, dyn, sched)
    if policy.backend == "kernel":
        import dataclasses as _dc

        from repro.kernels import ax_matmul_grid

        sched = resolve_for(a_i8.shape, b_i8.shape, "kernel",
                            policy.mult_name, "matmul_grid", override=schedule)
        if tiled:
            return _kernel_grid_tiled(a_i8, b_i8, mult, dyn, sched)
        a2d, bp, lead, m0, n0, (bm, bn, bk) = _pad_for_kernel(a_i8, b_i8, sched)
        gmk, gnk = a2d.shape[0] // bm, bp.shape[1] // bn
        grid = jnp.broadcast_to(dyn.astype(jnp.int32), (gmk, gnk, 3))
        out = ax_matmul_grid(a2d, bp, mult, grid,
                             schedule=_dc.replace(sched, bm=bm, bn=bn, bk=bk))
        return out[:m0, :n0].reshape(*lead, n0)
    # 'emul'
    lead = a_i8.shape[:-1]
    a2 = a_i8.reshape(-1, a_i8.shape[-1]).astype(jnp.int32)
    B = b_i8.astype(jnp.int32)
    if tiled:
        Mrows, N = a2.shape[0], b_i8.shape[-1]
        ri = rowtile_index(Mrows, dyn.shape[0])
        ci = rowtile_index(N, dyn.shape[1])
        rows = []
        for ti in range(int(dyn.shape[0])):
            sel = np.nonzero(ri == ti)[0]
            if len(sel) == 0:
                continue
            A = a2[sel[0]:sel[-1] + 1][:, :, None]
            blocks = []
            for tj in range(int(dyn.shape[1])):
                cs = np.nonzero(ci == tj)[0]
                if len(cs) == 0:
                    continue
                t = dyn[ti, tj]
                prod = apply_swapper_dyn(
                    mult, A, B[None, :, cs[0]:cs[-1] + 1], t[0], t[1], t[2])
                blocks.append(jnp.sum(prod.astype(jnp.int32), axis=1,
                                      dtype=jnp.int32))
            rows.append(jnp.concatenate(blocks, axis=1))
        return jnp.concatenate(rows, axis=0).reshape(*lead, N)
    prod = apply_swapper_dyn(mult, a2[:, :, None], B[None, :, :],
                             dyn[0], dyn[1], dyn[2]).astype(jnp.int32)
    return jnp.sum(prod, axis=1, dtype=jnp.int32).reshape(*lead, b_i8.shape[-1])


def ax_matmul_int_dyn_2mm(a_i8, b_i8, policy: AxPolicy, dyn) -> jax.Array:
    """The pre-K-stacking 2-matmul dynamic mxu path (bit-identity oracle /
    benchmark baseline).  mxu backend only."""
    assert policy.backend == "mxu", policy.backend
    sep = separable_transforms(policy.mult_name)
    assert sep is not None, f"{policy.mult_name} is not separable"
    f, g = sep
    ai = a_i8.astype(jnp.int32)
    bi = b_i8.astype(jnp.int32)
    x1, y1, x2, y2 = _mxu_limbs_dyn(ai, bi, f, g, dyn[0], dyn[1], dyn[2])
    return _int_mm(x1, y1) + _int_mm(x2, y2)


def ax_matmul_int_dyn_hist(a_i8, b_i8, policy: AxPolicy, dyn, schedule=None):
    """:func:`ax_matmul_int_dyn` (kernel backend, tiled dyn) that also
    returns the kernel's in-reduction per-row-tile operand statistic: the
    ``(tile_bits (g, bits) f32, tile_neg (g,) f32, tile_n (g,) i32)``
    triple ``runtime.telemetry.tile_summary`` accepts via ``bits_from=``.
    One kernel dispatch both applies the per-tile policy and emits the
    statistics the controller uses to compute the next one — the kernel
    backend's telemetry tap without a second pass over the operands."""
    assert policy.backend == "kernel", policy.backend
    dyn = jnp.asarray(dyn)
    assert dyn.ndim == 3, dyn.shape
    mult = M.get(policy.mult_name)
    sched = resolve_for(a_i8.shape, b_i8.shape, "kernel", policy.mult_name,
                        "matmul_grid", override=schedule)
    return _kernel_grid_tiled(a_i8, b_i8, mult, dyn, sched, tile_hist=True)


# ---------------------------------------------------------------------------
# the projection layer
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ax_dense(x, w, policy: AxPolicy):
    """y = x @ w through the SWAPPER approximate path (quantize -> ax matmul
    -> dequantize); straight-through exact gradients for training."""
    return _ax_dense_fwd_impl(x, w, policy)


def _ax_dense_fwd_impl(x, w, policy):
    xq, sx = quantize_rows(x.astype(jnp.float32), axis=-1)
    wq, sw = quantize_rows(w.astype(jnp.float32), axis=0)
    acc = ax_matmul_int(xq, wq, policy)
    return (acc.astype(jnp.float32) * sx * sw).astype(x.dtype)


def _ax_dense_fwd(x, w, policy):
    return _ax_dense_fwd_impl(x, w, policy), (x, w)


def _ax_dense_bwd(policy, res, gy):
    x, w = res
    gy32 = gy.astype(jnp.float32)
    gx = (gy32 @ w.astype(jnp.float32).T).astype(x.dtype)
    xf = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    gw = (xf.T @ gy32.reshape(-1, gy.shape[-1])).astype(w.dtype)
    return gx, gw


ax_dense.defvjp(_ax_dense_fwd, _ax_dense_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ax_dense_dyn_core(x, w, policy: AxPolicy, dyn, xq, sx, wq, sw):
    """Dequantized dynamic approximate matmul over *pre-quantized* operands.

    The quantization is hoisted into :func:`ax_dense_dyn` so the telemetry
    tap and the matmul share one set of ``quantize_rows`` results explicitly
    (the summary's tracers must belong to the outer trace to leave the jitted
    step, so it cannot live inside this custom_vjp boundary).  ``x``/``w``
    ride along as the straight-through gradient residuals."""
    acc = ax_matmul_int_dyn(xq, wq, policy, dyn)
    return (acc.astype(jnp.float32) * sx * sw).astype(x.dtype)


def _ax_dense_dyn_fwd(x, w, policy, dyn, xq, sx, wq, sw):
    return _ax_dense_dyn_core(x, w, policy, dyn, xq, sx, wq, sw), (x, w, dyn.shape)


def _ax_dense_dyn_bwd(policy, res, gy):
    x, w, dyn_shape = res
    gx, gw = _ax_dense_bwd(policy, res[:2], gy)
    # integer inputs (config triple/grid, int8 operands): symbolic-zero
    # (float0) cotangents; the f32 quantization scales get literal zeros
    # (STE ignores the quantization path entirely)
    f0 = jax.dtypes.float0
    return (gx, gw, np.zeros(dyn_shape, f0),
            np.zeros(x.shape, f0), jnp.zeros(x.shape[:-1] + (1,), jnp.float32),
            np.zeros(w.shape, f0), jnp.zeros((1, w.shape[-1]), jnp.float32))


_ax_dense_dyn_core.defvjp(_ax_dense_dyn_fwd, _ax_dense_dyn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ax_dense_dyn_hist_core(x, w, policy: AxPolicy, dyn, xq, sx, wq, sw):
    """:func:`_ax_dense_dyn_core` whose kernel dispatch also emits the
    per-row-tile bit-occupancy statistic (``ax_matmul_int_dyn_hist``) —
    the histogram rides THROUGH the custom_vjp boundary as a second output
    so the kernel backend's telemetry costs no extra operand pass.  Its
    cotangent is ignored (the statistic is observational; gradients are
    identical to the non-hist path)."""
    acc, hist = ax_matmul_int_dyn_hist(xq, wq, policy, dyn)
    return (acc.astype(jnp.float32) * sx * sw).astype(x.dtype), hist


def _ax_dense_dyn_hist_fwd(x, w, policy, dyn, xq, sx, wq, sw):
    return (_ax_dense_dyn_hist_core(x, w, policy, dyn, xq, sx, wq, sw),
            (x, w, dyn.shape))


def _ax_dense_dyn_hist_bwd(policy, res, gy):
    # gy = (gy_out, gy_hist); the hist cotangent is dropped — same
    # straight-through residual gradients as _ax_dense_dyn_bwd
    return _ax_dense_dyn_bwd(policy, res, gy[0])


_ax_dense_dyn_hist_core.defvjp(_ax_dense_dyn_hist_fwd, _ax_dense_dyn_hist_bwd)


def ax_dense_dyn(x, w, policy: AxPolicy, dyn, scope=None, target: str = ""):
    """``ax_dense`` with the swap decision as a traced input (adaptive
    runtime path): ``dyn`` is a (3,) triple, or a (gm, 1, 3) per-row-tile
    grid when the scope runs in tile mode (``ax_matmul_int_dyn`` handles
    both with zero recompiles on value changes).

    When a collecting scope is open this also emits the telemetry records
    for the call: the scalar ``operand_summary`` (its live-policy error
    sample uses the first tile's triple when ``dyn`` is a grid — the bit
    statistics are policy-independent), plus a per-row-tile
    ``tile_summary`` under ``tile_key(target)`` when ``scope.tile_rows``
    is set — the feed of the controller's per-tile re-tune path.

    ``quantize_rows`` runs once here and its results feed both the
    telemetry summaries and the matmul core explicitly (no reliance on XLA
    CSE).  The scope's traced observe gate (if any) lets off-steps skip the
    summary compute entirely (``lax.cond``) while keeping record shapes
    static.

    With ``scope.kernel_hist`` set (kernel backend, tile mode, grid dyn)
    the per-tile bit statistic comes out of the matmul kernel itself
    through the custom_vjp boundary (``_ax_dense_dyn_hist_core``) instead
    of a separate sampled pass — full counts, one fewer traversal of
    ``xq`` (the ROADMAP tile_hist fold-in)."""
    xq, sx = quantize_rows(x.astype(jnp.float32), axis=-1)
    wq, sw = quantize_rows(w.astype(jnp.float32), axis=0)
    dyn = jnp.asarray(dyn)
    if _kernel_hist(scope, policy, dyn):
        y, hist = _ax_dense_dyn_hist_core(x, w, policy, dyn, xq, sx, wq, sw)
        _record_telemetry(scope, target, policy, xq, wq, dyn, hist)
        return y
    _record_telemetry(scope, target, policy, xq, wq, dyn)
    return _ax_dense_dyn_core(x, w, policy, dyn, xq, sx, wq, sw)


def _kernel_hist(scope, policy: AxPolicy, dyn) -> bool:
    """Whether the tile statistic comes out of the matmul kernel itself."""
    return (scope is not None and scope.collect and scope.tile_rows > 0
            and getattr(scope, "kernel_hist", False)
            and policy.backend == "kernel" and dyn.ndim == 3)


def _record_telemetry(scope, target, policy: AxPolicy, xq, wq, dyn, hist=None):
    """Emit the call's telemetry records into a collecting scope: the scalar
    ``operand_summary`` (its live-policy error sample takes the first tile's
    triple when ``dyn`` is a grid) and, in tile mode, the per-row-tile
    ``tile_summary`` (bit statistic from ``hist`` when the kernel made it)."""
    if scope is None or not scope.collect:
        return
    from repro.runtime.telemetry import operand_summary, tile_key, tile_summary

    mult = M.get(policy.mult_name)
    dyn_rep = dyn if dyn.ndim == 1 else dyn[0, 0]
    with jax.named_scope(f"ax_telemetry.{target}"):
        scope.record(target, operand_summary(xq, wq, mult, dyn_rep,
                                             gate=scope.gate))
        if scope.tile_rows > 0:
            scope.record(tile_key(target),
                         tile_summary(xq, wq, mult, scope.tile_rows,
                                      gate=scope.gate, dyn=dyn,
                                      bits_from=hist))


# ---------------------------------------------------------------------------
# prepared weights: the weight side of a projection, built once per
# parameter set (serving), then read in place by every call
# ---------------------------------------------------------------------------

def prepare_weight(w, policy: AxPolicy, dtype) -> dict:
    """The weight side of one approximated projection ``w`` (..., K, N),
    leading (stacked-layer) axes kept: ``sw`` the per-column f32 scales and
    the int8 codes, quantized from ``w`` cast to ``dtype`` (the projection
    input's dtype) exactly as ``ax_dense`` quantizes it per call.

    For the mxu backend the record holds ``wfg`` (..., 2, K, N) =
    ``[f(wq), g(wq)]``, the limbs stacked on an axis of their own (so a
    K-sharded projection keeps both halves on one shard), and ``wq`` only
    where ``f`` is not the identity (else ``wq`` is ``wfg[..., 0, :, :]``).
    The other backends build no limbs from the weight: ``wq`` alone."""
    wq, sw = quantize_rows(w.astype(dtype).astype(jnp.float32), axis=-2)
    if policy.backend != "mxu":
        return {"wq": wq, "sw": sw}
    sep = separable_transforms(policy.mult_name)
    assert sep is not None, f"{policy.mult_name} is not separable; use backend='kernel'"
    f, g = sep
    wi = wq.astype(jnp.int32)
    rec = {"wfg": jnp.stack([f(wi), g(wi)], axis=-3).astype(jnp.int8),
           "sw": sw}
    if f is not _identity:
        rec["wq"] = wq
    return rec


@functools.partial(jax.jit, static_argnums=(1, 2))
def prepare_ax(ws, policy: AxPolicy, dtype):
    """:func:`prepare_weight` of every weight in ``ws`` as one program
    (traces name it ``jit_prepare_ax``)."""
    return [prepare_weight(w, policy, dtype) for w in ws]


def _projection_paths(params, targets):
    """Paths of the ``{"w": ...}`` subtrees that ``dense`` runs under one of
    ``targets``, found by the leaf names the sharding rules read."""
    from repro.models.layers import PROJECTION_TARGETS

    found = []

    def walk(node, path):
        for k, v in node.items():
            if not isinstance(v, dict) or k == "experts":
                continue          # expert FFNs are einsums, not ``dense``
            if "w" in v and PROJECTION_TARGETS.get(k) in targets:
                found.append(path + (k,))
            else:
                walk(v, path + (k,))

    if isinstance(params, dict):
        walk(params, ())
    return found


def prepare_params(params, cfg):
    """A new parameter tree whose approximated projections (``dense`` under
    a target in ``cfg.ax.targets``) hold prepared records
    (:func:`prepare_weight`) in place of ``{"w": ...}``; biases and every
    other leaf are the input's own arrays.  One jitted program
    (``jit_prepare_ax``, span ``ax_prepare``).  The input is not changed;
    for serving only — the prepared path has no gradient."""
    ax = cfg.ax
    paths = _projection_paths(params, ax.targets) if ax is not None else []
    if not paths:
        return params
    from repro import obs

    def node(tree, path):
        for k in path:
            tree = tree[k]
        return tree

    with obs.span("ax_prepare", cat="engine", projections=len(paths)):
        recs = prepare_ax([node(params, p)["w"] for p in paths], ax,
                          jnp.dtype(cfg.compute_dtype))
        jax.block_until_ready(recs)
    out = dict(params)
    for path, rec in zip(paths, recs):
        parent = out
        for k in path[:-1]:
            parent[k] = dict(parent[k])
            parent = parent[k]
        old = parent[path[-1]]
        parent[path[-1]] = {**rec, **{k: v for k, v in old.items() if k != "w"}}
    return out


def is_prepared(v) -> bool:
    """Whether ``v`` is a prepared projection record (every one has ``sw``)."""
    return isinstance(v, dict) and "sw" in v


def prepared_projections(params) -> int:
    """Approximated projections a tree holds as prepared records, stacked
    layers counted one by one."""
    n = 0
    for v in (params.values() if isinstance(params, dict) else ()):
        if is_prepared(v):
            n += int(np.prod(v["sw"].shape[:-2], dtype=np.int64))
        elif isinstance(v, dict):
            n += prepared_projections(v)
    return n


def take_layer(tree, n: int):
    """``tree`` (stacked layers) at layer ``n``, for an unrolled layer loop.
    A prepared record keeps its weight leaves whole, with ``layer`` = n,
    and is sliced where it is read: XLA fuses a slice into the dot that
    reads it, but copies a slice that enters a ``lax.cond`` — the swap-side
    branch of the dynamic path (see :func:`_prepared_int_dyn`)."""
    def one(v):
        if not is_prepared(v):
            return v[n]
        return {"layer": n, **{k: a if k in ("wfg", "wq") else a[n]
                               for k, a in v.items()}}

    return jax.tree.map(one, tree, is_leaf=is_prepared)


def _leaf(p, k):
    return p[k] if "layer" not in p else p[k][p["layer"]]


def _prepared_wq(p):
    return _leaf(p, "wq") if "wq" in p else _leaf(p, "wfg")[0]


def _wfg_mm(x1, x2, wfg, sched: KernelSchedule):
    """``x1 @ wfg[0] + x2 @ wfg[1]``: one int8 dot contracting the limb axis
    and K together (``limbs="split"``: two dots), reading ``wfg`` in place."""
    if sched.limbs == "split":
        return _int_mm(x1, wfg[0]) + _int_mm(x2, wfg[1])
    x = jnp.stack([x1, x2], axis=-2)
    return jax.lax.dot_general(
        x, wfg, (((x.ndim - 2, x.ndim - 1), (0, 1)), ((), ())),
        preferred_element_type=jnp.int32)


def _prepared_int(xq, p, policy: AxPolicy):
    """``ax_matmul_int`` (static policy) against a prepared record."""
    wq = _prepared_wq(p)
    swap = policy.swap
    if policy.backend != "mxu" or (swap is not None and swap.operand == "B"):
        return ax_matmul_int(xq, wq, policy)
    f, g = separable_transforms(policy.mult_name)
    ai = xq.astype(jnp.int32)
    wfg = _leaf(p, "wfg")
    if swap is None:
        return _int_mm(f(ai).astype(jnp.int8), wfg[1])
    sched = resolve_for(xq.shape, wq.shape, "mxu", policy.mult_name,
                        "int_static")
    s = _swap_mask(ai, swap).astype(jnp.int32)
    return _wfg_mm((s * g(ai)).astype(jnp.int8),
                   ((1 - s) * f(ai)).astype(jnp.int8), wfg, sched)


def _prepared_int_dyn(xq, p, policy: AxPolicy, dyn):
    """``ax_matmul_int_dyn`` (traced scalar triple, mxu) against a prepared
    record.  A-side and NoSwap triples dot ``[sa*g(A) | (1-sa)*f(A)]``
    against ``wfg`` as stored; a B-side triple masks the prepared limbs by
    the weight's bit (the one weight-sized build, in its own branch).  The
    weight leaves are read inside the branches, so a layer slice of them
    (:func:`take_layer`) fuses into the dot instead of being copied."""
    sched = resolve_for(xq.shape, p["wfg"].shape[-2:], "mxu",
                        policy.mult_name, "int_dyn")
    f, g = separable_transforms(policy.mult_name)
    ai = xq.astype(jnp.int32)
    bit, value = dyn[1], dyn[2]

    def a_side(_):
        sa = (((ai >> bit) & 1) == value).astype(jnp.int32)
        return _wfg_mm((sa * g(ai)).astype(jnp.int8),
                       ((1 - sa) * f(ai)).astype(jnp.int8), _leaf(p, "wfg"),
                       sched)

    def b_side(_):
        wfg = _leaf(p, "wfg")
        sb = ((_prepared_wq(p).astype(jnp.int32) >> bit) & 1) == value
        y = jnp.stack([jnp.where(sb, wfg[0], 0), jnp.where(sb, 0, wfg[1])])
        return _wfg_mm(g(ai).astype(jnp.int8), f(ai).astype(jnp.int8), y,
                       sched)

    def limb_path(_):
        return jax.lax.cond(dyn[0] == 1, a_side, b_side, None)

    if not sched.noswap_fast:
        return limb_path(None)

    def noswap_path(_):
        return _int_mm(f(ai).astype(jnp.int8), _leaf(p, "wfg")[1])

    return jax.lax.cond(value == 2, noswap_path, limb_path, None)


def ax_dense_prepared(x, p, policy: AxPolicy, dyn=None, scope=None,
                      target: str = ""):
    """``ax_dense`` (``dyn`` None) or ``ax_dense_dyn`` against a prepared
    record (:func:`prepare_weight`): only the activation is quantized, the
    weight's codes, limbs and scales are read as prepared.  Bit-identical
    to the raw path, telemetry records included; forward only.

    The mxu backend with a scalar triple reads ``wfg`` in place; tile grids
    and the ``kernel`` / ``emul`` backends build their operands from the
    prepared ``wq`` as the raw path does from its per-call quantization."""
    xq, sx = quantize_rows(x.astype(jnp.float32), axis=-1)
    if dyn is None:
        acc = _prepared_int(xq, p, policy)
    else:
        dyn = jnp.asarray(dyn)
        wq = _prepared_wq(p)
        hist = None
        if _kernel_hist(scope, policy, dyn):
            acc, hist = ax_matmul_int_dyn_hist(xq, wq, policy, dyn)
        elif policy.backend == "mxu" and dyn.ndim == 1:
            acc = _prepared_int_dyn(xq, p, policy, dyn)
        else:
            acc = ax_matmul_int_dyn(xq, wq, policy, dyn)
        _record_telemetry(scope, target, policy, xq, wq, dyn, hist)
    return (acc.astype(jnp.float32) * sx * p["sw"]).astype(x.dtype)
