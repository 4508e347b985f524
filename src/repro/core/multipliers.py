"""Bit-accurate approximate multiplier (AxIC) families in pure JAX integer ops.

The paper evaluates SWAPPER on EvoApproxLib netlists.  Those C models are not
available offline, so we implement *parametric closed-form AxIC families* from
the approximate-arithmetic literature — operand truncation, partial-product
perforation, broken-array (BAM), asymmetric Mitchell logarithmic, DRUM-style
leading-one segmenting — plus an arbitrary LUT-defined multiplier that can
represent *any* 8-bit circuit (the EvoApprox compatibility path).

Conventions
-----------
* Operands are M-bit integers carried in ``int32`` lanes:
  unsigned in ``[0, 2^M)``, signed two's complement in ``[-2^(M-1), 2^(M-1))``.
* Products are returned in ``uint32`` (unsigned) / ``int32`` (signed) lanes.
  For M <= 16 the exact product always fits: ``65535**2 < 2**32`` and
  ``|(-2^15)*(-2^15)| = 2^30 < 2^31``.
* Every family is jit/vmap-safe and usable *inside* Pallas kernel bodies
  (shifts, masks, selects, integer mul, clz only — no gathers except `lut`).
* Signed members use a sign-magnitude envelope around the unsigned core —
  a legitimate circuit construction that preserves the asymmetric error
  profiles SWAPPER exploits.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "AxMult",
    "exact",
    "trunc",
    "perforate",
    "broken_array",
    "mitchell",
    "drum",
    "lut_mult",
    "make_lut",
    "REGISTRY",
    "get",
    "is_commutative",
]


@dataclasses.dataclass(frozen=True)
class AxMult:
    """An M-bit approximate multiplier.

    ``fn(a, b)`` maps int32 M-bit operands to the (approximate) 2M-bit
    product carried in uint32 (unsigned) or int32 (signed) lanes.
    """

    name: str
    bits: int
    signed: bool
    fn: Callable = dataclasses.field(hash=False, compare=False)
    commutative: Optional[bool] = None  # None = unknown / check empirically

    def __call__(self, a, b):
        return self.fn(a, b)

    @property
    def out_dtype(self):
        return jnp.int32 if self.signed else jnp.uint32

    def exact_product(self, a, b):
        """The precise reference product for these operands."""
        if self.signed:
            return (a.astype(jnp.int32) * b.astype(jnp.int32)).astype(jnp.int32)
        au = a.astype(jnp.uint32)
        bu = b.astype(jnp.uint32)
        return (au * bu).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _at_least_one(xu):
    """max(xu, 1) for uint32 lanes, written as a select: Mosaic (the TPU
    kernel compiler) has no unsigned max."""
    return jnp.where(xu == 0, jnp.uint32(1), xu)


def _mask(m: int) -> int:
    return (1 << m) - 1


def _to_u32(x):
    return x.astype(jnp.uint32)


def _sign_mag(a, bits):
    """Two's complement -> (sign, magnitude) with magnitude in uint32."""
    a = a.astype(jnp.int32)
    neg = a < 0
    mag = jnp.where(neg, -a, a).astype(jnp.uint32)
    return neg, mag


def _apply_sign(neg_a, neg_b, p_u32):
    neg = jnp.logical_xor(neg_a, neg_b)
    p = p_u32.astype(jnp.int32)
    return jnp.where(neg, -p, p).astype(jnp.int32)


def _signed_envelope(core_u):
    """Wrap an unsigned-core multiplier into a sign-magnitude signed one."""

    def fn(a, b):
        na, ma = _sign_mag(a, 32)
        nb, mb = _sign_mag(b, 32)
        return _apply_sign(na, nb, core_u(ma, mb))

    return fn


# ---------------------------------------------------------------------------
# families
# ---------------------------------------------------------------------------

def exact(bits: int, signed: bool = False) -> AxMult:
    if signed:
        fn = lambda a, b: (a.astype(jnp.int32) * b.astype(jnp.int32)).astype(jnp.int32)
    else:
        fn = lambda a, b: (_to_u32(a) * _to_u32(b)).astype(jnp.uint32)
    return AxMult(f"mul{bits}{'s' if signed else 'u'}_exact", bits, signed, fn, True)


def trunc(bits: int, ka: int, kb: int, signed: bool = False) -> AxMult:
    """Operand-truncation multiplier: zero the low ``ka`` bits of A and the
    low ``kb`` bits of B before an exact multiply.  Non-commutative iff
    ``ka != kb`` (the canonical asymmetric AxIC)."""
    mka = ~np.uint32(_mask(ka)) & np.uint32(0xFFFFFFFF)
    mkb = ~np.uint32(_mask(kb)) & np.uint32(0xFFFFFFFF)

    def core(au, bu):
        return ((au & jnp.uint32(mka)) * (bu & jnp.uint32(mkb))).astype(jnp.uint32)

    fn = _signed_envelope(core) if signed else (lambda a, b: core(_to_u32(a), _to_u32(b)))
    nm = f"mul{bits}{'s' if signed else 'u'}_trunc{ka}_{kb}"
    return AxMult(nm, bits, signed, fn, ka == kb)


def perforate(bits: int, rows: tuple, signed: bool = False) -> AxMult:
    """Partial-product row perforation: PP rows generated by the listed bit
    positions of B are dropped: ``p = A * (B & ~rowmask)``.  Non-commutative
    (rows are tied to operand B)."""
    rowmask = np.uint32(0)
    for r in rows:
        rowmask |= np.uint32(1 << r)
    inv = ~rowmask & np.uint32(0xFFFFFFFF)

    def core(au, bu):
        return (au * (bu & jnp.uint32(inv))).astype(jnp.uint32)

    fn = _signed_envelope(core) if signed else (lambda a, b: core(_to_u32(a), _to_u32(b)))
    nm = f"mul{bits}{'s' if signed else 'u'}_perf" + "_".join(str(r) for r in rows)
    return AxMult(nm, bits, signed, fn, len(rows) == 0)


def broken_array(bits: int, v: int, h: int = 0, signed: bool = False) -> AxMult:
    """Broken-Array Multiplier (BAM).

    Vertical break ``v``: every partial-product cell in column < v is removed
    (column = PP bit position in the final product).  Horizontal break ``h``:
    PP rows with index < h are removed entirely.  Closed form:

        p = sum_{i >= h} b_i * ((A << i) & ~(2^v - 1))

    Strongly non-commutative for v > 0 (cells removed are tied to the row
    operand B and column position).
    """
    cmask = ~np.uint32(_mask(v)) & np.uint32(0xFFFFFFFF)
    M = bits

    def core(au, bu):
        acc = jnp.zeros_like(au, dtype=jnp.uint32)
        for i in range(h, M):
            bi = (bu >> np.uint32(i)) & jnp.uint32(1)
            pp = (au << np.uint32(i)) & jnp.uint32(cmask)
            acc = acc + bi * pp
        return acc

    fn = _signed_envelope(core) if signed else (lambda a, b: core(_to_u32(a), _to_u32(b)))
    nm = f"mul{bits}{'s' if signed else 'u'}_bam_v{v}_h{h}"
    # A vertical-only break is value-commutative (the removed column mass
    # sum_i b_i*2^i*(a mod 2^(v-i)) is symmetric); horizontal breaks (h>0)
    # remove rows tied to operand B and break commutativity.
    return AxMult(nm, bits, signed, fn, h == 0)


def mitchell(bits: int, ta: int = 0, tb: int = 0, signed: bool = False) -> AxMult:
    """Mitchell logarithmic multiplier with operand-specific fraction
    truncation (``ta``/``tb`` low fraction bits dropped for A/B).

    log2(x) ~ k + f where k = floor(log2 x), f = (x - 2^k)/2^k in [0,1).
    p ~ 2^(ka+kb) * (1 + fa + fb)            if fa + fb < 1
      ~ 2^(ka+kb+1) * (fa + fb)              otherwise.

    Fractions carried with F = 16 bits.  Asymmetric truncation (ta != tb)
    makes the circuit non-commutative.  Zero operands produce 0 exactly.
    """
    F = 16

    def _logfrac(xu, t):
        # position of the MSB (x > 0 guaranteed by caller's select)
        k = (jnp.uint32(31) - jax.lax.clz(_at_least_one(xu))).astype(jnp.uint32)
        # fraction f in F-bit fixed point: ((x - 2^k) << F) >> k
        frac = ((xu - (jnp.uint32(1) << k)) << jnp.uint32(F)) >> k
        if t > 0:
            frac = frac & jnp.uint32(~_mask(t) & 0xFFFFFFFF)
        return k, frac

    def core(au, bu):
        ka, fa = _logfrac(au, ta)
        kb, fb = _logfrac(bu, tb)
        ksum = ka + kb
        fsum = fa + fb
        carry = fsum >> jnp.uint32(F)              # 1 if fa+fb >= 1
        kk = ksum + carry
        # p ~ 2^ksum * (1 + fa + fb)         = 2^kk * (1 + fsum)/2^F  (no carry)
        #   ~ 2^(ksum+1) * (fa + fb)         = 2^kk * fsum/2^F        (carry)
        mant = fsum + ((jnp.uint32(1) - carry) << jnp.uint32(F))
        # p = mant * 2^kk / 2^F, exact while kk <= 31
        shift_left = jnp.maximum(kk.astype(jnp.int32) - F, 0).astype(jnp.uint32)
        shift_right = jnp.maximum(F - kk.astype(jnp.int32), 0).astype(jnp.uint32)
        p = (mant << shift_left) >> shift_right
        zero = (au == 0) | (bu == 0)
        return jnp.where(zero, jnp.uint32(0), p).astype(jnp.uint32)

    fn = _signed_envelope(core) if signed else (lambda a, b: core(_to_u32(a), _to_u32(b)))
    nm = f"mul{bits}{'s' if signed else 'u'}_mitch{ta}_{tb}"
    return AxMult(nm, bits, signed, fn, ta == tb)


def drum(bits: int, ka: int, kb: int, signed: bool = False) -> AxMult:
    """DRUM-style dynamic-range unbiased multiplier with per-operand segment
    widths ``ka``/``kb``: keep the ka-bit window below the leading one of A
    (forcing the window LSB to 1 for unbiasing), zero the rest; same for B;
    multiply the short segments exactly and shift back.  Non-commutative iff
    ``ka != kb``."""

    def _segment(xu, k):
        msb = (jnp.uint32(31) - jax.lax.clz(_at_least_one(xu))).astype(jnp.int32)
        sh = jnp.maximum(msb - (k - 1), 0).astype(jnp.uint32)
        seg = (xu >> sh)
        # unbias: set LSB of the segment when we actually truncated
        seg = jnp.where(sh > 0, seg | jnp.uint32(1), seg)
        return seg, sh

    def core(au, bu):
        sa, sha = _segment(au, ka)
        sb, shb = _segment(bu, kb)
        p = (sa * sb) << (sha + shb)
        zero = (au == 0) | (bu == 0)
        return jnp.where(zero, jnp.uint32(0), p).astype(jnp.uint32)

    fn = _signed_envelope(core) if signed else (lambda a, b: core(_to_u32(a), _to_u32(b)))
    nm = f"mul{bits}{'s' if signed else 'u'}_drum{ka}_{kb}"
    return AxMult(nm, bits, signed, fn, ka == kb)


# ---------------------------------------------------------------------------
# LUT-defined multipliers (EvoApprox compatibility path, 8-bit)
# ---------------------------------------------------------------------------

def make_lut(mult: AxMult) -> np.ndarray:
    """Materialize an 8-bit multiplier as a 65536-entry product table indexed
    by ``(a8 << 8) | b8`` where a8/b8 are the raw M-bit patterns."""
    assert mult.bits == 8, "LUT path is defined for 8-bit multipliers"
    vals = np.arange(256, dtype=np.int64)
    if mult.signed:
        # raw patterns -> two's complement values
        ops = np.where(vals < 128, vals, vals - 256).astype(np.int32)
    else:
        ops = vals.astype(np.int32)
    A = jnp.asarray(np.repeat(ops, 256))
    B = jnp.asarray(np.tile(ops, 256))
    out = np.asarray(jax.jit(mult.fn)(A, B))
    return out.astype(np.int32 if mult.signed else np.uint32)


def lut_mult(name: str, table: np.ndarray, signed: bool) -> AxMult:
    """An arbitrary 8-bit multiplier defined by its 65536-entry table."""
    tbl = jnp.asarray(table)

    def fn(a, b):
        ai = (a.astype(jnp.int32) & 0xFF).astype(jnp.int32)
        bi = (b.astype(jnp.int32) & 0xFF).astype(jnp.int32)
        idx = (ai << 8) | bi
        return jnp.take(tbl, idx, axis=0)

    return AxMult(name, 8, signed, fn, None)


# ---------------------------------------------------------------------------
# registry — a fixed library of named circuits (our EvoApproxLib analog)
# ---------------------------------------------------------------------------

def _build_registry():
    reg = {}

    def add(m: AxMult):
        reg[m.name] = m

    for bits in (8, 12, 16):
        for signed in (False, True):
            add(exact(bits, signed))
            q = bits // 4
            h = bits // 2
            # commutative approximations (controls, Table I right column analog)
            add(trunc(bits, q, q, signed))
            add(mitchell(bits, 0, 0, signed))
            add(drum(bits, h, h, signed))
            # non-commutative members (the SWAPPER targets)
            add(trunc(bits, 0, h, signed))
            add(trunc(bits, q, h, signed))
            add(trunc(bits, 1, h + 1, signed))
            add(perforate(bits, tuple(range(0, q)), signed))
            add(perforate(bits, tuple(range(1, h, 2)), signed))
            add(broken_array(bits, v=h, h=0, signed=signed))
            add(broken_array(bits, v=q, h=1, signed=signed))
            # fraction truncations are on the F=16 internal scale: keep only
            # the top (16-t) fraction bits, asymmetric per operand
            add(mitchell(bits, 13, 0, signed))
            add(mitchell(bits, 10, 13, signed))
            add(drum(bits, q + 1, h, signed))
            add(drum(bits, 2, bits - 2, signed))
    return reg


REGISTRY = _build_registry()


def get(name: str) -> AxMult:
    return REGISTRY[name]


def is_commutative(mult: AxMult, probe: int = 4096, seed: int = 0) -> bool:
    """Empirically check commutativity on ``probe`` random operand pairs
    (exact for 8-bit multipliers where we sweep everything in tests)."""
    rng = np.random.default_rng(seed)
    lo, hi = (
        (-(1 << (mult.bits - 1)), 1 << (mult.bits - 1)) if mult.signed else (0, 1 << mult.bits)
    )
    a = jnp.asarray(rng.integers(lo, hi, probe, dtype=np.int64).astype(np.int32))
    b = jnp.asarray(rng.integers(lo, hi, probe, dtype=np.int64).astype(np.int32))
    f = jax.jit(mult.fn)
    return bool(jnp.all(f(a, b) == f(b, a)))
