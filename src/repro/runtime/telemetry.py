"""Streaming operand/error telemetry for the adaptive SWAPPER runtime.

Two halves:

* **In-graph summaries** (:func:`operand_summary`) — tiny fixed-shape
  statistics computed on sampled int8 operands inside the compiled step:
  per-bit occupancy counts of both operands, exact absolute-error limb sums
  of the *live* policy (same 16-bit-limb scheme as ``core/metrics.py``), and
  a small operand sample that feeds the controller's re-tune buffer.  Cheap
  enough to leave on in serving: a handful of shifts/masks and reductions
  over ≤ ``TELEMETRY_SAMPLE`` elements per projection.

* **Host accumulators** (:class:`Telemetry`) — exponentially-decayed bit
  occupancy probabilities (the drift signal) plus an exact cumulative
  :class:`~repro.core.metrics.ErrorStats` window recombined from the limb
  sums, per target.

* **Admission control** (:class:`TelemetryQuarantine`) — sanitization in
  front of the accumulators: NaN/Inf records, records violating the
  summary's structural invariants (counts bounded by the sample size,
  operand codes bounded by the multiplier width), and — optionally —
  robust-z step-MAE outliers are quarantined BEFORE they can reach ring
  buffers or drift scores, so one poisoned shard cannot trigger (or skew)
  a fleet retune.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.metrics import ErrorStats, abs_err
from repro.core.multipliers import AxMult
from repro.core.swapper import NO_SWAP_TRIPLE, apply_swapper_dyn

__all__ = [
    "TELEMETRY_SAMPLE",
    "RETUNE_SAMPLE",
    "TILE_TELEMETRY_SAMPLE",
    "TILE_RETUNE_SAMPLE",
    "TILE_KEY_SUFFIX",
    "SUM_FIELDS",
    "MAX_FIELDS",
    "SAMPLE_FIELDS",
    "tile_key",
    "is_tile_key",
    "base_target",
    "operand_summary",
    "tile_summary",
    "combine_records",
    "TargetTelemetry",
    "TargetTileTelemetry",
    "Telemetry",
    "TelemetryQuarantine",
]

TELEMETRY_SAMPLE = 2048   # elements of each operand entering the bit/error stats
RETUNE_SAMPLE = 512       # operand sample exported per call for the re-tune buffer
TILE_TELEMETRY_SAMPLE = 512  # per-row-tile elements entering the tile bit stats
TILE_RETUNE_SAMPLE = 256     # per-row-tile operand sample for the tile buffers

# Tile records travel the same scope -> controller -> fleet plumbing as the
# scalar operand summaries, keyed by ``<target>@tiles`` (no "/" so the
# hierarchical fallback chain of runtime.scope never strips it).
TILE_KEY_SUFFIX = "@tiles"

# Cross-shard reduction classes of the summary fields (consumed by
# ``fleet.collect``): occupancy/error/limb counters are plain sums (psum over
# the mesh batch axes is exact), the worst-case error is a max, and operand
# samples concatenate (all-gather).  With TELEMETRY_SAMPLE=2048 the uint32
# limb sums stay overflow-free up to 32 shards (32 * 2048 * 0xFFFF < 2^32).
# The tile_* fields are the per-row-tile record (``tile_summary``): counts
# psum like their scalar counterparts; the per-tile samples are stored
# *sample-major* — (TILE_RETUNE_SAMPLE, gm), tiles on the LAST axis — so the
# shared axis-(-2) concatenation rule of combine_records / fleet.collect
# extends each tile's sample column instead of inventing new tiles.  The
# per-tile error limbs (tile_err_lo/hi, one uint32 per tile over a
# TILE_TELEMETRY_SAMPLE-element sample) psum with the same 32-shard
# headroom (32 * 512 * 0xFFFF < 2^32).
SUM_FIELDS = ("bits_a", "bits_b", "neg_a", "neg_b", "n",
              "err_lo", "err_hi", "err_cnt",
              "tile_bits_a", "tile_neg_a", "tile_n",
              "tile_err_lo", "tile_err_hi")
MAX_FIELDS = ("err_max",)
SAMPLE_FIELDS = ("a_smp", "b_smp", "tile_a_smp", "tile_b_smp")


def tile_key(target: str) -> str:
    """Record key the per-tile summary of ``target`` is collected under."""
    return target + TILE_KEY_SUFFIX


def is_tile_key(key: str) -> bool:
    return key.endswith(TILE_KEY_SUFFIX)


def base_target(key: str) -> str:
    """Inverse of :func:`tile_key` (identity for non-tile keys)."""
    return key[:-len(TILE_KEY_SUFFIX)] if is_tile_key(key) else key


def _flat_sample(x, n: int):
    """First ``n`` elements of ``x`` flattened, tiled cyclically when the
    tensor is smaller (keeps shapes static and stackable across call sites
    without zero-padding that would bias the statistics)."""
    flat = x.reshape(-1)
    if flat.shape[0] < n:
        reps = -(-n // flat.shape[0])
        flat = jnp.concatenate([flat] * reps)
    return flat[:n]


def _bit_counts(v_i32, bits: int):
    """(bits,) float32 count of set **magnitude** bits per position.  Raw
    two's-complement bits are a poor drift statistic for signed operands: a
    symmetric distribution shrinking toward zero keeps every high bit at
    ~P(0.5) (negative values sign-extend to ones), hiding the shift.  The
    sign frequency is tracked separately in the summary."""
    shifts = jnp.arange(bits, dtype=jnp.int32)
    mag = jnp.abs(v_i32)
    return jnp.sum((mag[:, None] >> shifts) & 1, axis=0).astype(jnp.float32)


def operand_summary(xq, wq, mult: AxMult, dyn, gate=None) -> dict:
    """Fixed-shape telemetry record for one approximate projection call.

    ``xq``/``wq`` are the quantized integer operands, ``dyn`` the traced
    (op_is_a, bit, value) triple currently applied.  All outputs are scalars
    or small vectors so the host transfer stays negligible.

    ``gate`` — optional traced boolean scalar (telemetry decimation): when
    False at runtime the whole summary compute is skipped via ``lax.cond``
    and an all-zero record of identical structure is produced instead.  The
    host only observes gated-on steps, so the zeros never reach the
    accumulators.
    """
    if gate is not None:
        import jax

        # the weight enters the gated branch as the elements it samples: a
        # weight that is a slice of a larger array is then not copied whole
        wq = _flat_sample(wq, max(TELEMETRY_SAMPLE, RETUNE_SAMPLE))
        impl = lambda: operand_summary(xq, wq, mult, dyn)
        shapes = jax.eval_shape(impl)
        zeros = lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return jax.lax.cond(gate, impl, zeros)
    bits = mult.bits
    a = _flat_sample(xq, TELEMETRY_SAMPLE).astype(jnp.int32)
    b = _flat_sample(wq, TELEMETRY_SAMPLE).astype(jnp.int32)

    # live-policy error sample (exact limb sums, as in core/tuning._row_stats)
    approx = apply_swapper_dyn(mult, a, b, dyn[0], dyn[1], dyn[2])
    e = abs_err(approx, mult.exact_product(a, b), mult.signed)
    lo = jnp.sum(e & jnp.uint32(0xFFFF), dtype=jnp.uint32)
    hi = jnp.sum(e >> jnp.uint32(16), dtype=jnp.uint32)

    return dict(
        bits_a=_bit_counts(a, bits),
        bits_b=_bit_counts(b, bits),
        neg_a=jnp.sum((a < 0).astype(jnp.int32)).astype(jnp.float32),
        neg_b=jnp.sum((b < 0).astype(jnp.int32)).astype(jnp.float32),
        n=jnp.int32(TELEMETRY_SAMPLE),
        err_lo=lo,
        err_hi=hi,
        err_max=jnp.max(e),
        err_cnt=jnp.sum((e != 0).astype(jnp.int32)),
        a_smp=_flat_sample(xq, RETUNE_SAMPLE),
        b_smp=_flat_sample(wq, RETUNE_SAMPLE),
    )


def tile_summary(xq, wq, mult: AxMult, gm: int, gate=None, dyn=None,
                 bits_from=None) -> dict:
    """Per-row-tile telemetry record for one approximate projection call —
    the host-side twin of the kernels' in-reduction ``tile_hist`` output,
    shaped for the adaptive loop rather than the physical block layout.

    The flattened row space of ``xq`` (tokens) is split into ``gm`` row
    tiles by the SAME partition the execution paths apply config tiles with
    (``core.tiling.rowtile_*`` — observed rows and configured rows must
    coincide; ``min(gm, rows)`` tiles are emitted when the call is smaller
    than the granularity, and when the floor span does not divide the row
    count the last tile's few absorbed remainder rows are left unsampled —
    shapes stay static and no tile's statistic is ever fabricated from
    another tile's rows).  Per tile: magnitude-bit occupancy
    counts + sign count of a ``TILE_TELEMETRY_SAMPLE``-element sample (the
    per-tile drift statistic) and a ``TILE_RETUNE_SAMPLE``-element operand
    sample feeding the controller's per-tile re-tune buffers.  ``wq`` is
    shared by every row tile of a projection, so its sample is emitted once
    and broadcast — tile re-tunes pair each tile's A sample against it.

    Samples are laid out (sample, tile) — tiles on the last axis — so the
    fleet's axis-(-2) all-gather/concat rule applies unchanged.  ``gate`` is
    the same traced decimation boolean as :func:`operand_summary`.

    ``dyn`` — the traced live config: a (3,) triple, a (gm, 1, 3) row-tile
    grid, or None (no-swap).  It selects the per-tile triple the exact
    error-limb sums (``tile_err_lo``/``tile_err_hi``, one uint32 pair per
    tile) are computed under, so per-tile QoR attribution sees the error of
    the policy actually applied to each tile.

    ``bits_from`` — optional kernel-supplied drift statistic: a
    ``(tile_bits_a, tile_neg_a, tile_n)`` triple aggregated from the Pallas
    kernel's in-reduction ``tile_hist`` output (``quant.ax`` kernel-hist
    path).  When given, the sampled bit-count pass over ``xq`` is skipped —
    the kernel already counted every element of every tile as a side
    product of the matmul — and the record carries FULL per-tile counts
    (``tile_n`` = the tile's true element count instead of the
    ``TILE_TELEMETRY_SAMPLE`` constant).  The counts/n normalization the
    accumulators apply makes both forms the same statistic; the kernel form
    is exact rather than sampled.
    """
    if gate is not None:
        import jax

        # as in operand_summary: only the sampled weight enters the branch
        wq = _flat_sample(wq, max(TILE_TELEMETRY_SAMPLE, TILE_RETUNE_SAMPLE))
        impl = lambda: tile_summary(xq, wq, mult, gm, dyn=dyn,
                                    bits_from=bits_from)
        shapes = jax.eval_shape(impl)
        zeros = lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return jax.lax.cond(gate, impl, zeros)
    import jax

    from repro.core.tiling import rowtile_count, rowtile_span

    bits = mult.bits
    x2d = xq.reshape(-1, xq.shape[-1])
    M = x2d.shape[0]
    g = rowtile_count(M, gm)
    rows_per = rowtile_span(M, gm)
    # g * rows_per <= M (floor span): the last tile's absorbed remainder
    # rows fall outside the equal reshape and go unsampled
    tiles = x2d[:g * rows_per].reshape(g, rows_per * x2d.shape[-1])
    a_t = jax.vmap(lambda v: _flat_sample(v, TILE_TELEMETRY_SAMPLE))(tiles)
    a_i32 = a_t.astype(jnp.int32)
    if bits_from is not None:
        kb, kn, kc = bits_from
        assert kb.shape == (g, bits) and kn.shape == (g,) and kc.shape == (g,), (
            kb.shape, kn.shape, kc.shape, (g, bits))
    smp = jax.vmap(lambda v: _flat_sample(v, TILE_RETUNE_SAMPLE))(tiles)
    b_smp = _flat_sample(wq, TILE_RETUNE_SAMPLE)

    # per-tile exact error limbs of the live policy: each tile's A sample
    # against the shared B sample under the triple configured FOR that tile
    if dyn is None:
        trip = jnp.broadcast_to(
            jnp.asarray(NO_SWAP_TRIPLE, jnp.int32), (g, 3))
    else:
        dyn = jnp.asarray(dyn, jnp.int32)
        if dyn.ndim == 3:
            # row-tile grid: telemetry tiles and config tiles share the
            # rowtile_* partition, so tile i observes config row i (clamped
            # when the call emits fewer tiles than the grid)
            trip = dyn[:, 0, :][jnp.minimum(jnp.arange(g), dyn.shape[0] - 1)]
        else:
            trip = jnp.broadcast_to(dyn.reshape(3), (g, 3))
    b_i32 = _flat_sample(wq, TILE_TELEMETRY_SAMPLE).astype(jnp.int32)

    def _tile_err(a_row, t):
        approx = apply_swapper_dyn(mult, a_row, b_i32, t[0], t[1], t[2])
        e = abs_err(approx, mult.exact_product(a_row, b_i32), mult.signed)
        return (jnp.sum(e & jnp.uint32(0xFFFF), dtype=jnp.uint32),
                jnp.sum(e >> jnp.uint32(16), dtype=jnp.uint32))

    tile_err_lo, tile_err_hi = jax.vmap(_tile_err)(a_i32, trip)
    if bits_from is not None:
        tile_bits_a = kb.astype(jnp.float32)
        tile_neg_a = kn.astype(jnp.float32)
        tile_n = kc.astype(jnp.int32)
    else:
        tile_bits_a = jax.vmap(lambda v: _bit_counts(v, bits))(a_i32)
        tile_neg_a = jnp.sum((a_i32 < 0), axis=1).astype(jnp.float32)
        tile_n = jnp.full((g,), TILE_TELEMETRY_SAMPLE, jnp.int32)
    return dict(
        tile_bits_a=tile_bits_a,                                      # (g, bits)
        tile_neg_a=tile_neg_a,                                        # (g,)
        tile_n=tile_n,
        tile_err_lo=tile_err_lo,                                      # (g,)
        tile_err_hi=tile_err_hi,                                      # (g,)
        tile_a_smp=smp.T,                                             # (S, g)
        tile_b_smp=jnp.broadcast_to(b_smp[:, None],
                                    (TILE_RETUNE_SAMPLE, g)),         # (S, g)
    )


def combine_records(shard_records) -> Dict[str, Dict[str, np.ndarray]]:
    """Host-side reference combiner: fold per-shard record trees into the
    fleet record (sum/max/concat per the field classes above).  This is the
    oracle the in-graph ``fleet.collect.aggregate_records`` psum path is
    tested bit-exactly against."""
    out: Dict[str, Dict[str, np.ndarray]] = {}
    for records in shard_records:
        for target, rec in records.items():
            acc = out.get(target)
            if acc is None:
                out[target] = {k: np.asarray(v).copy() for k, v in rec.items()}
                continue
            for k, v in rec.items():
                v = np.asarray(v)
                if k in MAX_FIELDS:
                    acc[k] = np.maximum(acc[k], v)
                elif k in SAMPLE_FIELDS:
                    acc[k] = np.concatenate([acc[k], v], axis=-2)
                else:
                    acc[k] = acc[k] + v
    return out


# ---------------------------------------------------------------------------
# host side
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TargetTelemetry:
    """Decayed + exact accumulators for one projection target."""

    bits: int
    decay: float
    n_steps: int = 0
    # (2, bits+1) EW occupancy: per-operand magnitude-bit P(bit==1) columns
    # plus a trailing sign-frequency column (the drift statistic)
    bit_probs: Optional[np.ndarray] = None
    ew_mae: Optional[float] = None             # EW-decayed per-step MAE
    stats: ErrorStats = dataclasses.field(default_factory=ErrorStats)

    def update(self, rec: Dict[str, np.ndarray]) -> None:
        """``rec`` holds stacked per-call arrays for one step (leading axis =
        calls of this target inside the step)."""
        n = float(np.sum(rec["n"]))
        probs = np.stack([
            np.concatenate([np.sum(rec["bits_a"], axis=0),
                            np.sum(np.atleast_1d(rec["neg_a"]), keepdims=True)]),
            np.concatenate([np.sum(rec["bits_b"], axis=0),
                            np.sum(np.atleast_1d(rec["neg_b"]), keepdims=True)]),
        ]) / max(n, 1.0)

        step = ErrorStats()
        for lo, hi, mx, cnt, cn in zip(
            np.atleast_1d(rec["err_lo"]), np.atleast_1d(rec["err_hi"]),
            np.atleast_1d(rec["err_max"]), np.atleast_1d(rec["err_cnt"]),
            np.atleast_1d(rec["n"]),
        ):
            step.add_limbs(int(cn), int(lo), int(hi), int(mx), int(cnt), 0.0, 0.0)
        self.stats.n += step.n
        self.stats.sum_abs += step.sum_abs
        self.stats.max_abs = max(self.stats.max_abs, step.max_abs)
        self.stats.count_neq += step.count_neq

        d = self.decay
        if self.bit_probs is None:
            self.bit_probs = probs
            self.ew_mae = step.mae
        else:
            self.bit_probs = (1.0 - d) * self.bit_probs + d * probs
            self.ew_mae = (1.0 - d) * self.ew_mae + d * step.mae
        self.n_steps += 1

    def snapshot(self) -> dict:
        return dict(
            bit_probs=None if self.bit_probs is None else self.bit_probs.copy(),
            ew_mae=self.ew_mae,
            mae=self.stats.mae,
            wce=self.stats.wce,
            ep=self.stats.ep,
            n=self.stats.n,
            n_steps=self.n_steps,
        )


@dataclasses.dataclass
class TargetTileTelemetry:
    """Decayed per-row-tile accumulators for one projection target's
    ``tile_summary`` records (collected under ``tile_key(target)``).

    ``bit_probs`` is a (gm, bits+1) matrix — per row tile, the EW-decayed
    magnitude-bit P(bit==1) columns plus the trailing sign frequency; the
    same sufficient statistic the scalar drift detector uses, one row per
    tile.  The generic :class:`~repro.runtime.drift.DriftDetector` scores it
    unchanged (mean |delta| over the matrix), so a shift confined to one of
    ``gm`` tiles reaches the threshold diluted by ~1/gm — size tile drift
    thresholds accordingly (mirrors the fleet's 1/N shard dilution)."""

    bits: int
    decay: float
    n_steps: int = 0
    bit_probs: Optional[np.ndarray] = None      # (gm, bits+1)
    ew_mae: Optional[np.ndarray] = None         # (gm,) EW per-tile step MAE

    def update(self, rec: Dict[str, np.ndarray]) -> None:
        """``rec`` holds stacked per-call arrays (leading axis = calls of
        this target inside the observed step)."""
        bits_a = np.sum(np.asarray(rec["tile_bits_a"]), axis=0)    # (gm, bits)
        neg_a = np.sum(np.asarray(rec["tile_neg_a"]), axis=0)      # (gm,)
        n = np.maximum(np.sum(np.asarray(rec["tile_n"]), axis=0), 1.0)
        probs = np.concatenate([bits_a, neg_a[:, None]], axis=-1) / n[:, None]
        if self.bit_probs is None or self.bit_probs.shape != probs.shape:
            self.bit_probs = probs
            self.ew_mae = None
        else:
            d = self.decay
            self.bit_probs = (1.0 - d) * self.bit_probs + d * probs
        if "tile_err_lo" in rec:
            lo = np.sum(np.asarray(rec["tile_err_lo"], np.float64), axis=0)
            hi = np.sum(np.asarray(rec["tile_err_hi"], np.float64), axis=0)
            mae = (lo + hi * 65536.0) / n
            if self.ew_mae is None or self.ew_mae.shape != mae.shape:
                self.ew_mae = mae
            else:
                self.ew_mae = (1.0 - self.decay) * self.ew_mae \
                    + self.decay * mae
        self.n_steps += 1

    def snapshot(self) -> dict:
        return dict(
            bit_probs=None if self.bit_probs is None else self.bit_probs.copy(),
            ew_mae=None if self.ew_mae is None else self.ew_mae.copy(),
            n_steps=self.n_steps,
        )


class Telemetry:
    """Per-target streaming telemetry over the records a scope collected.
    Records keyed ``<target>@tiles`` route to per-row-tile accumulators
    (:class:`TargetTileTelemetry`); everything else to the scalar
    :class:`TargetTelemetry`."""

    def __init__(self, bits: int, decay: float = 0.2):
        self.bits = bits
        self.decay = decay
        self.targets: Dict[str, TargetTelemetry] = {}
        self.tile_targets: Dict[str, TargetTileTelemetry] = {}

    def update(self, records: Dict[str, Dict[str, np.ndarray]]) -> None:
        for target, rec in records.items():
            if is_tile_key(target):
                tt = self.tile_targets.get(target)
                if tt is None:
                    tt = self.tile_targets[target] = TargetTileTelemetry(
                        self.bits, self.decay)
                tt.update(rec)
                continue
            tt = self.targets.get(target)
            if tt is None:
                tt = self.targets[target] = TargetTelemetry(self.bits, self.decay)
            tt.update(rec)

    def snapshot(self) -> Dict[str, dict]:
        out = {t: tt.snapshot() for t, tt in self.targets.items()}
        out.update({t: tt.snapshot() for t, tt in self.tile_targets.items()})
        return out

    def describe(self) -> str:
        parts = []
        for t, tt in sorted(self.targets.items()):
            parts.append(f"{t}: ew_mae={tt.ew_mae:.2f} mae={tt.stats.mae:.2f} "
                         f"n={tt.stats.n}")
        for t, tt in sorted(self.tile_targets.items()):
            gm = 0 if tt.bit_probs is None else tt.bit_probs.shape[0]
            parts.append(f"{t}: tiles={gm} steps={tt.n_steps}")
        return "telemetry " + " | ".join(parts) if parts else "telemetry <empty>"


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

_QUARANTINED = obs.default_registry().counter(
    "repro_telemetry_quarantined_total",
    "telemetry records quarantined before the accumulators, by target and "
    "reason (nonfinite / bounds / outlier)")


class TelemetryQuarantine:
    """Record sanitization in front of the accumulators and ring buffers.

    Three independent checks, cheapest first:

    1. **nonfinite** — any NaN/Inf in a float field (corrupt shard math,
       torn transfers);
    2. **bounds** — structural invariants every honest ``operand_summary``
       / ``tile_summary`` record satisfies by construction: per-bit
       occupancy counts cannot exceed the total sample count, error-limb
       sums are bounded by ``n * 0xFFFF``, the nonzero-error count by
       ``n``, and exported operand codes by the multiplier's ``2**bits``
       magnitude range;
    3. **outlier** (``z_threshold`` set) — robust z-score of the record's
       step MAE against the trailing per-target history (median/MAD):
       finite, in-bounds, but absurd records — the "one shard went insane"
       case.  Quarantined records are NOT appended to the history, so a
       poison burst cannot drag the baseline toward itself.

    Records with ``n == 0`` pass untouched: the fused decode's gated-off
    slots legitimately emit all-zero records, and vetoing them would change
    accumulator trajectories for honest traffic.
    """

    REASONS = ("nonfinite", "bounds", "outlier")

    def __init__(self, bits: int, z_threshold: Optional[float] = None,
                 history: int = 64, min_history: int = 8):
        self.bits = int(bits)
        self.z_threshold = z_threshold
        self.history = int(history)
        self.min_history = int(min_history)
        self._mae_hist: Dict[str, collections.deque] = {}
        self.quarantined = 0
        self.by_reason: Dict[str, int] = {}

    # -- checks --------------------------------------------------------
    def check(self, target: str, rec: Dict[str, np.ndarray]) -> Optional[str]:
        """The quarantine reason for this record, or None when admissible."""
        for v in rec.values():
            v = np.asarray(v)
            if np.issubdtype(v.dtype, np.floating) and not bool(
                    np.all(np.isfinite(v))):
                return "nonfinite"
        tile = is_tile_key(target)
        n = float(np.sum(np.asarray(rec["tile_n" if tile else "n"],
                                    np.float64)))
        if n <= 0:
            return None                      # gated-off zero record: vacuous
        lim = float(2 ** self.bits)
        for k in ("bits_a", "bits_b") if not tile else ("tile_bits_a",):
            if k in rec:
                counts = np.asarray(rec[k], np.float64)
                counts = counts.reshape(-1, counts.shape[-1]).sum(axis=0)
                if float(counts.max(initial=0.0)) > n + 0.5:
                    return "bounds"
        for k in ("a_smp", "b_smp", "tile_a_smp", "tile_b_smp"):
            if k in rec and np.abs(
                    np.asarray(rec[k], np.float64)).max(initial=0.0) > lim:
                return "bounds"
        if tile and "tile_err_lo" in rec:
            tn = np.asarray(rec["tile_n"], np.float64)
            tn = tn.reshape(-1, tn.shape[-1]).sum(axis=0)
            for k in ("tile_err_lo", "tile_err_hi"):
                limb = np.asarray(rec[k], np.float64)
                limb = limb.reshape(-1, limb.shape[-1]).sum(axis=0)
                if np.any(limb > tn * 0xFFFF + 0.5):
                    return "bounds"
        if not tile:
            lo = float(np.sum(np.asarray(rec["err_lo"], np.float64)))
            hi = float(np.sum(np.asarray(rec["err_hi"], np.float64)))
            cnt = float(np.sum(np.asarray(rec["err_cnt"], np.float64)))
            if lo > n * 0xFFFF or hi > n * 0xFFFF or cnt > n + 0.5:
                return "bounds"
            if self.z_threshold is not None:
                mae = (lo + hi * 65536.0) / n
                hist = self._mae_hist.setdefault(
                    target, collections.deque(maxlen=self.history))
                if len(hist) >= self.min_history:
                    arr = np.asarray(hist, np.float64)
                    med = float(np.median(arr))
                    mad = float(np.median(np.abs(arr - med)))
                    # the 0.05*med floor keeps a near-zero-MAD history from
                    # flagging ordinary drift as an outlier (scale-relative)
                    z = abs(mae - med) / (1.4826 * mad + 0.05 * med + 1e-9)
                    if z > self.z_threshold:
                        return "outlier"     # and keep it OUT of the history
                hist.append(mae)
        return None

    def filter(self, records: Dict[str, Dict[str, np.ndarray]]
               ) -> Tuple[Dict[str, Dict[str, np.ndarray]],
                          List[Tuple[str, str]]]:
        """(admitted records, [(target, reason) dropped]) — the controller
        feeds only the admitted half to accumulators/buffers/drift."""
        admitted, dropped = {}, []
        for target, rec in records.items():
            reason = self.check(target, rec)
            if reason is None:
                admitted[target] = rec
            else:
                dropped.append((target, reason))
                self.quarantined += 1
                self.by_reason[reason] = self.by_reason.get(reason, 0) + 1
                _QUARANTINED.inc(1, target=target, reason=reason)
        return admitted, dropped
