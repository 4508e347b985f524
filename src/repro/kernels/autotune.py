"""Kernel autotuner: measured-wall schedule search + fleet-shared tables.

ROADMAP direction 4.  The hand-picked ``(bm, bn, k_slab)`` defaults lost
the raw-speed story (BENCH_9: slab-vectorized kernels at 0.79–0.81x of
their rank-1 baseline on this host) — so the schedule becomes *searched
data* instead of a constant:

* :func:`candidate_schedules` enumerates the schedule space for one
  dispatch signature — block caps x ``k_slab`` x grid order for the Pallas
  backend, limb strategy (+ ``noswap_fast``) for mxu.  The historical
  default and the rank-1 baseline are always candidates, so the measured
  winner can never be slower than what shipped before (the bench-gate
  ``>= 1.0`` floors rely on exactly this: tuned wall = min over a
  candidate set that *contains* the baseline, timed in the same
  interleaved loop).
* :func:`sweep` times candidates interleaved round-robin (best-of-reps
  per candidate, one warmup dispatch to exclude compiles) so drift hits
  every candidate equally.
* :func:`tune_signature` / :func:`tune_table` run the search on real
  dispatches (`kernels.ops` / `quant.ax`) over synthetic int8 operands of
  the signature's shape and collect winners into a
  :class:`~repro.kernels.schedule.ScheduleTable`.
* :class:`ScheduleStore` / :class:`ScheduleReader` persist tables with
  the PolicyStore file protocol — versioned immutable JSON, atomic
  fsync'd CURRENT pointer, HEARTBEAT ``mtime_ns == version`` poll fast
  path, degrade-never-crash reads — so fleet replicas share one tuning
  result; a reader adoption calls
  :func:`~repro.kernels.schedule.install_table`, which is zero-retrace by
  construction (resolution is host-side at trace time).

CLI::

    python -m repro.kernels.autotune --quick --out schedules.json \
        --shapes 128x128x128 --backends kernel,mxu [--store DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro import obs
from repro.core.tiling import largest_divisor_leq

from .schedule import KernelSchedule, ScheduleTable, install_table, sig_key

__all__ = [
    "candidate_schedules",
    "sweep",
    "tune_signature",
    "tune_table",
    "ScheduleStore",
    "ScheduleReader",
    "DEFAULT_OPS",
]

_READ_ERRS = (OSError, ValueError, KeyError, TypeError, AssertionError)

# ops tuned per backend by default (the quant layer's mxu forms have their
# own signature ops because their candidate axes differ from the kernels')
DEFAULT_OPS = {
    "kernel": ("matmul", "matmul_grid"),
    "mxu": ("int_static", "int_dyn"),
}


# ---------------------------------------------------------------------------
# candidate generation
# ---------------------------------------------------------------------------

def _dedup(scheds: Sequence[KernelSchedule]) -> List[KernelSchedule]:
    seen, out = set(), []
    for s in scheds:
        if s not in seen:
            seen.add(s)
            out.append(s)
    return out


def candidate_schedules(backend: str, M: int, K: int, N: int,
                        op: str = "matmul",
                        quick: bool = False) -> List[KernelSchedule]:
    """The schedule space swept for one dispatch signature.

    Kernel backend: ``k_slab`` x ``grid_order`` at the default block caps,
    plus block-cap variants at the default slab — block caps are clamped to
    the largest divisor of the logical dim so every candidate dispatches on
    the un-padded operands.  Candidate 0 is always the historical default
    (``k_slab=None`` auto-slab) and the rank-1 baseline (``k_slab=1``) is
    always present.  mxu: limb strategy ("stacked" vs "split"), plus the
    ``noswap_fast`` branch for the dyn op.  ``quick`` halves the kernel
    space (CI sweeps)."""
    if backend == "mxu":
        out = [KernelSchedule(backend="mxu", limbs="stacked"),
               KernelSchedule(backend="mxu", limbs="split")]
        if op == "int_dyn":
            out.append(KernelSchedule(backend="mxu", limbs="stacked",
                                      noswap_fast=True))
        return out
    assert backend == "kernel", backend
    # block caps clamped to divisors of the logical dims so every candidate
    # dispatches the un-padded operands (the kernels layer requires
    # divisibility; the quant layer clamps+pads the same way)
    bm0 = largest_divisor_leq(M, 128)
    bn0 = largest_divisor_leq(N, 128)
    bk0 = largest_divisor_leq(K, 128)
    base = KernelSchedule(backend="kernel", bm=bm0, bn=bn0, bk=bk0)
    slabs: List[Optional[int]] = [None, 1, 4, 8] if quick else [None, 1, 2, 4, 8, 16]
    out = [base]
    for ks in slabs:
        for go in ("mn", "nm"):
            out.append(KernelSchedule(backend="kernel", bm=bm0, bn=bn0,
                                      bk=bk0, k_slab=ks, grid_order=go))
    if not quick:
        for cap in (32, 64):
            bm = largest_divisor_leq(M, cap)
            bn = largest_divisor_leq(N, cap)
            if (bm, bn) != (bm0, bn0):
                out.append(KernelSchedule(backend="kernel", bm=bm, bn=bn,
                                          bk=bk0))
    return _dedup(out)


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def sweep(fns: Sequence[Callable[[], object]], reps: int = 5,
          warmup: int = 1) -> Tuple[int, List[float]]:
    """Best-of-``reps`` walls (us) for each zero-arg dispatch callable,
    measured INTERLEAVED round-robin so clock drift and thermal state hit
    every candidate equally — the only defensible way to compare candidate
    walls in one process.  Each callable is invoked ``warmup`` times first
    (compiles excluded from the timed reps).  Returns ``(best_index,
    walls_us)``."""
    for f in fns:
        for _ in range(warmup):
            f()
    walls = [float("inf")] * len(fns)
    for _ in range(max(1, reps)):
        for i, f in enumerate(fns):
            t0 = time.perf_counter()
            f()
            walls[i] = min(walls[i], (time.perf_counter() - t0) * 1e6)
    return min(range(len(fns)), key=lambda i: walls[i]), walls


def _operands(M: int, K: int, N: int, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(-128, 128, (M, K), np.int64), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, (K, N), np.int64), jnp.int8)
    return a, b


def _dispatch_fn(op: str, a, b, mult_name: str,
                 sched: KernelSchedule) -> Callable[[], object]:
    """A zero-arg blocking dispatch of signature op ``op`` under ``sched``
    — the exact production call path (kernels.ops / quant.ax), so measured
    walls include the wrapper overhead schedules actually pay."""
    import jax.numpy as jnp

    from repro.core import multipliers as M_

    mult = M_.get(mult_name)
    if op == "matmul":
        from . import ops

        swap = None  # static swap config lives in the kernel either way
        return lambda: ops.ax_matmul(a, b, mult, swap,
                                     schedule=sched).block_until_ready()
    if op == "matmul_grid":
        from . import ops

        gm, gn = a.shape[0] // sched.bm, b.shape[1] // sched.bn
        grid = jnp.broadcast_to(jnp.asarray((1, 3, 0), jnp.int32), (gm, gn, 3))
        return lambda: ops.ax_matmul_grid(a, b, mult, grid,
                                          schedule=sched).block_until_ready()
    from repro.configs.base import AxPolicy
    from repro.quant import ax as qax

    policy = AxPolicy(mult_name=mult_name, backend="mxu")
    if op == "int_static":
        return lambda: qax.ax_matmul_int(a, b, policy,
                                         schedule=sched).block_until_ready()
    assert op == "int_dyn", op
    dyn = jnp.asarray((1, 3, 0), jnp.int32)
    return lambda: qax.ax_matmul_int_dyn(a, b, policy, dyn,
                                         schedule=sched).block_until_ready()


def tune_signature(M: int, K: int, N: int, backend: str, mult_name: str,
                   op: str = "matmul", quick: bool = True,
                   reps: Optional[int] = None,
                   seed: int = 0) -> Tuple[KernelSchedule, dict]:
    """Measured-wall search over :func:`candidate_schedules` for one
    dispatch signature.  Returns ``(winner, report)`` where the report
    carries every candidate's best-of wall (us) keyed by
    ``KernelSchedule.short()`` plus the baseline/default walls — the raw
    evidence behind a table entry."""
    cands = candidate_schedules(backend, M, K, N, op=op, quick=quick)
    a, b = _operands(M, K, N, seed=seed)
    fns = [_dispatch_fn(op, a, b, mult_name, s) for s in cands]
    best, walls = sweep(fns, reps=(3 if quick else 8) if reps is None else reps)
    report = {
        "sig": sig_key(M, K, N, backend, mult_name, op),
        "winner": cands[best].short(),
        "best_us": walls[best],
        "default_us": walls[0],
        "walls_us": {s.short(): w for s, w in zip(cands, walls)},
    }
    return cands[best], report


def tune_table(shapes: Sequence[Tuple[int, int, int]], mult_name: str,
               backends: Sequence[str] = ("kernel", "mxu"),
               ops_by_backend: Optional[Dict[str, Sequence[str]]] = None,
               quick: bool = True, seed: int = 0) -> Tuple[ScheduleTable, list]:
    """Tune every ``(shape, backend, op)`` signature and collect winners
    into a :class:`ScheduleTable` (version 0 — the store assigns the real
    version at publish).  Returns ``(table, reports)``."""
    ops_by_backend = dict(ops_by_backend or DEFAULT_OPS)
    t0 = time.perf_counter()
    table = ScheduleTable()
    reports = []
    for (M, K, N) in shapes:
        for backend in backends:
            for op in ops_by_backend.get(backend, ()):
                win, rep = tune_signature(M, K, N, backend, mult_name,
                                          op=op, quick=quick, seed=seed)
                table.set(M, K, N, backend, mult_name, win, op=op)
                reports.append(rep)
    table.meta = {
        "mode": "quick" if quick else "full",
        "mult": mult_name,
        "signatures": len(reports),
        "tuner_wall_s": round(time.perf_counter() - t0, 3),
    }
    return table, reports


# ---------------------------------------------------------------------------
# fleet persistence: the PolicyStore file protocol for schedule tables
# ---------------------------------------------------------------------------

_CURRENT = "CURRENT"
_HEARTBEAT = "HEARTBEAT"
_FMT = "schedules_v{:06d}.json"
_RX = re.compile(r"^schedules_v(\d{6})\.json$")


class ScheduleStore:
    """Directory-backed versioned schedule tables — the same crash-atomic
    single-writer protocol as ``fleet.PolicyStore`` (fsync'd temp+rename
    version files, atomic CURRENT pointer, HEARTBEAT ``mtime_ns ==
    version`` so reader polls fast-path on one ``stat()``), applied to the
    autotuner's artifact so every replica of a fleet dispatches the same
    measured winners.  Publishes append a ``schedule_publish`` audit event
    to the store-adjacent ``audit.jsonl`` (same replayable trail as policy
    retunes).

    Layout::

        <root>/CURRENT                  # text: current version number
        <root>/HEARTBEAT                # empty; mtime_ns == version
        <root>/schedules_v000001.json   # immutable once written
        <root>/audit.jsonl
    """

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._last_published: Optional[int] = None

    # -- paths / versions ---------------------------------------------
    def _path(self, version: int) -> str:
        return os.path.join(self.root, _FMT.format(version))

    def versions(self) -> List[int]:
        out = []
        for fn in os.listdir(self.root):
            m = _RX.match(fn)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def current_version(self) -> Optional[int]:
        try:
            with open(os.path.join(self.root, _CURRENT)) as f:
                return int(f.read().strip())
        except (FileNotFoundError, ValueError):
            vs = self.versions()
            return vs[-1] if vs else None

    # -- reader side ---------------------------------------------------
    def load(self, version: int) -> ScheduleTable:
        return ScheduleTable.load(self._path(version))

    def load_current(self) -> Optional[Tuple[int, ScheduleTable]]:
        for _ in range(2):
            v = self.current_version()
            if v is None:
                return None
            try:
                return v, self.load(v)
            except FileNotFoundError:
                continue
        return None

    def load_newest_loadable(self) -> Optional[Tuple[int, ScheduleTable]]:
        """Newest version that parses — never raises (the reader's defense
        when CURRENT is torn or the newest file is corrupt)."""
        for v in reversed(self.versions()):
            try:
                return v, self.load(v)
            except _READ_ERRS:
                continue
        return None

    # -- writer side ---------------------------------------------------
    def _fsync_dir(self) -> None:
        try:
            fd = os.open(self.root, os.O_RDONLY)
        except OSError:
            return
        try:
            os.fsync(fd)
        except OSError:
            pass
        finally:
            os.close(fd)

    def _write_atomic(self, name: str, text: str) -> None:
        path = os.path.join(self.root, name)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._fsync_dir()

    def _touch_heartbeat(self, version: int) -> None:
        # mtime_ns == version (not wall time): granularity-immune, and
        # readers compare by EQUALITY — see fleet.store._touch_heartbeat
        path = os.path.join(self.root, _HEARTBEAT)
        if not os.path.exists(path):
            with open(path, "w"):
                pass
        os.utime(path, ns=(version, version))

    def heartbeat_ns(self) -> Optional[int]:
        try:
            return os.stat(os.path.join(self.root, _HEARTBEAT)).st_mtime_ns
        except FileNotFoundError:
            return None

    def publish(self, table: ScheduleTable) -> int:
        """Persist ``table`` as the next version and swing CURRENT
        (heartbeat touched BEFORE the pointer swap — a crash between the
        two degrades readers to full polls, never hides the publish).
        Single-writer guarded like ``PolicyStore.publish``."""
        cur = self.current_version()
        if (self._last_published is not None and cur is not None
                and cur > self._last_published):
            raise RuntimeError(
                f"ScheduleStore single-writer violation: on-disk {cur} > "
                f"last published {self._last_published}")
        version = max([cur or 0] + self.versions()) + 1
        table.version = version
        self._write_atomic(_FMT.format(version), table.to_json())
        self._touch_heartbeat(version)
        self._write_atomic(_CURRENT, str(version))
        self._last_published = version
        audit = obs.audit_for_store(self)
        if audit is not None:
            audit.append("schedule_publish", store_version=version,
                         entries=len(table), meta=dict(table.meta))
        return version

    def prune(self, keep_last: int = 8) -> List[int]:
        vs = self.versions()
        cur = self.current_version()
        drop = [v for v in vs[:-keep_last] if v != cur] if keep_last else []
        for v in drop:
            os.remove(self._path(v))
        return drop


class ScheduleReader:
    """A replica's view of a :class:`ScheduleStore`: ``poll()`` adopts a
    newer table by calling :func:`schedule.install_table` — which is
    zero-retrace by the resolution contract (entries only affect
    signatures traced after adoption), asserted in tests/test_autotune.py.
    Same degraded-read ladder as ``fleet.PolicyReader``: unchanged
    heartbeat short-circuits to one ``stat()``; torn/corrupt files fall
    back to the newest loadable version; on total store damage the replica
    keeps the table it has."""

    def __init__(self, store: ScheduleStore, name: str = "replica",
                 install: bool = True):
        self.store = store
        self.name = name
        self.install = install
        self.version: int = -1
        self.table: Optional[ScheduleTable] = None
        self._hb_seen: Optional[int] = None
        self.poll()

    def poll(self) -> bool:
        """Adopt the current table if newer; True when it changed."""
        hb = self.store.heartbeat_ns()
        if hb is not None and hb == self._hb_seen:
            return False
        v = self.store.current_version()
        caught_up = hb is not None and v is not None and v >= hb
        if v is None or v == self.version:
            self._hb_seen = hb if caught_up else None
            return False
        got = self._load_degrading(v)
        if got is None or got[0] == self.version:
            return False
        self.version, self.table = got
        self._hb_seen = hb if caught_up else None
        if self.install:
            install_table(self.table)
        return True

    def _load_degrading(self, v: Optional[int]):
        for _ in range(2):
            if v is None:
                break
            try:
                return v, self.store.load(v)
            except _READ_ERRS:
                v = self.store.current_version()
        return self.store.load_newest_loadable()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parse_shapes(text: str) -> List[Tuple[int, int, int]]:
    out = []
    for part in text.split(","):
        m, k, n = (int(v) for v in part.lower().split("x"))
        out.append((m, k, n))
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Measured-wall kernel-schedule autotuner")
    ap.add_argument("--shapes", default="128x128x128",
                    help="comma-separated MxKxN signatures to tune")
    ap.add_argument("--mult", default="mul8s_trunc0_4")
    ap.add_argument("--backends", default="kernel,mxu")
    ap.add_argument("--quick", action="store_true",
                    help="halved candidate space, 3 reps (CI)")
    ap.add_argument("--out", default=None,
                    help="write the tuned table JSON here")
    ap.add_argument("--store", default=None,
                    help="publish the table to this ScheduleStore directory")
    args = ap.parse_args(argv)

    shapes = _parse_shapes(args.shapes)
    backends = tuple(b for b in args.backends.split(",") if b)
    table, reports = tune_table(shapes, args.mult, backends=backends,
                                quick=args.quick)
    for rep in reports:
        print(f"{rep['sig']}: {rep['winner']}  "
              f"best={rep['best_us']:.1f}us default={rep['default_us']:.1f}us")
    if args.out:
        table.save(args.out)
        print(f"wrote {args.out} ({len(table)} entries)")
    if args.store:
        v = ScheduleStore(args.store).publish(table)
        print(f"published v{v} to {args.store}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
