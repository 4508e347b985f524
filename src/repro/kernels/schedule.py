"""KernelSchedule: the single dispatch currency of the kernel layer.

A :class:`KernelSchedule` is a frozen, hashable value object naming every
knob of one approximate-matmul dispatch — block shapes, slab depth, grid
iteration order, backend, and the mxu limb strategy.  It replaces the old
ad-hoc kwarg surface (``block_m=``/``block_n=``/``block_k=``/``k_slab=``
scattered across ``kernels/ops.py`` and ``quant/ax.py``) with one value
that is simultaneously

* a **jit static argument** — hashable, so one schedule == one compiled
  program, and resolving it host-side at trace time keeps every dynamic
  input (operands, swap-config grids) traced: schedule adoption can never
  retrace a compiled program that is not being traced anyway;
* the **autotuner's native output** — ``kernels/autotune.py`` sweeps
  candidate schedules with measured walls and persists winners in a
  :class:`ScheduleTable` keyed by the dispatch signature
  ``(M, K, N, backend, mult, op)``;
* the **fleet's shared tuning state** — tables travel through the
  PolicyStore file protocol (``autotune.ScheduleStore``), and every
  dispatch site consults the process-installed table via :func:`resolve`
  with a zero-recompile fallback to the historical defaults when no entry
  exists.

Legacy ``block_m=``/``k_slab=`` call sites keep working through
:func:`from_legacy_kwargs` (one-release ``DeprecationWarning`` path — see
``kernels/ops.py``).
"""
from __future__ import annotations

import dataclasses
import json
import threading
from typing import Dict, Optional, Tuple


__all__ = [
    "KernelSchedule",
    "ScheduleTable",
    "sig_key",
    "default_schedule",
    "from_legacy_kwargs",
    "install_table",
    "installed_table",
    "clear_table",
    "resolve",
]

GRID_ORDERS = ("mn", "nm")
LIMB_MODES = ("stacked", "split")
BACKENDS = ("kernel", "mxu", "emul")

@dataclasses.dataclass(frozen=True)
class KernelSchedule:
    """One approximate-matmul dispatch configuration.

    ``bm``/``bn``/``bk`` — VMEM block shape caps (clamped to the operand
    dims, which must stay divisible — the quant layer pads to multiples).
    ``k_slab`` — sublane depth of the vectorized K reduction (None = auto,
    1 = the legacy rank-1 schedule).  ``grid_order`` — iteration order of
    the two parallel grid dimensions ("mn" = M-major, "nm" = N-major; the
    K reduction stays innermost either way, so outputs are bit-identical).
    ``backend`` — which dispatch family the schedule tunes.
    ``limbs`` — mxu only: "stacked" K-stacks the swap factorization into
    one 2K/4K dot_general, "split" keeps the pre-stacking 2-matmul form.
    ``noswap_fast`` — mxu dyn only: opt-in ``lax.cond`` K-limb sparsity —
    the compiled program carries a K-inner-dim NoSwap branch next to the
    2K stacked one and the traced config picks per call (bit-identical;
    costs an extra dot_general in the jaxpr, hence opt-in)."""

    backend: str = "kernel"
    bm: int = 128
    bn: int = 128
    bk: int = 128
    k_slab: Optional[int] = None
    grid_order: str = "mn"
    limbs: str = "stacked"
    noswap_fast: bool = False

    def __post_init__(self):
        assert self.backend in BACKENDS, self.backend
        assert self.grid_order in GRID_ORDERS, self.grid_order
        assert self.limbs in LIMB_MODES, self.limbs
        assert self.bm > 0 and self.bn > 0 and self.bk > 0, (
            self.bm, self.bn, self.bk)
        assert self.k_slab is None or self.k_slab > 0, self.k_slab

    # -- (de)serialization --------------------------------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelSchedule":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def short(self) -> str:
        """Compact human label for tables/logs."""
        ks = "auto" if self.k_slab is None else self.k_slab
        tag = f"{self.backend}:{self.bm}x{self.bn}x{self.bk}/ks{ks}/{self.grid_order}"
        if self.backend == "mxu":
            tag += f"/{self.limbs}" + ("+noswap" if self.noswap_fast else "")
        return tag


def default_schedule(backend: str = "kernel") -> KernelSchedule:
    """The historical defaults: 128-cap blocks, auto slab, M-major grid,
    K-stacked mxu limbs — exactly what every dispatch site did before the
    schedule API, so a missing table entry is behavior-identical to the
    pre-autotuner code (the zero-recompile fallback contract)."""
    return KernelSchedule(backend=backend)


def from_legacy_kwargs(backend: str, block_m: Optional[int],
                       block_n: Optional[int], block_k: Optional[int],
                       k_slab: Optional[int]) -> KernelSchedule:
    """Map the deprecated kwarg surface onto a schedule (missing kwargs
    take the historical 128 defaults)."""
    return KernelSchedule(
        backend=backend,
        bm=128 if block_m is None else int(block_m),
        bn=128 if block_n is None else int(block_n),
        bk=128 if block_k is None else int(block_k),
        k_slab=None if k_slab is None else int(k_slab),
    )


# ---------------------------------------------------------------------------
# signature keys + table
# ---------------------------------------------------------------------------

def sig_key(M: int, K: int, N: int, backend: str, mult_name: str,
            op: str = "matmul") -> str:
    """Dispatch-signature key: problem shape x backend x multiplier x kernel
    flavor (``op``: "matmul" static, "matmul_grid" scalar-prefetch grid,
    "int_static"/"int_dyn" the quant-layer mxu forms).  The shape is the
    *logical* (pre-padding) one — resolution happens before padding."""
    return f"{int(M)}x{int(K)}x{int(N)}/{backend}/{mult_name}/{op}"


class ScheduleTable:
    """A versioned ``sig_key -> KernelSchedule`` mapping (the autotuner's
    artifact).  JSON round-trips losslessly; ``meta`` carries provenance
    (host, sweep mode, walls) without affecting equality."""

    def __init__(self, entries: Optional[Dict[str, KernelSchedule]] = None,
                 version: int = 0, meta: Optional[dict] = None):
        self.entries: Dict[str, KernelSchedule] = dict(entries or {})
        self.version = int(version)
        self.meta = dict(meta or {})

    def set(self, M: int, K: int, N: int, backend: str, mult_name: str,
            schedule: KernelSchedule, op: str = "matmul") -> None:
        self.entries[sig_key(M, K, N, backend, mult_name, op)] = schedule

    def lookup(self, M: int, K: int, N: int, backend: str, mult_name: str,
               op: str = "matmul") -> Optional[KernelSchedule]:
        return self.entries.get(sig_key(M, K, N, backend, mult_name, op))

    def __len__(self) -> int:
        return len(self.entries)

    def __eq__(self, other) -> bool:
        return (isinstance(other, ScheduleTable)
                and self.entries == other.entries
                and self.version == other.version)

    # -- JSON ----------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "kind": "schedule_table",
            "version": self.version,
            "meta": self.meta,
            "entries": {k: s.to_dict() for k, s in sorted(self.entries.items())},
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_dict(cls, d: dict) -> "ScheduleTable":
        assert d.get("kind") == "schedule_table", d.get("kind")
        return cls(
            entries={k: KernelSchedule.from_dict(v)
                     for k, v in d.get("entries", {}).items()},
            version=int(d.get("version", 0)),
            meta=d.get("meta", {}),
        )

    @classmethod
    def from_json(cls, text: str) -> "ScheduleTable":
        return cls.from_dict(json.loads(text))

    def save(self, path: str) -> str:
        import os

        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as fh:
            fh.write(self.to_json())
        return path

    @classmethod
    def load(cls, path: str) -> "ScheduleTable":
        with open(path) as fh:
            return cls.from_json(fh.read())


# ---------------------------------------------------------------------------
# process-installed table + resolution
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_ACTIVE: Optional[ScheduleTable] = None


def install_table(table: Optional[ScheduleTable]) -> Optional[ScheduleTable]:
    """Install ``table`` as the process-wide schedule source consulted by
    every dispatch site; returns the previously installed table (None to
    uninstall).  Installing a table NEVER invalidates compiled programs:
    lookups happen host-side at trace time, so entries only take effect on
    signatures that trace after the install (unseen shapes, or natural
    recompiles) — the zero-retrace adoption contract, asserted in
    tests/test_autotune.py."""
    global _ACTIVE
    with _LOCK:
        prev, _ACTIVE = _ACTIVE, table
    return prev


def installed_table() -> Optional[ScheduleTable]:
    return _ACTIVE


def clear_table() -> None:
    install_table(None)


def resolve(M: int, K: int, N: int, backend: str, mult_name: str,
            op: str = "matmul",
            override: Optional[KernelSchedule] = None) -> KernelSchedule:
    """The one resolution order every dispatch site uses:
    explicit schedule > installed-table entry > backend defaults."""
    if override is not None:
        return override
    table = _ACTIVE
    if table is not None:
        hit = table.lookup(M, K, N, backend, mult_name, op)
        if hit is not None:
            return hit
    return default_schedule(backend)


def resolve_for(a_shape: Tuple[int, ...], b_shape: Tuple[int, ...],
                backend: str, mult_name: str, op: str = "matmul",
                override: Optional[KernelSchedule] = None) -> KernelSchedule:
    """:func:`resolve` from operand shapes: ``(..., K) @ (K, N)`` with the
    leading dims flattened into the logical M."""
    m = 1
    for d in a_shape[:-1]:
        m *= int(d)
    return resolve(m, int(a_shape[-1]), int(b_shape[-1]), backend, mult_name,
                   op, override=override)
