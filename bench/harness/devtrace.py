"""From the profiler's ``.xplane.pb`` to device busy time, idle share,
per-program device time and the breakdown.

Ops are read from each device plane's ``XLA Ops`` line (on a TPU), or,
on the CPU backend, from host-thread events that carry an ``hlo_op`` stat.
An op's program is its ``hlo_module`` stat, or else the ``XLA Modules``
event that covers it.  All times are seconds on the trace's clock; the
caller aligns host spans to it with one marker that it places."""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Op:
    device: str
    name: str
    module: str
    start: float
    end: float


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, AttributeError, ValueError):
        return {}


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def read(path: str) -> Tuple[List[Op], Dict[str, List[Tuple[str, float, float]]]]:
    """(device ops, host events by name) from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    host = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            modules = []
            lines = {line.name: list(line.events) for line in plane.lines}
            for ev in lines.get("XLA Modules", []):
                modules.append((ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
            modules.sort()
            starts = [m[0] for m in modules]
            for ev in lines.get("XLA Ops", []):
                s = ev.start_ns * 1e-9
                mod = (_stats(ev).get("hlo_module")
                       or _covering(modules, starts, s))
                ops.append(Op(plane.name, _op_name(ev.name), _module_name(mod),
                              s, s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                    if "hlo_op" in st:
                        ops.append(Op(f"cpu:{st.get('device_ordinal', 0)}",
                                      ev.name, _module_name(st.get("hlo_module")),
                                      s, e))
                    else:
                        host[ev.name].append((line.name, s, e))
    return ops, dict(host)


def _module_name(mod) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    if not mod:
        return ""
    return str(mod).split("(")[0]


def _op_name(text: str) -> str:
    """``%fusion.110 = (s8[...]) fusion(...)`` -> ``fusion.110``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _covering(modules, starts, t) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ops: Sequence[Op], t0: float, t1: float) -> List[Op]:
    return [dataclasses.replace(o, start=max(o.start, t0), end=min(o.end, t1))
            for o in ops if o.end > t0 and o.start < t1]


def busy(ops: Sequence[Op], t0: float, t1: float) -> Dict[str, float]:
    """Seconds in which some op ran, per device, inside [t0, t1]."""
    per = collections.defaultdict(list)
    for o in clip(ops, t0, t1):
        per[o.device].append((o.start, o.end))
    return {d: sum(e - s for s, e in union(iv)) for d, iv in per.items()}


def program_time(ops: Sequence[Op], t0: float, t1: float,
                 modules: Sequence[str]) -> Optional[float]:
    """Device seconds of the named programs, summed over devices as a
    union per device (so overlapping ops count once); None when no op of
    them ran."""
    sel = [o for o in clip(ops, t0, t1) if o.module in modules]
    if not sel:
        return None
    per = collections.defaultdict(list)
    for o in sel:
        per[o.device].append((o.start, o.end))
    return sum(sum(e - s for s, e in union(iv)) for iv in per.values())


def top_ops(ops: Sequence[Op], t0: float, t1: float, n: int = 10):
    tot = collections.Counter()
    for o in clip(ops, t0, t1):
        tot[f"{o.module}/{o.name}" if o.module else o.name] += o.end - o.start
    return [[k, v] for k, v in tot.most_common(n)]


def idle_gaps(ops: Sequence[Op], t0: float, t1: float,
              host: Sequence[Tuple[str, float, float]], device: str,
              n: int = 10):
    """The ``n`` longest gaps on ``device`` in which no op ran, each named
    by the host span that covers most of it (``host idle`` where none
    does)."""
    iv = union((o.start, o.end) for o in clip(ops, t0, t1) if o.device == device)
    edges = [t0] + [x for s, e in iv for x in (s, e)] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, cover = "host idle", 0.0
        for name, hs, he in host:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, e - s])
    return out
