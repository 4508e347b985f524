"""Reductions for the per-layer metrics that read the program's named
scopes and phase spans.

Device side: a decode step's device time is split by the named scope of
each op (``ax.<target>``, ``ax_telemetry.<target>``, ``sample``).  An op's
scope is read from the ``op_name`` metadata of the same instruction in the
optimized HLO of its program, which the process still holds
(:func:`hlo_scopes`); a fusion carries the metadata XLA gives its root.
Where ops nest in the trace (a loop and its body), each instant goes to the
innermost op, so the parts add up to the program's device time
(``devtrace.program_time``).

Host side: the scheduler's phase spans (``token_read``, ``token_step``,
``admit``, the runtime spans) on the trace's clock.

Every function returns None where the window gives it nothing to read: a
program built without the scopes or spans reads nothing, never 0."""
from __future__ import annotations

import bisect
import collections
import heapq
import re
from typing import (Callable, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional, Tuple)

from . import devtrace, readers

RUNTIME_SPANS = ("controller_observe", "qor_observe", "policy_poll",
                 "policy_tree", "slo_observe", "audit_append", "retune")

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def family(op_name: str) -> Optional[str]:
    """The scope family of the innermost named scope in an ``op_name``
    path (``jit(step)/ax.mlp/ax_telemetry.mlp/cond/...`` ->
    ``ax_telemetry``), or None outside every scope."""
    for seg in reversed(op_name.split("/")):
        if seg == "sample":
            return "sample"
        head = seg.split(".", 1)[0]
        if "." in seg and head in ("ax", "ax_telemetry"):
            return head
    return None


class _Instr(NamedTuple):
    name: str
    op_name: Optional[str]       # its metadata's op_name, if any
    calls: Optional[str]         # the computation a fusion calls
    root: bool


def _computations(text: str) -> Dict[str, List[_Instr]]:
    """Computation name -> its instructions, from one module's HLO text."""
    comps: Dict[str, List[_Instr]] = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        meta, called = _OP_NAME.search(line), _CALLS.search(line)
        comps[comp].append(_Instr(m.group(2), meta and meta.group(1),
                                  called and called.group(1),
                                  bool(m.group(1))))
    return comps


def scopes_from_text(text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> scope family, from one module's HLO text.  A
    fusion that XLA left without metadata takes its fused root's."""
    comps = _computations(text)
    instrs = {i.name: i for body in comps.values() for i in body}
    root = {c: i.name for c, body in comps.items() for i in body if i.root}

    def scope(name, depth=0):
        i = instrs[name]
        if i.op_name is not None:
            return family(i.op_name)
        sub = root.get(i.calls)
        return scope(sub, depth + 1) if sub and depth < 8 else None

    return {i.name: scope(i.name) for i in instrs.values()
            if i.op_name is not None or i.calls is not None}


def fused_families(text: str) -> Dict[str, List[str]]:
    """Fusion -> the scope families (``none``: outside every scope) of the
    ops fused into it, for the fusions whose ops come from more than one.
    A reducer's parameters carry a bare op name with no path: not counted."""
    comps = _computations(text)
    out = {}
    for body in comps.values():
        for i in body:
            if i.calls is None:
                continue
            seen = {family(j.op_name) or "none" for j in comps.get(i.calls, ())
                    if j.op_name and "/" in j.op_name}
            if len(seen) > 1:
                out[i.name] = sorted(seen)
    return out


def hlo_texts(module: str) -> Optional[List[str]]:
    """The optimized HLO text of each live executable whose module is
    named ``module``; None where the backend cannot show it."""
    import jax

    try:
        return [m.to_string()
                for ex in jax.devices()[0].client.live_executables()
                for m in ex.hlo_modules() if m.name == module]
    except (AttributeError, RuntimeError, NotImplementedError):
        return None


def hlo_scopes(module: str) -> Optional[Dict[str, Optional[str]]]:
    """Instruction name -> scope family for the live executables whose
    module is named ``module`` (``jit_step``).  An instruction that two such
    executables place in different families is left out.  None where no
    such executable is alive or the backend cannot show its HLO."""
    texts = hlo_texts(module)
    if not texts:
        return None
    out: Dict[str, Optional[str]] = {}
    clash = set()
    for text in texts:
        for name, fam in scopes_from_text(text).items():
            if out.setdefault(name, fam) != fam:
                clash.add(name)
    for name in clash:
        del out[name]
    return out


def innermost(items: Iterable[Tuple[float, float, object]]
              ) -> Iterator[Tuple[float, float, object]]:
    """``(start, end, label)`` items -> disjoint ``(a, b, label)`` segments
    of the time some item covers, each labelled by the covering item that
    started last (on a tie, the one that ends first)."""
    items = sorted(items, key=lambda x: (x[0], x[1]))
    pts = sorted({p for s, e, _ in items for p in (s, e)})
    heap: list = []
    i = 0
    for a, b in zip(pts, pts[1:]):
        while i < len(items) and items[i][0] <= a:
            s, e, lab = items[i]
            heapq.heappush(heap, (-s, e, i, lab))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            yield a, b, heap[0][3]


def split_by(ops: List[devtrace.Op], t0: float, t1: float,
             label: Callable[[devtrace.Op], object]) -> Dict[object, float]:
    """Device seconds of ``ops`` inside [t0, t1] by ``label(op)`` of the
    innermost op at each instant, summed over devices.  The parts add up
    to ``devtrace.program_time`` of the same ops."""
    per = collections.defaultdict(list)
    for o in devtrace.clip(ops, t0, t1):
        per[o.device].append((o.start, o.end, label(o)))
    out: Dict[object, float] = collections.defaultdict(float)
    for items in per.values():
        for a, b, lab in innermost(items):
            out[lab] += b - a
    return dict(out)


def scope_seconds(ops: List[devtrace.Op], t0: float, t1: float,
                  modules, scopes: Dict[str, Optional[str]]
                  ) -> Dict[Optional[str], float]:
    """Device seconds of the named programs by scope family (None: no
    scope)."""
    sel = [o for o in ops if o.module in modules]
    return split_by(sel, t0, t1, lambda o: scopes.get(o.name))


def scope_ms_per_step(ctx, fam: str,
                      scopes: Optional[dict] = None) -> Optional[float]:
    """Device time of the token step's (jit_step) ops whose innermost scope is of
    family ``fam``, per decode step of the window, per device: the steps
    and devices ``readers.program_ms_per_step`` divides by.  0 where the
    program opens the scope but XLA fused all of its work into ops that
    carry another (an argmax fused into the matmul that feeds it); None
    where the program never opens it."""
    if ctx.trace is None:
        return None
    if scopes is None:
        scopes = hlo_scopes("jit_step")
    if not scopes or fam not in set(scopes.values()):
        return None
    n = len(readers._steps_in(ctx))
    if n == 0:
        return None
    secs = scope_seconds(ctx.trace["ops"], ctx.trace["t0"], ctx.trace["t1"],
                         ("jit_step",), scopes)
    if not secs:
        return None
    return secs.get(fam, 0.0) / len(readers._devices(ctx)) / n * 1e3


def _named(spans, name):
    return [(s, e) for n, s, e in spans if n == name]


def _overlap(iv: List[Tuple[float, float]], a: float, b: float) -> float:
    """Seconds of the disjoint sorted intervals ``iv`` inside [a, b]."""
    i = max(bisect.bisect_right([s for s, _ in iv], a) - 1, 0)
    tot = 0.0
    for s, e in iv[i:]:
        if s >= b:
            break
        tot += max(0.0, min(e, b) - max(s, a))
    return tot


def host_ms_per_step(ctx) -> Optional[float]:
    """Mean host time per decode step of the window in which the device
    waits on the host: from the end of the previous step's ``token_read``
    (its tokens on the host) to the end of this step's ``token_step``
    dispatch, less the time inside ``admit`` spans (each waits on a
    prefill running on the device)."""
    if ctx.trace is None:
        return None
    spans, t0, t1 = ctx.trace["spans"], ctx.trace["t0"], ctx.trace["t1"]
    reads = sorted(e for _, e in _named(spans, "token_read"))
    admits = devtrace.union(_named(spans, "admit"))
    gaps = []
    for s, e in sorted(_named(spans, "token_step")):
        if not t0 < s <= t1:
            continue
        i = bisect.bisect_right(reads, s) - 1
        if i < 0:
            continue
        gaps.append(e - reads[i] - _overlap(admits, reads[i], e))
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3


def runtime_host_ms_per_step(ctx) -> Optional[float]:
    """Host time inside the runtime's spans (their union, so a span nested
    in another counts once) in the window, per decode step of the window."""
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace["t0"], ctx.trace["t1"]
    iv = [(max(s, t0), min(e, t1)) for n, s, e in ctx.trace["spans"]
          if n in RUNTIME_SPANS and e > t0 and s < t1]
    n = len(readers._steps_in(ctx))
    if not iv or n == 0:
        return None
    return sum(e - s for s, e in devtrace.union(iv)) / n * 1e3


def idle_by_span(ops: List[devtrace.Op], t0: float, t1: float,
                 spans, device: str) -> Dict[str, float]:
    """Seconds in [t0, t1] in which no op ran on ``device``, by the
    innermost host span covering each instant (``host_idle`` where none
    does)."""
    busy = devtrace.union((o.start, o.end) for o in devtrace.clip(ops, t0, t1)
                          if o.device == device)
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    covered = list(innermost((max(s, t0), min(e, t1), n)
                             for n, s, e in spans if e > t0 and s < t1))
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for a, b in idle:
        left = b - a
        while j < len(covered) and covered[j][1] <= a:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < b:
            c = min(b, covered[k][1]) - max(a, covered[k][0])
            if c > 0:
                out[covered[k][2]] += c
                left -= c
            k += 1
        out["host_idle"] += left
    return dict(out)
