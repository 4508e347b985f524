"""Reductions for the per-layer metrics of sliding-window attention: device
time under the program's ``attn.<kind>`` named scopes (``attn.local``,
``attn.global``: the attention proper, from the cache write to the values,
without the projections) and the least time the K and V reads of the
window's decode tokens take at the chip's bandwidth peak.

An op's scope is the innermost of the ``attn.<kind>``, ``ax.<target>``,
``ax_telemetry.<target>`` and ``sample`` scopes in the ``op_name`` of the
same instruction in the optimized HLO of its program (a fusion without
metadata takes its fused root's), as ``spans`` reads the other scopes.
Every function returns None where the window gives it nothing to read: a
program built without the scopes reads nothing, never 0."""
from __future__ import annotations

from typing import Dict, Optional

from . import readers, spans


def attn_kind(op_name: str) -> Optional[str]:
    """``attn.<kind>`` when that is the innermost named scope of an
    ``op_name`` path, else None (another scope's op, or no scope)."""
    for seg in reversed(op_name.split("/")):
        if seg.startswith("attn.") and len(seg) > len("attn."):
            return seg
        if spans.family(seg) is not None:
            return None
    return None


def scopes_from_text(text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> ``attn.<kind>`` or None, from one module's HLO
    text (a fusion without metadata takes its fused root's)."""
    comps = spans._computations(text)
    instrs = {i.name: i for body in comps.values() for i in body}
    root = {c: i.name for c, body in comps.items() for i in body if i.root}

    def scope(name, depth=0):
        i = instrs[name]
        if i.op_name is not None:
            return attn_kind(i.op_name)
        sub = root.get(i.calls)
        return scope(sub, depth + 1) if sub and depth < 8 else None

    return {i.name: scope(i.name) for i in instrs.values()
            if i.op_name is not None or i.calls is not None}


def hlo_scopes(module: str) -> Optional[Dict[str, Optional[str]]]:
    """Instruction name -> ``attn.<kind>`` for the live executables whose
    module is named ``module``; an instruction two of them place apart is
    left out.  None where none is alive or its HLO cannot be shown."""
    texts = spans.hlo_texts(module)
    if not texts:
        return None
    out: Dict[str, Optional[str]] = {}
    clash = set()
    for text in texts:
        for name, kind in scopes_from_text(text).items():
            if out.setdefault(name, kind) != kind:
                clash.add(name)
    for name in clash:
        del out[name]
    return out


def attn_seconds(ctx, module: str, kind: str) -> Optional[float]:
    """Device seconds of program ``module``'s ops whose innermost scope is
    ``attn.<kind>`` in the traced window, summed over devices; None where
    the program never opens that scope."""
    if ctx.trace is None:
        return None
    scopes = hlo_scopes(module)
    name = f"attn.{kind}"
    if not scopes or name not in set(scopes.values()):
        return None
    secs = spans.scope_seconds(ctx.trace["ops"], ctx.trace["t0"],
                               ctx.trace["t1"], (module,), scopes)
    return secs.get(name, 0.0) if secs else None


def attn_ms_per_step(ctx, module: str, kind: str) -> Optional[float]:
    """:func:`attn_seconds` per decode step of the window, per device."""
    secs = attn_seconds(ctx, module, kind)
    n = len(readers._steps_in(ctx))
    if secs is None or n == 0:
        return None
    return secs / len(readers._devices(ctx)) / n * 1e3


def decode_window_bytes(ctx) -> int:
    """The K and V bytes decode attention must read for the window's decode
    tokens, by the reference module's ``window_bytes`` at each token's
    position (token ``i`` >= 1 of a request of prompt length ``L`` is
    decoded at position ``L + i - 1``)."""
    total = 0
    for rid, ts in ctx.times.items():
        L = len(ctx.prompts.get(rid, ()))
        if not L:
            continue
        for i, t in enumerate(ts):
            if i and ctx.w0 < t <= ctx.w1:
                total += ctx.reference.window_bytes(ctx.config, L + i - 1)
    return total


def roofline_pct(ctx, kind: str = "local") -> Optional[float]:
    """100 x the least time for :func:`decode_window_bytes` at the chip's
    bandwidth peak / the measured ``attn.<kind>`` time of the token step
    (``jit_step``) in the window."""
    if ctx.peaks is None or not hasattr(ctx.reference, "window_bytes"):
        return None
    secs = attn_seconds(ctx, "jit_step", kind)
    nbytes = decode_window_bytes(ctx)
    if not secs or not nbytes:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs
