"""Reductions shared by the per-layer metric readers in ``bench/metrics/``.
Each returns None where the window gives it nothing to read; none returns
0 for a share it could not measure."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from . import devtrace


def _steps_in(ctx):
    return [k for k, t in ctx.steps.start.items() if ctx.w0 < t <= ctx.w1]


def _devices(ctx):
    return sorted({o.device for o in ctx.trace["ops"]}) if ctx.trace else []


def program_ms_per_step(ctx, modules: Sequence[str]) -> Optional[float]:
    """Device time of the named programs per decode step of the window,
    per device."""
    if ctx.trace is None:
        return None
    t = devtrace.program_time(ctx.trace["ops"], ctx.trace["t0"],
                              ctx.trace["t1"], modules)
    n = len(_steps_in(ctx))
    if t is None or n == 0:
        return None
    return t / len(_devices(ctx)) / n * 1e3


def idle_pct(ctx) -> Optional[float]:
    """100 x (1 - busy / window), averaged over the cell's devices."""
    if ctx.trace is None:
        return None
    busy = devtrace.busy(ctx.trace["ops"], ctx.trace["t0"], ctx.trace["t1"])
    if not busy:
        return None
    win = ctx.trace["t1"] - ctx.trace["t0"]
    return 100.0 * (1.0 - float(np.mean(list(busy.values()))) / win)


def mfu_pct(ctx) -> Optional[float]:
    """100 x the least time at peak for the window's work / (window x
    chips): each prompt whose first token came in the window, and each
    decode token at its position, by the work count of the configuration's
    reference module."""
    if ctx.peaks is None:
        return None
    ops = dict(int8=0, flops=0)
    for rid, ts in ctx.times.items():
        L = len(ctx.prompts.get(rid, ()))
        if not L:
            continue
        for i, t in enumerate(ts):
            if not ctx.w0 < t <= ctx.w1:
                continue
            w = (ctx.reference.prefill(ctx.config, L) if i == 0
                 else ctx.reference.per_token(ctx.config, L + i - 1, True))
            ops["int8"] += w["int8"]
            ops["flops"] += w["flops"]
    if not ops["int8"] and not ops["flops"]:
        return None
    secs = seconds_at_peak(ops, ctx.peaks)
    return 100.0 * secs / ((ctx.w1 - ctx.w0) * ctx.chips)


def seconds_at_peak(ops: dict, peaks: dict) -> float:
    """The least time the chip could take: int8 ops at the int8 peak plus
    the rest at the bf16 peak."""
    return (ops["int8"] / peaks["int8_ops_per_s"]
            + ops["flops"] / peaks["bf16_flops_per_s"])
