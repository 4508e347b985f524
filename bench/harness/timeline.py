"""Per-request token times, rebuilt from the program's spans and the
window source's step clock.

In token mode the batcher, at each step boundary, reads the step's tokens
to the host, retires finished slots (``retire`` instants), polls the
arrival source (the source notes the host time: the step's tokens are on
the host by then) and admits into free slots: ``admit_dispatch`` (the
prefill is dispatched), then ``admit`` and a ``splice`` instant, by which
the request's first token is on the host.  A spliced request then gets one
token in every decode step (``token_step`` spans, numbered by ``step``)
that starts after its splice and before its retirement.

All times here are host ``perf_counter`` seconds."""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Steps:
    start: Dict[int, float]          # step -> host time its dispatch began
    done: Dict[int, float]           # step -> host time its tokens were read


def parse(events: list, offset: float, step_done: Dict[int, float]):
    """Split recorder events into steps and per-request instants.
    ``step_done`` maps the batcher's ``decode_steps`` counter to the host
    time the source saw it, so step ``k`` is done at ``step_done[k + 1]``."""
    start = {}
    splice, dispatch, retire = {}, {}, {}
    for ev in events:
        t = offset + ev["ts"] / 1e6
        a = ev.get("args", {})
        if ev["name"] == "token_step" and ev["ph"] == "X":
            start[int(a["step"])] = t
        elif ev["name"] == "splice":
            splice[int(a["rid"])] = t
        elif ev["name"] == "admit_dispatch" and ev["ph"] == "X":
            dispatch[int(a["rid"])] = t
        elif ev["name"] == "retire":
            retire[int(a["rid"])] = t
    done = {k - 1: t for k, t in step_done.items() if k >= 1}
    return Steps(start, done), splice, dispatch, retire


def token_times(steps: Steps, splice: Dict[int, float],
                retire: Dict[int, float]) -> Dict[int, List[float]]:
    """rid -> host times of each of its tokens (the first at its splice)."""
    order = sorted(steps.start)
    starts = [steps.start[k] for k in order]
    out = {}
    for rid, s in splice.items():
        i = bisect.bisect_right(starts, s)          # first step after splice
        end = retire.get(rid)
        j = (bisect.bisect_left(starts, end) if end is not None
             else len(order))                        # steps before retiring
        times = [s]
        for k in order[i:j]:
            if k in steps.done:
                times.append(steps.done[k])
        out[rid] = times
    return out


def window_stats(times: Dict[int, List[float]], due: Dict[int, float],
                 dispatch: Dict[int, float], w0: float, w1: float) -> dict:
    """The window's raw samples: output tokens, time to first token of each
    request due in the window (a request with no token yet enters with its
    wait so far), the gap before each later token, and due -> prefill
    dispatch (queue wait)."""
    def inside(t):
        return w0 < t <= w1

    tokens = sum(1 for ts in times.values() for t in ts if inside(t))
    ttft, wait = [], []
    for rid, d in due.items():
        if not inside(d):
            continue
        first = times.get(rid, [None])[0]
        ttft.append((first if first is not None and first <= w1 else w1) - d)
        sent = dispatch.get(rid)
        wait.append((sent if sent is not None and sent <= w1 else w1) - d)
    itl = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
           if inside(b)]
    return dict(tokens=tokens, ttft=np.asarray(ttft), itl=np.asarray(itl),
                queue_wait=np.asarray(wait), seconds=w1 - w0,
                due=sum(1 for d in due.values() if inside(d)))


def percentile(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(x, q)) if len(x) else None
