"""Host cost of one ``obs.span``: microseconds per span with no recorder
installed, and with one installed (the benchmark's untraced runs; the
profiler is not running, so the span's TraceAnnotation records nothing).

    PYTHONPATH=src python benchmarks/span_cost.py [--spans 200000]

Prints one JSON line.  The first recorded span imports ``jax.profiler``;
a warm-up pass keeps that out of the timing.  Each figure is the best of
five passes.
"""
from __future__ import annotations

import argparse
import json
import time

from repro import obs


def us_per_span(n: int, recorder: bool) -> float:
    best = float("inf")
    for _ in range(5):
        prev = obs.install_recorder(obs.TraceRecorder() if recorder else None)
        try:
            t0 = time.perf_counter()
            for i in range(n):
                with obs.span("token_read", cat="scheduler", step=i):
                    pass
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
        finally:
            obs.install_recorder(prev)
    return best


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spans", type=int, default=200_000)
    n = ap.parse_args().spans
    us_per_span(1000, True)                      # imports jax.profiler
    print(json.dumps(dict(spans=n, no_recorder_us=us_per_span(n, False),
                          recorder_us=us_per_span(n, True))))


if __name__ == "__main__":
    main()
