"""Where the time of one traced run of a cell goes, by the program's named
scopes and phase spans.

    python3 bench/spans_report.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does and prints its result line, then
one JSON object: the window's device-idle seconds by the innermost program
span covering them (``host_idle`` where none does), the token step's device
time per decode step by named scope (``none``: outside every scope) beside
``decode_step_ms``, each phase span's mean time per decode step, the
fusions of the step whose fused ops come from more than one scope, and the
longest intervals between decode steps with the spans inside them.  Like
``run.py`` it refuses a machine without a TPU."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from harness import cell, devtrace, spans
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    kept = {}

    class KeepingTracer(cell.Tracer):
        def reduce(self, *a):
            kept.update(super().reduce(*a))
            return kept

    cell.Tracer = KeepingTracer
    try:
        out = cell.run(args.workload, args.seed, args.seconds, True, T_START)
    except cell.NoDevice as e:
        print(f"spans_report: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)

    ops, host, t0, t1 = kept["ops"], kept["spans"], kept["t0"], kept["t1"]
    devices = sorted({o.device for o in ops})
    steps = sorted((s, e) for n, s, e in host if n == "token_step" and t0 < s <= t1)
    n = len(steps)
    report = dict(window_s=t1 - t0, decode_steps=n, devices=devices)
    report["idle_s_by_span"] = dict(sorted(
        spans.idle_by_span(ops, t0, t1, host, devices[0]).items(),
        key=lambda kv: -kv[1]))
    texts = spans.hlo_texts("jit_step") or []
    scopes = spans.hlo_scopes("jit_step") or {}
    secs = spans.scope_seconds(ops, t0, t1, ["jit_step"], scopes)
    per = 1e3 / max(n, 1) / len(devices)
    report["step_ms_by_scope"] = {str(k if k else "none"): v * per
                                  for k, v in secs.items()}
    report["step_ms_sum"] = sum(secs.values()) * per
    prog = devtrace.program_time(ops, t0, t1, ["jit_step"])
    report["decode_step_ms"] = None if prog is None else prog * per
    mixed = spans.fused_families(texts[0]) if texts else {}
    op_s = collections.Counter()
    for o in devtrace.clip(ops, t0, t1):
        if o.module == "jit_step" and o.name in mixed:
            op_s[o.name] += o.end - o.start
    report["mixed_fusions_ms_per_step"] = [
        [k, mixed[k], v * per] for k, v in op_s.most_common(12)]
    span_s = collections.Counter()
    for name, s, e in host:
        if e > t0 and s < t1:
            span_s[name] += min(e, t1) - max(s, t0)
    report["span_ms_per_step"] = {k: v * 1e3 / max(n, 1)
                                  for k, v in span_s.most_common()}
    gaps = sorted(((b[1] - a[1], a[1], b[1]) for a, b in zip(steps, steps[1:])),
                  reverse=True)[:3]
    report["longest_step_intervals"] = [
        dict(ms=g * 1e3, at_s=a - t0,
             spans=[[nm, round((e - s) * 1e3, 3)] for nm, s, e in host
                    if s >= a and e <= b and e - s > 0.2 * g][:12])
        for g, a, b in gaps]
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
