"""Benchmark driver — one function per paper table.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--full] [--check]

Prints each table and a ``name,us_per_call,derived`` CSV summary line per
benchmark (derived = the table's headline number).  Also runs the hot-path
perf microbenchmarks plus the fleet-, token-granular-serving-,
chaos-recovery-, and audit-report microbenchmarks and writes
``BENCH_10.json`` (dispatch / reduction / decode / fleet / tile-adaptation
/ serving / chaos / audit numbers — this PR's point on the perf
trajectory).  ``--check`` then diffs the artifact's deterministic counters
against the committed baseline (``benchmarks/baselines/BENCH_9.json``) and
exits non-zero on regression — wall times are reported informationally
only (see ``benchmarks.regress``).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (adaptive_table, app_table, audit_report, chaos_table,
               component_table, fleet_table, hw_table, perf_table, regress,
               roofline_table, serving_table)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small fast subset")
    ap.add_argument("--full", action="store_true", help="all multipliers + ALL parts")
    ap.add_argument("--bench-out", default="BENCH_10.json",
                    help="perf/fleet/tile/serving/chaos/audit JSON artifact "
                         "path")
    ap.add_argument("--check", action="store_true",
                    help="fail on deterministic-counter regression vs --baseline")
    ap.add_argument("--baseline", default="benchmarks/baselines/BENCH_9.json",
                    help="committed baseline artifact for --check")
    ap.add_argument("--audit", default=None, metavar="PATH",
                    help="audit.jsonl for the audit report row (default: "
                         "synthesized promoted-retune history)")
    args = ap.parse_args()

    csv = ["name,us_per_call,derived"]

    t0 = time.time()
    comp = component_table.run(quick=args.quick)
    print(component_table.format_table(comp))
    n_calls = len(comp["rows"])
    best = max(r["swapper_reduction"] for r in comp["rows"])
    csv.append(f"component_table,{1e6*(time.time()-t0)/max(n_calls,1):.0f},"
               f"best_mae_reduction={100*best:.1f}%")

    t0 = time.time()
    app = app_table.run(quick=args.quick, full=args.full)
    print("\n" + app_table.format_table(app))
    gains = []
    for r in app["rows"]:
        base, swapped = r["noswap"], r["swapper_app"]
        if r["minimize"] and base > 0:
            gains.append((base - swapped) / base)
        elif not r["minimize"] and base > 0:
            gains.append((swapped - base) / base)
    best_gain = max(gains) if gains else 0.0
    csv.append(f"app_table,{1e6*(time.time()-t0)/max(len(app['rows']),1):.0f},"
               f"best_app_gain={100*best_gain:.1f}%")

    t0 = time.time()
    ad = adaptive_table.run(quick=args.quick)
    print("\n" + adaptive_table.format_table(ad))
    csv.append(f"adaptive_table,{1e6*(time.time()-t0)/max(len(ad['rows']),1):.0f},"
               f"adaptive_gain_vs_static={100*ad['gain_vs_static']:.1f}%"
               f" retunes={ad['retunes']}"
               f" telemetry_us_per_step={ad['telemetry_us_per_step']:.0f}"
               f" tile_best_gain={100*ad['tile']['best_gain']:.1f}%")

    t0 = time.time()
    perf = perf_table.run(quick=args.quick)
    print("\n" + perf_table.format_table(perf))
    d = perf["matmul_dispatch"]
    csv.append(f"perf_table,{1e6*(time.time()-t0):.0f},"
               f"dispatch={d['static_2mm']['dot_generals']}->"
               f"{d['static_stacked']['dot_generals']}"
               f" reduction_steps_ratio={perf['kernel_reduction']['reduction_step_ratio']:.0f}x"
               f" decode_speedup={perf['decode']['speedup']:.2f}x")

    t0 = time.time()
    fleet = fleet_table.run(quick=args.quick)
    print("\n" + fleet_table.format_table(fleet))
    fa = fleet["adaptive_decode"]
    csv.append(f"fleet_table,{1e6*(time.time()-t0):.0f},"
               f"adaptive_dispatch={fa['stepwise_dispatch_per_gen']}->"
               f"{fa['fused_dispatch_per_gen']}"
               f" fused_speedup={fa['speedup']:.2f}x"
               f" slot_util={100*fleet['scheduler']['slot_utilization']:.0f}%")

    t0 = time.time()
    srv = serving_table.run(quick=args.quick)
    print("\n" + serving_table.format_table(srv))
    csv.append(f"serving_table,{1e6*(time.time()-t0):.0f},"
               f"occupancy={srv['wave_occupancy']:.2f}->"
               f"{srv['token_granular_occupancy']:.2f}"
               f" splices={srv['token_splices']}"
               f" bit_identical={srv['bit_identical_requests']}"
               f" qor_live={srv['qor_attribution_live']}"
               f" statsd_lines={srv['statsd_lines_sent']}"
               f" sampled_identical={srv['sampled_bit_identical']}"
               f" arrival_goodput_ratio="
               f"{srv['arrivals']['goodput_ratio']:.2f}")

    t0 = time.time()
    cha = chaos_table.run(quick=args.quick)
    print("\n" + chaos_table.format_table(cha))
    csv.append(f"chaos_table,{1e6*(time.time()-t0):.0f},"
               f"faults={cha['faults_injected']}"
               f" rollbacks={cha['rollbacks_recovered']}/"
               f"{cha['rollbacks_triggered']}"
               f" survived_all={cha['survived_all']}")

    t0 = time.time()
    aud = audit_report.run(quick=args.quick, audit_path=args.audit)
    print("\n" + audit_report.format_table(aud))
    gr = aud["gain_realization"]
    csv.append(f"audit_report,{1e6*(time.time()-t0):.0f},"
               f"rejection_rate={aud['rejection_rate']:.2f}"
               f" gain_realization={'-' if gr is None else f'{gr:.2f}'}"
               f" slo_veto_blocks_promotion="
               f"{aud['slo_veto_blocks_promotion']}")

    perf["fleet"] = fleet
    perf["tile_adaptation"] = ad["tile"]
    perf["serving"] = srv
    perf["chaos"] = cha
    perf["audit"] = aud
    perf_table.write_json(perf, args.bench_out)
    print(f"(perf+fleet+tile+serving+chaos+audit tables written to "
          f"{args.bench_out})")

    t0 = time.time()
    hw = hw_table.run()
    print("\n" + hw_table.format_table(hw))
    csv.append(f"hw_table,{1e6*(time.time()-t0):.0f},"
               f"mxu_swap_overhead={100*hw['mxu_swap_overhead']:.1f}%")

    rl = roofline_table.run()
    if rl["n"]:
        print("\nRoofline (from dry-run artifacts):")
        print(roofline_table.format_table(rl["rows"]))
        ok = [r for r in rl["rows"] if r.get("status") == "ok"]
        if ok:
            bestr = max(r["roofline_fraction"] for r in ok)
            csv.append(f"roofline_table,0,best_roofline_fraction={100*bestr:.1f}%")
    else:
        print("\n(roofline: no dryrun_*.jsonl found — run repro.launch.dryrun --all)")

    print("\n" + "\n".join(csv))

    if args.check:
        failures, notes = regress.check_files(args.bench_out, args.baseline)
        print(f"\nperf gate vs {args.baseline}:")
        for line in notes:
            print(f"  {line}")
        if failures:
            for line in failures:
                print(f"  REGRESSION {line}")
            sys.exit(1)
        print("  gate: ok (no deterministic-counter regressions)")


if __name__ == "__main__":
    main()
