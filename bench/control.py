"""Readings that set the correctness limit of a cell, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: one run of the cell as ``run.py`` makes it
(a short window), then its served tokens replayed through the reference
and, at the same rows, the fp8 control's first choice.  Prints one JSON
line per seed: whether the program and the control in its place are
correct by the configuration's statistic and limit, the control's value
of that statistic, and for both the widest and mean gap and the share of
tokens not the reference's first.  The benchmark's own runs never run
the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import gc

    import jax

    from harness import cell
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = cell.run(args.workload, seed, args.seconds, False,
                           time.perf_counter(), control=True)
        except cell.NoDevice as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        ctl = out["control"]
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              program_correct=out["correct"],
                              control_correct=ctl.pop("correct"), **ctl)),
              flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
