"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` with its configuration and
traffic, builds the serving stack, warms up every shape the cell uses,
measures one window of ``--seconds``, checks the served tokens against the
plain reference, and prints one JSON object as the last line of standard
output.  Exits non-zero, printing no result, without a TPU or with fewer
chips than the cell asks for.  JAX's persistent compilation cache lives in
``.jax_cache/`` of the checkout, or where ``JAX_COMPILATION_CACHE_DIR``
says.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from harness import cell
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, small ones too, goes to the cache: a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except cell.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
