#!/usr/bin/env python3
"""Runs one cell of the benchmark once, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX start, the cell's data from the seed, its weights, one
warm-up study that compiles every program the window uses) is timed as
setup_s.  The window then runs whole studies through the program's
`run_experiment`, back to back, while less than `--seconds` has passed;
round_s is the window's wall time over the rounds completed.  After the
window the last study's sampled rounds are compared with the plain
reference (see fedbench/checks.py).  With `--trace 1` the window runs
under the profiler with the per-layer metrics' spans installed, and the
result carries those metrics instead.

The last line of standard output is the JSON result; the numbers
compared for `correct` end standard error and the result line.  A run
that finds no TPU, or fewer chips than the cell asks for, exits 2 and
prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache lives inside the checkout, at a
# fixed path, whatever the environment says
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from fedbench.harness import NoChip, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=T0)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
