#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check, on the chip at
the cell's own size, all seeds in one process (the compiled programs are
shared):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control 3] [--witness 3] [--rounds 0,1]

For each seed: one study through `run_experiment` as the window runs it,
then the numbers of fedbench/checks.py for the program (the lower
readings).  For the first `--control` seeds also the same numbers for
the bfloat16 reference put in the program's place (the control), and for
the reference with a planted fault of local training: half of every
batch left out, and a step that returns the params unchanged.  For the
first `--witness` seeds also the reference at `highest` matmul precision
in the program's place, and the program against a reference at
`highest`: how far precision alone moves each number.  `--rounds` sets the checked round of
successive seeds instead of the seed's own draw.  Prints one JSON line
per seed and reading, with the median client's training numbers beside
the worst's, and last the limits that `propose` sets from them; the
limits in bench/limits/ are set from these readings, as PERF.md records.
"""
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
sys.path.insert(0, str(BENCH))


# a reading a planted fault gives by construction: an evaluation whose
# accuracy is raised by 0.1 reads 0.1 for eval_acc_gap
BUILT_IN = {"eval_acc_gap": 0.1}
# how many times the lower reading a stand-in's least reading must be to
# set the upper one
RATIO = {"control": 3, "unchanged": 3, "half_batch": 10}


def propose(rows):
    """Limits from the readings: lower = the largest program reading;
    upper = the least of each stand-in's least reading (the control,
    each planted fault, BUILT_IN) where that is not 0 and RATIO times
    the lower or more (10x for BUILT_IN); limit = lower^(1/3)
    upper^(2/3), leaving more room above the lower than below the upper,
    or upper / 10 where the lower is 0; None where no reading is an
    upper one."""
    from fedbench.checks import NUMBERS
    out = {}
    for k in NUMBERS:
        def least(kind):
            v = [r[k] for r in rows if r["reading"] == kind]
            return min(v) if v else None
        lower = max(r[k] for r in rows if r["reading"] == "program")
        cands = [v for v, ratio in
                 [(least(kind), r) for kind, r in RATIO.items()]
                 + [(BUILT_IN.get(k), 10)] if v and v >= ratio * lower]
        if not cands:
            out[k] = None
            continue
        upper = min(cands)
        limit = upper / 10 if lower == 0 else lower ** (1 / 3) * upper ** (
            2 / 3)
        out[k] = float(f"{limit:.2g}")
    return out


def main() -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--rounds", default="")
    args = ap.parse_args()
    from fedbench import harness
    from fedbench.registry import Cell, _json
    cell = Cell(args.workload)
    harness.check_devices(cell.chips, _json(BENCH / "peaks.json"))
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    bench = harness.Bench(cell)
    rounds = [int(r) for r in args.rounds.split(",") if r]
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        bench.prepare(seed)
        if rounds:
            bench.cap.round_t = rounds[i % len(rounds)]
        bench.study()
        readings = [("program", bench.checker(), "program")]
        chk = readings[0][1]
        if i < args.control:
            readings += [(k, chk, k) for k in RATIO]
        if i < args.witness:
            hi = bench.checker("highest")
            hi.trained = chk.trained
            readings += [("highest", chk, "highest"),
                         ("program_vs_highest", hi, "program")]
        for name, chk, impl in readings:
            nums = chk.numbers(bench.cap, impl=impl)
            median = {f"median_{k}": v for k, v in chk.median.items()}
            rows.append({"seed": seed, "reading": name,
                         "round_t": bench.cap.round_t,
                         "round_e": bench.cap.round_e, **nums, **median,
                         "merge_error": chk.merge_error})
            print(json.dumps(rows[-1]), flush=True)
        print(f"calibrate: seed {seed} {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
    print(json.dumps({"limits": propose(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
