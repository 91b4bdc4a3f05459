#!/usr/bin/env python3
"""Read a cell's correctness numbers and its control's, seed by seed.

    python3 bench/control.py --workload <cell> --seeds <a,b,...> --seconds <s>

For each seed, one run of the cell as ``bench/run.py`` makes it (on the
chip, at the cell's size), then the same comparison with the plain
reference computed one precision below the configuration's (``--dtype``,
bfloat16 for float32) in the program's place, judged as the run judges
its own.  Prints one JSON line per seed: the program's numbers and
``correct``, and the control's.  The
limits in the traffic files are set from these readings (PERF.md).  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            result, run, loop = bench_run.run_cell_state(
                args.workload, seed, args.seconds, False, t_start=t0)
        except bench_run.BenchError as e:
            print(f"bench: {e}", file=sys.stderr, flush=True)
            return 2
        control = loop.check(run, args.dtype)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": result["correct"],
                          "program": result["checks"],
                          "control_correct": bench_run.harness.judge(control),
                          "control": control, "dtype": args.dtype}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
