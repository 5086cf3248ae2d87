#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator.

    python3 bench/cell.py --workload porto2d.batch --seed 7 --seconds 40 \
        --trace 0

Prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics and device trace (``--trace 1``) as one JSON object, the last
line of standard output; the numbers compared with the reference are
the last lines of standard error. Exits non-zero, and prints no result,
when JAX finds no TPU or fewer chips than the cell asks for.
BENCHMARK.json names the cells; bench/benchlib/runner.py says what a run
does.
"""
import time

T_START = time.perf_counter()           # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchlib import runner
    try:
        return runner.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except runner.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
