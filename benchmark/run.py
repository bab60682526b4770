#!/usr/bin/env python3
"""One run of one cell, as a new process:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier lines of standard output are JSON records of the run's phases;
the last line is the result.  Exits with another code than 0, and prints
no result, when JAX finds no TPU (or fewer chips than the cell asks
for), or when the checkout holds no program to measure.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    try:
        rc, result = harness.run_cell(args.workload, args.seed,
                                      args.seconds, bool(args.trace),
                                      T_START)
    except Exception as e:                      # noqa: BLE001
        import traceback

        traceback.print_exc()
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if result is None:
        return rc
    harness.print_result(result)
    return rc


if __name__ == "__main__":
    sys.exit(main())
