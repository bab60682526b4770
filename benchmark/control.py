#!/usr/bin/env python3
"""The control of a sync cell, and sound runs beside it, in one process
after one set-up of the device programs:

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 --seconds 5

The system runs no model and states no precision, so the control breaks
one guarantee that the configuration states: it is the program with its
own host path switched on (the deferred-batch and batch-seam thresholds
raised past any commit), the step that would tempt a later PR - every
signature is still verified and the stored chain is right, but the chip
did not do it, which breaks the configuration's fourth guarantee.  For
each seed this prints one line for the sound run
(`correct` must be true) and one for the control (`correct` must be
false), each with every number compared beside its limit.  The
benchmark's own runs never run this; it needs a TPU as they do.
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@contextlib.contextmanager
def host_path():
    """The control: nothing reaches the device lane."""
    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.types import validation

    old = (validation.DeferredSigBatch.DEVICE_THRESHOLD,
           cb.DEVICE_THRESHOLD)
    validation.DeferredSigBatch.DEVICE_THRESHOLD = 10 ** 9
    cb.DEVICE_THRESHOLD = 10 ** 9
    try:
        yield
    finally:
        validation.DeferredSigBatch.DEVICE_THRESHOLD, \
            cb.DEVICE_THRESHOLD = old


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from benchmark import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    ok = True
    for seed in seeds:
        for arm, ctx in (("sound", contextlib.nullcontext),
                         ("control", host_path)):
            with ctx():
                rc, result = harness.run_cell(
                    args.workload, seed, args.seconds, False,
                    time.perf_counter())
            if result is None:
                return rc
            line = {"arm": arm, "seed": seed,
                    "correct": result["correct"],
                    "attempted": result["attempted"],
                    "failed": result["failed"],
                    "metrics": {k: v["value"]
                                for k, v in result["metrics"].items()},
                    "compared": {k: v["value"]
                                 for k, v in result["compared"].items()}}
            print("CONTROL " + json.dumps(line), flush=True)
            ok = ok and (result["correct"] == (arm == "sound"))
    print("CONTROL_OK" if ok else "CONTROL_MISMATCH", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
