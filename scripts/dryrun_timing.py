#!/usr/bin/env python3
"""Run __graft_entry__.dryrun_multichip(8) and commit its per-phase
timing record to MULTICHIP_local_timing.json.

The driver gives the dryrun an 1800 s subprocess window;
tests/test_tools.py (tier 1) requires the committed record to show
>= 2x headroom against the 900 s half-window (total <= 450 s).  Run
this after any change to the dryrun phases:

    python scripts/dryrun_timing.py            # warm-cache timing
    python scripts/dryrun_timing.py --cold     # wipe the jax cache first
"""

from __future__ import annotations

import datetime
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "MULTICHIP_local_timing.json")
# where ops/compile_hook.ensure_compile_cache puts it
CACHE = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
    os.path.join(ROOT, ".jax_cache")
BUDGET_S = 900.0


def main() -> int:
    sys.path.insert(0, ROOT)
    cold = "--cold" in sys.argv
    if cold and os.path.isdir(CACHE):
        shutil.rmtree(CACHE)
    import __graft_entry__ as graft

    t0 = time.perf_counter()
    timings = graft.dryrun_multichip(8)
    wall = round(time.perf_counter() - t0, 3)
    ok = timings is not None and "total" in timings
    record = {
        "ok": bool(ok),
        "n_devices": 8,
        "timings": timings,
        "parent_wall_seconds": wall,
        "budget_seconds": BUDGET_S,
        "headroom_x": round(BUDGET_S / timings["total"], 1)
        if ok and timings["total"] else None,
        "cache": "cold" if cold else "warm",
        "generated_utc": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%d %H:%M:%S"),
        "generated_by": "scripts/dryrun_timing.py",
    }
    with open(OUT, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(json.dumps(record, indent=2))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
