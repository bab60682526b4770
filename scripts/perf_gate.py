"""Performance regression gate over the committed BENCH_r*.json
trajectory.

Each round's bench record (bench.py output, committed as
BENCH_r<NN>.json) carries a headline metric (`parsed.value`) and the
per-subsystem extras (`parsed.extra`: blocksync_blocks_per_sec,
light_client_headers_per_sec, critical_path_device_share, ...).  The
gate compares the LATEST record against the median of the last N prior
records per metric and exits non-zero when any higher-is-better metric
fell more than --tolerance below its trajectory (or a lower-is-better
one rose above it).  Metrics need at least --min-points prior data
points to gate — a metric that first appears this round passes
trivially, so adding a new bench extra never blocks the round that
introduces it.

Usage:
    python scripts/perf_gate.py --check-only
        gate the newest committed BENCH_r*.json against the rest
    python scripts/perf_gate.py --current BENCH_live.json
        gate a fresh (uncommitted) record against the whole trajectory
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# metrics where smaller is the improvement.  NOTE
# verdict_cache_hit_rate stays in the default higher-is-better set: a
# hit-rate drop means commits started re-verifying signatures.
LOWER_IS_BETTER = {"chaos_recovery_seconds",
                   "chaos_flap_recovery_seconds", "commit_splice_ms",
                   # lightserve fleet serve latency: the coalescer's
                   # whole point is cutting the tail — p99 rising
                   # means merged flushes stopped paying for the wait
                   "light_serve_p99_ms",
                   # per-consumer verify latency under contention
                   # (libs/latledger.py): the ledger exists to keep the
                   # consensus vote tail short while bulk tenants share
                   # the pipeline — either p99 rising is queueing the
                   # decomposition must explain, not an improvement
                   "vote_verify_p99_ms", "bulk_verify_p99_ms",
                   # fleet clock-offset spread: the cross-process merge
                   # solves per-process offsets from p2p send/recv
                   # pairs — the spread widening means the edge solver
                   # degraded toward wall-clock anchors
                   "e2e_fleet_clock_offset_spread_ms"}
# non-metric extras (configs, notes, lists) are skipped by the numeric
# filter; these numerics are ratios/counters, not rates to gate on.
# critical_path_device_share moved here when the signature-verdict
# cache landed: the cache removes device dispatches from the
# proposal->commit critical path BY DESIGN, so the share falling is
# the optimisation working, not a regression — and it rising again is
# not an improvement either.
SKIP = {"rlc_batch", "headline_passes", "vs_baseline",
        "critical_path_device_share",
        # devprof diagnostics (libs/devprof.py): compile seconds flap
        # with persistent-cache warmth across machines/rounds, and the
        # host-bound share moves whenever the verdict cache shifts work
        # off the device — both are readings, not rates to gate on.
        # device_occupancy_fraction does gate (default higher-is-better:
        # chips going idle means the feed path regressed).
        "compile_seconds_total", "host_bound_fraction",
        # the ladder arm of the mixed-commit A/B: a comparison reading
        # against mixed_commit_sigs_per_sec (the gated headline is the
        # MSM-engine arm; the ladder arm moving says nothing about the
        # shipping path).  secp256k1_msm_sigs_per_sec DOES gate, with
        # the default higher-is-better direction.
        "mixed_commit_sigs_per_sec_ladder",
        # the scheduler-OFF arm of the QoS A/B (crypto/sched.py): a
        # diagnostic showing what the vote tail costs WITHOUT priority
        # lanes — it moving says nothing about the shipping path.  The
        # ON-arm vote_verify_p99_ms gates lower-is-better above, and
        # bulk_verify_throughput_ratio gates with the default
        # higher-is-better direction (priority lanes must not tax the
        # bulk tenant's throughput).  bulk_verify_sigs_per_s is the
        # raw numerator, machine-speed-dependent, so a reading.
        "vote_verify_p99_ms_sched_off", "bulk_verify_sigs_per_s",
        # the fleet-wide critical-path device share is a reading for
        # the same reason critical_path_device_share is: optimisations
        # that cut device dispatches LOWER it by design, so neither
        # direction is a regression.  e2e_fleet_height_coverage DOES
        # gate (default higher-is-better: heights losing their
        # cross-process flow edges means the in-band trace context or
        # the clock-aligned merge broke).
        "e2e_fleet_critical_path_device_share"}


def load_record(path: str) -> dict | None:
    """Flatten one bench JSON into {metric: float}; None when the round
    produced no parsed result (rc != 0 runs are committed too)."""
    with open(path) as f:
        rec = json.load(f)
    parsed = rec.get("parsed")
    if not isinstance(parsed, dict) or parsed.get("value") is None:
        return None
    out = {"headline": float(parsed["value"])}
    for k, v in (parsed.get("extra") or {}).items():
        if k in SKIP:
            continue
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = float(v)
    return out


def trajectory(root: str) -> list[tuple[str, dict]]:
    """(path, metrics) for every parseable BENCH_r*.json, round order."""
    paths = sorted(
        glob.glob(os.path.join(root, "BENCH_r*.json")),
        key=lambda p: int(re.search(r"r(\d+)", os.path.basename(p))
                          .group(1)))
    out = []
    for p in paths:
        m = load_record(p)
        if m is not None:
            out.append((p, m))
    return out


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def gate(current: dict, history: list[dict], tolerance: float,
         last_n: int, min_points: int) -> list[dict]:
    """Compare `current` against the trajectory; returns a report row
    per metric with status ok / regressed / skipped."""
    rows = []
    for metric, value in sorted(current.items()):
        prior = [h[metric] for h in history if metric in h][-last_n:]
        if len(prior) < min_points:
            rows.append({"metric": metric, "value": value,
                         "status": "skipped",
                         "reason": f"{len(prior)} prior point(s)"})
            continue
        base = _median(prior)
        if base == 0:
            rows.append({"metric": metric, "value": value,
                         "status": "skipped", "reason": "zero baseline"})
            continue
        if metric in LOWER_IS_BETTER:
            regressed = value > base * (1.0 + tolerance)
        else:
            regressed = value < base * (1.0 - tolerance)
        rows.append({"metric": metric, "value": value,
                     "baseline": round(base, 4),
                     "delta_pct": round((value / base - 1.0) * 100, 2),
                     "status": "regressed" if regressed else "ok"})
    return rows


def staleness_warning(root: str, live_path: str) -> str | None:
    """Warn (don't fail) when the live capture predates the newest
    committed round: its numbers were measured against an older tree,
    so gating or reporting from it undersells work already banked.
    Pairs with the capture_git_rev stamp bench.py writes into extras."""
    try:
        live_m = os.path.getmtime(live_path)
    except OSError:
        return None
    rounds = glob.glob(os.path.join(root, "BENCH_r*.json"))
    if not rounds:
        return None
    newest = max(rounds, key=os.path.getmtime)
    if os.path.getmtime(newest) <= live_m:
        return None
    rev = ""
    try:
        with open(live_path) as f:
            d = json.load(f)
        r = ((d.get("parsed") or {}).get("extra") or {}).get(
            "capture_git_rev") or (d.get("extra") or {}).get(
            "capture_git_rev")
        if r:
            rev = f" (captured at rev {r})"
    except Exception:
        pass
    return (f"warning: {os.path.basename(live_path)}{rev} predates "
            f"{os.path.basename(newest)} — the live capture is stale;"
            f" re-run bench.py before trusting it")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench trajectory regression gate")
    ap.add_argument("--root", default=ROOT,
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--check-only", action="store_true",
                    help="gate the newest committed record against the "
                         "prior ones (no fresh bench run needed)")
    ap.add_argument("--current", metavar="PATH",
                    help="gate this record (e.g. BENCH_live.json) "
                         "against the whole committed trajectory")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed fractional drop below the trajectory "
                         "median (default 0.15)")
    ap.add_argument("--last-n", type=int, default=3,
                    help="trajectory window: median of the last N "
                         "prior values (default 3)")
    ap.add_argument("--min-points", type=int, default=2,
                    help="prior data points a metric needs before it "
                         "gates (default 2)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)

    traj = trajectory(args.root)
    if args.current:
        current = load_record(args.current)
        if current is None:
            print(f"perf_gate: {args.current} has no parsed result",
                  file=sys.stderr)
            return 2
        history = [m for _, m in traj]
        label = args.current
        stale = staleness_warning(args.root, args.current)
        if stale:
            print(f"perf_gate: {stale}", file=sys.stderr)
    else:
        if not args.check_only:
            print("perf_gate: pass --check-only or --current PATH",
                  file=sys.stderr)
            return 2
        if not traj:
            print("perf_gate: no parseable BENCH_r*.json found",
                  file=sys.stderr)
            return 2
        label, current = traj[-1]
        history = [m for _, m in traj[:-1]]

    rows = gate(current, history, args.tolerance, args.last_n,
                args.min_points)
    regressions = [r for r in rows if r["status"] == "regressed"]
    if args.json:
        print(json.dumps({"record": os.path.basename(label),
                          "rows": rows,
                          "regressed": len(regressions)}, indent=2))
    else:
        print(f"perf_gate: {os.path.basename(label)} vs last "
              f"{args.last_n} (tolerance {args.tolerance:.0%})")
        for r in rows:
            if r["status"] == "skipped":
                print(f"  - {r['metric']:<36} {r['value']:>14.2f}  "
                      f"skipped ({r['reason']})")
            else:
                print(f"  - {r['metric']:<36} {r['value']:>14.2f}  "
                      f"{r['status']} ({r['delta_pct']:+.1f}% vs "
                      f"{r['baseline']})")
        print(f"perf_gate: {len(regressions)} regression(s)")
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
