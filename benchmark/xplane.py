"""From a profiler trace (.xplane.pb) to busy/idle, kernel time and the
breakdown.  Reads the file with jax.profiler.ProfileData and nothing
else.

A TPU's plane is named "/device:TPU:<n>".  Its line "XLA Modules" holds
one event for each whole program the chip ran (named
jit_<function>(<fingerprint>)), "XLA Ops" one for each operation inside
them - a quarter of a million for one dispatch of an XLA-path RLC
program, which is why a slice is short.  Busy time is the union of the
programs' intervals; a program's device time is the sum of its module
events.  Host threads are lines of the plane "/host:CPU".  All
planes share one clock, in nanoseconds; `clock_offset` maps it onto
time.perf_counter through a mark that the harness writes into the trace
(a TraceAnnotation named MARK) at an instant whose perf_counter it
keeps.
"""

from __future__ import annotations

import glob
import os

MARK = "benchmark_mark"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DROPPED = "Trace Buffers Dropped"

# HLO module name (jit_<python function>) -> the program's kind
MODULE_KINDS = {
    "jit_rlc_verify_kernel_cached_a": "ed25519_rlc_cached",
    "jit_rlc_verify_kernel": "ed25519_rlc",
    "jit__msm_tables": "ed25519_a_tables",
}


def find_xplane(log_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def module_kind(name: str) -> str | None:
    base = name.split("(")[0]
    return MODULE_KINDS.get(base)


def _union(intervals: list) -> tuple[float, list]:
    """Total covered length and the merged intervals, of (start, end)."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _short(op_name: str) -> str:
    """An XLA op's event name is its whole HLO line; keep what is left
    of " = " (%slice_multiply_fusion.4409), which names the opcode."""
    return op_name.partition(" = ")[0][:120]


def load(path: str) -> dict:
    """{"devices": {plane: {"modules": [(name, start_ns, end_ns)],
    "op_seconds": {short name: seconds}, "n_ops": int, "dropped_ns":
    start of a "Trace Buffers Dropped" event or None}}, "mark_ns": the
    mark's start or None, "lines": [(plane, line, events)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    mark_ns = None
    summary = []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            n = 0
            if is_dev:
                dev = devices.setdefault(
                    plane.name, {"modules": [], "op_seconds": {},
                                 "n_ops": 0, "dropped_ns": None})
                if line.name == MODULES_LINE:
                    dev["modules"] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                    n = len(dev["modules"])
                elif line.name == OPS_LINE:
                    # millions of events where a program loops: summed
                    # as they stream by, never kept
                    ops = dev["op_seconds"]
                    for e in line.events:
                        n += 1
                        k = e.name
                        ops[k] = ops.get(k, 0.0) + e.duration_ns / 1e9
                    dev["n_ops"] = n
                    dev["op_seconds"] = {}
                    for k, v in ops.items():
                        sk = _short(k)
                        dev["op_seconds"][sk] = \
                            dev["op_seconds"].get(sk, 0.0) + v
                else:
                    for e in line.events:
                        n += 1
                        if e.name == DROPPED and dev["dropped_ns"] is None:
                            dev["dropped_ns"] = e.start_ns
            else:
                for e in line.events:
                    n += 1
                    if mark_ns is None and e.name == MARK:
                        mark_ns = e.start_ns
            summary.append((plane.name, line.name, n))
    return {"devices": devices, "mark_ns": mark_ns, "lines": summary}


def reduce(trace: dict, spans: list | None = None,
           mark_perf: float | None = None, stop_perf: float | None = None,
           top: int = 10) -> dict:
    """busy_s and window_s (averaged over the device planes), the device
    seconds of each program kind, and the breakdown.

    The slice runs from the mark (or the first program) to the
    profiler's stop, or to where it dropped its buffers.  Busy time is
    the union of the PROGRAMS' intervals ("XLA Modules"): a program's
    operations follow one another without the host, and their events
    number millions where a program loops.  spans are the host's (name,
    start, end) on perf_counter; with mark_perf, the perf_counter of the
    trace's mark, each idle gap is named by the host stage that covered
    most of it, and the runs of the known programs are listed on that
    clock."""
    devs = {k: v for k, v in trace["devices"].items() if v["modules"]}
    if not devs:
        return {}
    offset = None
    if trace.get("mark_ns") is not None and mark_perf is not None:
        offset = mark_perf - trace["mark_ns"] / 1e9
    busy, windows, gaps = [], [], []
    kinds: dict = {}
    op_time: dict = {}
    mod_time: dict = {}
    programs: list = []
    lo_all, hi_all = None, None
    for name in sorted(devs):
        mods = devs[name]["modules"]
        lo = trace["mark_ns"] if trace.get("mark_ns") is not None \
            else min(a for _, a, _ in mods)
        hi = max(b for _, _, b in mods)
        if offset is not None and stop_perf is not None:
            hi = max(hi, (stop_perf - offset) * 1e9)
        if devs[name]["dropped_ns"] is not None:
            hi = min(hi, devs[name]["dropped_ns"])
        clipped = [(m, max(a, lo), min(b, hi), lo <= a and b <= hi)
                   for m, a, b in mods if min(b, hi) > max(a, lo)]
        total, merged = _union([(a, b) for _, a, b, _ in clipped])
        busy.append(total / 1e9)
        windows.append((hi - lo) / 1e9)
        lo_all = lo if lo_all is None else min(lo_all, lo)
        hi_all = hi if hi_all is None else max(hi_all, hi)
        for mname, a, b, whole in clipped:
            base = mname.split("(")[0]
            mod_time[base] = mod_time.get(base, 0.0) + (b - a) / 1e9
            kind = module_kind(mname)
            if kind:
                rec = kinds.setdefault(kind, {"seconds": 0.0, "count": 0})
                rec["seconds"] += (b - a) / 1e9
                rec["count"] += 1
                programs.append((a, kind, (b - a) / 1e9, whole))
        for k, v in devs[name]["op_seconds"].items():
            op_time[k] = op_time.get(k, 0.0) + v
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for e0, s1 in zip(edges[0::2], edges[1::2]):
            if s1 > e0:
                gaps.append((s1 - e0, e0, s1))
    n = len(busy)
    out = {"busy_s": sum(busy) / n, "window_s": sum(windows) / n,
           "n_device_planes": n, "kinds": kinds,
           "n_op_events": sum(d["n_ops"] for d in devs.values())}
    if offset is not None:
        out["range_perf"] = (lo_all / 1e9 + offset, hi_all / 1e9 + offset)
        # each run of a known program, in the order the device ran them:
        # [start on perf_counter, kind, seconds, whole (not cut by an
        # edge of the slice)]
        out["programs"] = [[a / 1e9 + offset, kind, secs, whole]
                           for a, kind, secs, whole in sorted(programs)]
    # the operations where the trace has them, else the programs
    device_ops = sorted((op_time or mod_time).items(),
                        key=lambda kv: -kv[1])[:top]
    named: dict = {}
    for length, a, b in gaps:
        stage = "unnamed"
        if offset is not None and spans is not None:
            stage = _stage_of(spans, a / 1e9 + offset, b / 1e9 + offset)
        named[stage] = named.get(stage, 0.0) + length / 1e9
    out["breakdown"] = {
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[k, v] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
    }
    return out


def _stage_of(spans: list, a: float, b: float) -> str:
    """The host stage whose spans cover most of [a, b]; "host_other"
    where none covers any of it."""
    cover: dict = {}
    for name, s, e in spans:
        lo, hi = max(a, s), min(b, e)
        if hi > lo:
            cover[name] = cover.get(name, 0.0) + hi - lo
    if not cover:
        return "host_other"
    return max(cover.items(), key=lambda kv: kv[1])[0]
