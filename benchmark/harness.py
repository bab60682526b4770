"""One run of one cell: set-up, warm-up pass, measured window, readings,
the comparison with the reference, the result line.

Driven by data.  The cell's configuration is the file that
BENCHMARK.json names for it, its traffic mix is traffic/<traffic>.json,
the mix's `mode` is modes/<mode>.py, and every per-layer metric of the
manifest is metrics/<name>.json or metrics/<name>.py.  Nothing in this
file knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

EXIT_NO_PROGRAM = 4
EXIT_NO_CHIP = 3


class BenchmarkError(Exception):
    """The run cannot produce a result line."""


def log(rec: dict) -> None:
    """Earlier lines of standard output: one JSON object each."""
    print(json.dumps(rec, sort_keys=True, default=str), flush=True)


# -- the manifest and the files it names ---------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, manifest_path: str | None = None) -> dict:
    """The cell's entry, its configuration and its traffic mix."""
    manifest = load_json(manifest_path
                         or os.path.join(REPO, "BENCHMARK.json"))
    root = os.path.dirname(manifest_path) if manifest_path else REPO
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json"
                             f" (has: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return {"manifest": manifest, "cell": cell, "config": config,
            "traffic": traffic}


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, workload: str, group: str) -> list:
    """The manifest's metrics of one group that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


# -- device ----------------------------------------------------------------------

PEAKS = {
    # device_kind: peaks of one chip, with their source
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def device_info() -> dict:
    """First touch of JAX."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- native libraries ----------------------------------------------------------------

def build_native() -> float:
    """Build native/protowire and native/bls12381 where the checkout has
    no library yet (they are git-ignored, so a new checkout has none)."""
    t0 = time.perf_counter()
    if not (shutil.which("make") and shutil.which("g++")):
        return 0.0
    for name, lib in (("protowire", "libcommitcodec.so"),
                      ("bls12381", "libbls12381.so")):
        d = os.path.join(REPO, "native", name)
        if os.path.isdir(d) and not os.path.exists(os.path.join(d, lib)):
            p = subprocess.run(["make", "-C", d], capture_output=True,
                               text=True)
            if p.returncode != 0:
                raise BenchmarkError(
                    f"native/{name} build failed: {p.stderr[-400:]}")
    return time.perf_counter() - t0


# -- instruments (a copy of chip_smoke.install_instruments) -----------------------------

@dataclasses.dataclass
class Instruments:
    device_metrics: object
    recorder: object
    devprof: object
    tracer: object

    def rlc_fallbacks(self) -> float:
        m = self.device_metrics.rlc_fallbacks
        with m._mtx:
            return m._values.get((), 0.0)

    def events_since(self, seq: int) -> list:
        return [e for e in self.recorder.events() if e["seq"] >= seq]

    def dispatches(self) -> dict:
        return {(p["kind"], tuple(p["shape"] or ())): p["dispatches"]
                for p in self.devprof.snapshot()["programs"]}

    def compiles(self) -> tuple:
        c = self.devprof.snapshot()["compile"]
        return c["count"], c["seconds_total"]


def install_instruments() -> Instruments:
    """Install what the counters need BEFORE any node starts: with no
    DeviceMetrics / FlightRecorder installed, rlc_fallbacks and the EV_*
    events are dropped and every check on them passes vacuously."""
    from cometbft_tpu.libs import devprof, flightrec
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.libs import trace as libtrace
    from cometbft_tpu.ops import compile_hook

    from .spans import SpanTracer

    dm = libmetrics.DeviceMetrics(libmetrics.Registry())
    libmetrics.set_device_metrics(dm)
    rec = flightrec.FlightRecorder(capacity=1 << 17)
    flightrec.set_recorder(rec)
    dp = devprof.DevprofRecorder()
    devprof.set_recorder(dp)
    compile_hook.install(dp)
    tracer = SpanTracer()
    libtrace.set_tracer(tracer)
    return Instruments(dm, rec, dp, tracer)


def uninstall_instruments() -> None:
    from cometbft_tpu.libs import devprof, flightrec
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.libs import trace as libtrace
    from cometbft_tpu.ops import compile_hook

    libmetrics.set_device_metrics(None)
    flightrec.set_recorder(None)
    devprof.set_recorder(None)
    compile_hook.uninstall()
    libtrace.set_tracer(None)


# -- the profiler slice ------------------------------------------------------------------

class Profile:
    """A slice of a steady pass under the JAX profiler, Python tracing
    off.  start() writes the mark that puts the trace on
    time.perf_counter."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.mark_perf = None
        self.t0 = self.t1 = None
        self._on = False
        self._lock = threading.Lock()

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._on = True
        from .xplane import MARK

        self.mark_perf = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARK):
            pass
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        with self._lock:
            if self._on:
                self._on = False
                self.t1 = time.perf_counter()
                jax.profiler.stop_trace()


# -- one run -----------------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric's reader may read."""
    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    device: dict
    setup: dict = dataclasses.field(default_factory=dict)
    window_s: float = 0.0
    units: int = 0                  # blocks (headers) done in the window
    spans: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    profile: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, check_chip: bool = True,
             manifest_path: str | None = None) -> tuple[int, dict | None]:
    """(exit code, result).  check_chip=False is for the tests, which
    drive everything but the look for a chip; run.py has no such flag."""
    if not os.path.isdir(os.path.join(REPO, "cometbft_tpu")):
        print("benchmark: no cometbft_tpu/ beside benchmark/: nothing to "
              "measure", file=sys.stderr)
        return EXIT_NO_PROGRAM, None
    spec = load_cell(workload, manifest_path)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    mode = load_module("modes", traffic["mode"])

    device = device_info()
    log({"phase": "device", **device, "cpu_count": os.cpu_count()})
    if check_chip and (device["platform"] != "tpu"
                       or device["count"] < int(cell["chips"])):
        print(f"benchmark: {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {device['count']} x {device['platform']}",
              file=sys.stderr)
        return EXIT_NO_CHIP, None
    if device["kind"] not in PEAKS and device["platform"] == "tpu":
        raise BenchmarkError(f"no peaks known for {device['kind']!r}")

    from cometbft_tpu.ops import compile_hook

    cache_dir = compile_hook.ensure_compile_cache()
    run = Run(workload, config, traffic, seed, seconds, device,
              peaks=PEAKS.get(device["kind"], {}))
    run.setup["import_s"] = time.perf_counter() - t_start
    run.setup["native_s"] = build_native()
    inst = install_instruments()
    session = mode.Session(run, inst, cache_dir, log)
    try:
        session.setup()
        run.setup["total_s"] = time.perf_counter() - t_start
        log({"phase": "setup", **{k: round(v, 3)
                                  for k, v in run.setup.items()}})
        profile = Profile(os.path.join(HERE, ".profile")) if trace else None
        if profile is not None:
            # a pass of its own under the profiler, before the window:
            # stopping a trace takes a minute and would sit in the
            # window's spans
            session.profile_pass(profile)
        session.window(seconds)
        peak = memory_peak_bytes()
        if profile is not None:
            t0 = time.perf_counter()
            read_profile(run, inst, profile)
            run.profile.update(session.slice_work(
                *run.profile.get("range_perf", (profile.t0, profile.t1))))
            log({"phase": "profile", "read_s":
                 round(time.perf_counter() - t0, 2),
                 "slice_s": run.profile.get("window_s"),
                 "cpu_count": os.cpu_count()})
        t0 = time.perf_counter()
        compared, attempted, failed = session.check()
        log({"phase": "check", "seconds": round(time.perf_counter() - t0, 2)})
    finally:
        session.close()
        uninstall_instruments()

    manifest = spec["manifest"]
    metrics = {}
    if trace:
        from .readers import read_metric

        for m in cell_metrics(manifest, workload, "per_layer"):
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell_metrics(manifest, workload, "end_to_end"):
            v = session.end_to_end(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {**device, "memory_peak_bytes": peak}
    result = {"correct": all(v["value"] <= v["limit"]
                             for v in compared.values()),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if trace and run.profile.get("busy_s"):
        dev["busy_s"] = run.profile["busy_s"]
        dev["window_s"] = run.profile["window_s"]
        result["breakdown"] = run.profile["breakdown"]
    result["compared"] = compared
    return 0, result


def read_profile(run: Run, inst: Instruments, profile: Profile) -> None:
    from . import xplane

    path = xplane.find_xplane(profile.log_dir)
    if path is None:
        raise BenchmarkError("the profiler wrote no .xplane.pb")
    trace = xplane.load(path)
    log({"phase": "xplane", "bytes": os.path.getsize(path),
         "lines": [ln for ln in trace["lines"] if ln[2]][:40],
         "mark": trace["mark_ns"] is not None})
    spans = inst.tracer.spans(profile.t0 - 1.0, profile.t1 + 1.0)
    run.profile = xplane.reduce(trace, spans, profile.mark_perf,
                                profile.t1)
    run.profile["slice_s"] = profile.t1 - profile.t0
    shutil.rmtree(profile.log_dir, ignore_errors=True)


def print_result(result: dict) -> None:
    """The compared numbers as the last lines of standard error, the
    result as the last line of standard output."""
    sys.stdout.flush()
    for name, v in result["compared"].items():
        print(f"compared {name} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
