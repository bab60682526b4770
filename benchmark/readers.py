"""Per-layer metrics, each from a file of its own: metrics/<name>.json
names one of the general readers below with its parameters, and
metrics/<name>.py brings a reader function `read(run)`.  A reader that
finds nothing to read returns None and the metric is left out of the
line; no reader turns "nothing" into 0.
"""

from __future__ import annotations

import os

from . import harness


def span_ms_per_unit(run, spec: dict):
    """A stage's span seconds in the window over the blocks (headers)
    done in it, in milliseconds."""
    rec = run.spans.get(spec["span"])
    if rec is None or not run.units:
        return None
    return rec["seconds"] * 1000.0 / run.units


def setup_seconds(run, spec: dict):
    return run.setup.get(spec["key"])


def counter(run, spec: dict):
    v = run.counters
    for key in spec["path"]:
        if not isinstance(v, dict) or key not in v:
            return None
        v = v[key]
    return v


READERS = {"span_ms_per_unit": span_ms_per_unit,
           "setup_seconds": setup_seconds, "counter": counter}


def read_metric(name: str, run):
    base = os.path.join(harness.HERE, "metrics", name)
    if os.path.exists(base + ".json"):
        spec = harness.load_json(base + ".json")
        v = READERS[spec["reader"]](run, spec)
    elif os.path.exists(base + ".py"):
        v = harness.load_module("metrics", name).read(run)
    else:
        raise harness.BenchmarkError(f"no metrics/{name}.json or .py")
    return None if v is None else float(v)
