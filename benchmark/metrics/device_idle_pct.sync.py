"""1 - (union of the device operations' intervals) / (the traced slice),
from the profiler trace, in percent."""


def read(run):
    p = run.profile
    if not p or not p.get("window_s"):
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
