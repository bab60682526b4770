"""Dispatches of the ed25519_* device programs in the window (devprof's
program account) over the blocks stored in it.  A count: it repeats
exactly."""


def read(run):
    n = sum(v for k, v in run.counters.get("dispatches", {}).items()
            if k.startswith("ed25519_"))
    if not n or not run.units:
        return None
    return n / run.units
