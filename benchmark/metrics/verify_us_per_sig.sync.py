"""Device time of the ed25519_rlc* programs over the signatures they
carried, in microseconds, for the measured window's mix of dispatches:
each class of dispatch at the mean device time the traced slice read
for it (work.kernel_mix says why not the slice's own ratio).  Where the
trace's runs could not be set beside the host's dispatches, the slice's
own seconds over its own signatures."""

from benchmark import work


def read(run):
    p = run.profile
    if not p or not p.get("kinds"):
        return None
    mix = work.kernel_mix(p, run.counters.get("rlc_dispatches_by_width",
                                              {}))
    if mix:
        return mix[0] * 1e6 / mix[1]
    if not p.get("signatures"):
        return None
    secs = sum(k["seconds"] for name, k in p["kinds"].items()
               if name.startswith("ed25519_rlc"))
    return secs * 1e6 / p["signatures"] if secs > 0 else None
