"""Signatures the RLC programs verified in the window (the program's own
counter, cometbft_device_signatures_verified_total{program="rlc"}) over
the window's ed25519_rlc* dispatches (devprof's program account).  A
count: it repeats exactly.  None where the program has no such counter
or dispatched nothing."""


def read(run):
    sigs = run.counters.get("signatures_verified_rlc")
    n = sum(run.counters.get("rlc_dispatches_by_width", {}).values())
    if not sigs or not n:
        return None
    return sigs / n
