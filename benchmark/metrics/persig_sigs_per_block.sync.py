"""Signatures the per-signature program judged in the window (the
program's own counter,
cometbft_device_signatures_verified_total{program="persig"}) over the
blocks (headers) done in it.  A count: it repeats exactly.  Where the
window saw no reject (rlc_fallbacks 0) the program did not run and the
count IS 0; where it saw one and the mode kept no such count, None."""


def read(run):
    rejects = run.counters.get("rlc_fallbacks")
    if rejects is None or not run.units:
        return None
    if not rejects:
        return 0.0
    n = run.counters.get("signatures_verified_persig")
    return None if n is None else n / run.units
