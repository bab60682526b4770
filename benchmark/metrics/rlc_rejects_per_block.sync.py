"""RLC batches the device rejected in the window (the program's own
counter, cometbft_device_rlc_fallbacks) over the blocks (headers) done
in it.  A count: it repeats exactly.  0 in a cell whose peers are
honest - that zero is the guard: an honest window that rejects has a
verifier that is wrong - and one a verify window where a peer forges.
None where the mode kept no such count."""


def read(run):
    n = run.counters.get("rlc_fallbacks")
    if n is None or not run.units:
        return None
    return n / run.units
