"""A-table cache hits in the window (the program's own counter,
cometbft_device_a_table_cache_hits) over the window's ed25519_rlc*
dispatches: the share of RLC dispatches that found their A side's tables
on the device.  0 where every A side was new; None where the mode kept
no such count or nothing was dispatched."""


def read(run):
    hits = run.counters.get("a_table", {}).get("hits")
    n = sum(run.counters.get("rlc_dispatches_by_width", {}).values())
    if hits is None or not n:
        return None
    return hits / n
