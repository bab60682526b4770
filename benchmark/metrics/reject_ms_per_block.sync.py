"""A reject as the reactor lives it: from a window's false verdict to the
heights it named verified true (both suppliers dropped, the pair fetched
again, the window verified a second time): span blocksync.reject.
0 where the window saw no reject, None where it saw some and the program
opened no such span (benchmark/reject_metrics.py)."""

from benchmark import reject_metrics


def read(run):
    return reject_metrics.span_ms_per_unit(run, "blocksync.reject")
