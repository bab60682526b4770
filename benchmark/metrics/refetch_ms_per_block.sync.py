"""The part of a reject spent fetching the pair again: from the redo of
the two heights to both blocks back in the pool (the pool's jitter, a
round trip to another peer, the reactor's poll): span blocksync.refetch.
0 where the window saw no reject, None where it saw some and the program
opened no such span (benchmark/reject_metrics.py)."""

from benchmark import reject_metrics


def read(run):
    return reject_metrics.span_ms_per_unit(run, "blocksync.refetch")
