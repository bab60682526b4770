"""The reader the reject path's span metrics share (metrics/localize_,
reject_ and refetch_ms_per_block.sync.py): a span's seconds in the
window over the blocks (headers) done in it, in milliseconds, for a span
the program opens only where a window rejects.  Where the window saw no
reject (rlc_fallbacks 0) and no such span, the time IS 0 - and that zero
guards the honest cells; where it saw rejects and the program opened no
such span (a tree before PR 31), None; None too where the mode kept no
count of rejects."""


def span_ms_per_unit(run, span: str):
    rejects = run.counters.get("rlc_fallbacks")
    if rejects is None or not run.units:
        return None
    rec = run.spans.get(span)
    if rec is None:
        return None if rejects else 0.0
    return rec["seconds"] * 1000.0 / run.units
