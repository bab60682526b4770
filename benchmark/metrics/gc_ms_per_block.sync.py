"""The garbage collector's pauses in the window: the seconds of the spans
gc.young and gc.full (cometbft_tpu/libs/trace.py's collector hook, one a
collection, on the tracer's clock) over the blocks (headers) done in it,
in milliseconds.  A collection stops every thread, and the span open
when it ran holds it too.  None where the run has neither span: a
program without the hook."""


def read(run):
    young, full = run.spans.get("gc.young"), run.spans.get("gc.full")
    if (young is None and full is None) or not run.units:
        return None
    seconds = sum(rec["seconds"] for rec in (young, full) if rec)
    return seconds * 1000.0 / run.units
