"""The garbage collector's full collections in the window (generation 2,
the whole heap): the seconds of the span gc.full (cometbft_tpu/libs/
trace.py's collector hook) over the blocks (headers) done in it, in
milliseconds.  0 where the window has gc.young spans and no gc.full one:
the hook ran and found no full collection.  None where the run has
neither span: a program without the hook."""


def read(run):
    if not run.units or ("gc.young" not in run.spans
                         and "gc.full" not in run.spans):
        return None
    full = run.spans.get("gc.full")
    return (full["seconds"] if full else 0.0) * 1000.0 / run.units
