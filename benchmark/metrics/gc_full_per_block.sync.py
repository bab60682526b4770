"""The garbage collector's full collections in the window, counted: the
spans gc.full (cometbft_tpu/libs/trace.py's collector hook, one a
generation-2 collection) over the blocks (headers) done in it.  Beside
gc_full_ms_per_block.sync it tells rarer collections from cheaper ones.
0 where the window has gc.young spans and no gc.full one; None where the
run has neither span: a program without the hook."""


def read(run):
    if not run.units or ("gc.young" not in run.spans
                         and "gc.full" not in run.spans):
        return None
    full = run.spans.get("gc.full")
    return (full["count"] if full else 0) / run.units
