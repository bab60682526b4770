"""What is still unnamed inside `apply`: the span blocksync.apply less
the state.* spans opened inside it (cometbft_tpu/libs/trace.py
APPLY_STAGES; the names are spelled out here because the benchmark
reads the program from outside), over the blocks stored in the window,
in milliseconds.  None where the program opens no state.validate span:
`apply` is then one number, and nothing of it is named."""

INSIDE = ("state.validate", "state.abci_finalize", "state.save",
          "state.update", "state.abci_commit", "state.events")


def read(run):
    outer = run.spans.get("blocksync.apply")
    if outer is None or "state.validate" not in run.spans \
            or not run.units:
        return None
    named = sum(run.spans[name]["seconds"] for name in INSIDE
                if name in run.spans)
    return (outer["seconds"] - named) * 1000.0 / run.units
