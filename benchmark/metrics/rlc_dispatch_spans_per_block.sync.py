"""The COUNT of verify.dispatch spans in the window over the blocks
stored in it.  The span sits in the one funnel every RLC dispatch
passes (crypto/ed25519.rlc_verify_async), so this must equal
dispatches_per_block.sync less the ed25519_a_tables dispatches: the
proof that the span sees every dispatch the program account counts.
None where the program opens no such span."""


def read(run):
    rec = run.spans.get("verify.dispatch")
    if rec is None or not run.units:
        return None
    return rec["count"] / run.units
