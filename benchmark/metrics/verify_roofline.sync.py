"""What the protocol hands the verifier for the window's signatures (96
bytes a signature - key, R, S - plus the sign-bytes hashed), over the
HBM peak of the device, over the kernels' device time for the window's
mix of dispatches (work.kernel_mix; the slice's own seconds and
signatures where the trace could not be set beside the dispatches).  HBM
is the only published peak that bounds this work (Google publishes no
int32 vector peak for the v5e), so this share is tiny and says so: the
kernels are compute-bound."""

from benchmark import work


def read(run):
    p = run.profile
    peak = run.peaks.get("hbm_bytes_per_s")
    if not p or not p.get("kinds") or not peak:
        return None
    mix = work.kernel_mix(p, run.counters.get("rlc_dispatches_by_width",
                                              {}))
    if mix:
        secs, sigs = mix
    else:
        sigs = p.get("signatures")
        secs = sum(k["seconds"] for name, k in p["kinds"].items()
                   if name.startswith("ed25519_rlc"))
    if not sigs or secs <= 0:
        return None
    least = work.verify_bytes(sigs, p["sign_bytes_len"]) / peak
    return 100.0 * least / secs
