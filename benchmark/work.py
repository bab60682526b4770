"""The work of a kernel, counted from what the protocol hands it."""

SIG_BYTES = 96          # public key 32, R 32, S 32


def verify_bytes(n_sigs: int, sign_bytes_len: int) -> int:
    """Bytes an Ed25519 verifier must read for n_sigs signatures: key, R
    and S of each, and the sign-bytes that are hashed with them."""
    return n_sigs * (SIG_BYTES + sign_bytes_len)


def match_dispatches(programs: list, calls: list) -> dict | None:
    """Which dispatch each program run of a traced slice was.

    programs: [start, kind, seconds, whole] of the trace, in the order
    the device ran them; calls: (time, kind, width) of every dispatch the
    host made, all on one clock.  The device runs programs in the order
    they were dispatched, so once the slice's first run is set beside
    the last dispatch of its kind made before it started, the runs that
    follow are the dispatches that follow.  Returns {width: {"seconds",
    "count"}} over the runs no edge of the slice cut, or None where the
    kinds do not line up (a dispatch the host did not see)."""
    calls = sorted(calls)
    if not programs or not calls:
        return None
    start, kind = programs[0][0], programs[0][1]
    first = [i for i, (t, k, _) in enumerate(calls)
             if k == kind and t <= start + 1e-3]
    if not first:
        return None
    out: dict = {}
    for (_, kind, secs, whole), call in zip(programs, calls[first[-1]:]):
        if call[1] != kind:
            return None
        if whole:
            rec = out.setdefault(call[2], {"seconds": 0.0, "count": 0})
            rec["seconds"] += secs
            rec["count"] += 1
    return out


def kernel_mix(profile: dict, window_dispatches: dict) -> tuple | None:
    """(device seconds, signatures) of the measured window's dispatches,
    each class of dispatch (a padded width) at the mean device time the
    traced slice read for it.  A slice is short and catches the classes
    in other shares than a pass makes them (one verify window to eight
    remainders, where a pass makes one to thirty-two), so the slice's
    own seconds over its own signatures would weigh the classes wrongly.
    A class the slice did not catch adds its signatures and no time; the
    caller is told which were caught.  None where nothing was."""
    classes = profile.get("classes") or {}
    seconds = sigs = 0.0
    for width, n in window_dispatches.items():
        c = classes.get(width)
        if c is None:
            continue
        sigs += n * c["sigs"]
        if c["count"]:
            seconds += n * c["seconds"] / c["count"]
    if seconds <= 0 or not sigs:
        return None
    return seconds, sigs
