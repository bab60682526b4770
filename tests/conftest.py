"""Test harness config: run JAX on CPU with 8 virtual devices.

Multi-chip sharding (jax.sharding.Mesh over 8 devices) is exercised on a
virtual CPU mesh, mirroring how the driver's dryrun validates the
multi-chip path without real hardware.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import pytest  # noqa: E402

# -- concurrency sanitizer plane (libs/lockrank.py) --------------------------
# The whole tier-1 suite runs with the lock-rank checker in raise mode
# and the thread/future-leak fixtures armed.  Opt out (bisecting a
# sanitizer report from the code under test) with
# COMETBFT_TPU_LOCKRANK=0 / COMETBFT_TPU_SANITIZERS=0.
os.environ.setdefault("COMETBFT_TPU_LOCKRANK", "1")
os.environ.setdefault("COMETBFT_TPU_SANITIZERS", "1")

from cometbft_tpu.libs import lockrank  # noqa: E402

lockrank.enable_from_env()
_SANITIZERS_ON = os.environ.get("COMETBFT_TPU_SANITIZERS", "0") == "1"
lockrank.set_sanitizer(_SANITIZERS_ON)

from cometbft_tpu.ops import compile_hook  # noqa: E402

compile_hook.ensure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

if _SANITIZERS_ON:
    import sys as _sys

    _prev_unraisable = _sys.unraisablehook

    def _lockrank_unraisable(unraisable, _prev=_prev_unraisable):
        # a TrackedFuture finalizer must never die silently — surface
        # it through the same leak list the fixture checks
        if isinstance(unraisable.object, lockrank.TrackedFuture):
            lockrank._leaked_futures.append(
                f"unraisable in TrackedFuture finalizer: "
                f"{unraisable.exc_value!r}")
        _prev(unraisable)

    _sys.unraisablehook = _lockrank_unraisable


@pytest.fixture(autouse=True)
def _concurrency_sanitizer():
    """Fail the test that leaked a non-daemon thread or dropped a
    failed future (libs/lockrank.py registries).  Also fail on lock-
    rank violations accumulated in warn mode (raise mode surfaces
    them at the acquire site instead)."""
    if not _SANITIZERS_ON:
        yield
        return
    import gc
    import threading

    baseline = set(threading.enumerate())
    lockrank.clear_leaked_futures()
    yield
    gc.collect()
    leaked_futs = lockrank.leaked_futures()
    lockrank.clear_leaked_futures()
    leaked = lockrank.leaked_threads(baseline, grace_s=1.0)
    c = lockrank.checker()
    viols = list(c.violations) if c is not None and c.mode == "warn" \
        else []
    if c is not None and c.mode == "warn":
        c.violations.clear()
        c._seen.clear()
    msgs = []
    if leaked:
        msgs.append("leaked non-daemon threads: "
                    + ", ".join(t.name for t in leaked))
    if leaked_futs:
        msgs.append("futures dropped with unretrieved exceptions:\n"
                    + "\n".join(leaked_futs))
    if viols:
        msgs.append("lock-rank violations (warn mode):\n"
                    + "\n".join(viols))
    if msgs:
        pytest.fail("concurrency sanitizer: " + "\n".join(msgs))


def _rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1])
    return 0


@pytest.fixture(autouse=True)
def _sigcache_isolation():
    """The signature-verdict cache (crypto/sigcache) is process-wide
    by design — which in a test process means verdicts leak across
    tests: a triple verified in one test resolves as a cache hit in
    the next, masking the code path the later test means to exercise.
    Start every test with an empty cache and the default (env-driven)
    enable state."""
    from cometbft_tpu.crypto import sigcache

    sigcache.reset()
    sigcache.set_enabled(None)
    yield
    sigcache.reset()
    sigcache.set_enabled(None)


@pytest.fixture(autouse=True, scope="module")
def _module_memory_hygiene(request):
    """Drop live jit executables between modules: a full-suite run
    accumulates every compiled kernel otherwise (15+ GB by the tail of
    the suite, enough to destabilize late compiles), and the
    persistent compile cache makes re-tracing cheap.  Set
    COMETBFT_TPU_RSS_LOG=<path> to record per-module peak RSS.

    Measured footprint (r4): steady-state ~0.6 GB between modules; the
    peak is transient XLA-CPU *compile* memory — each RLC-kernel
    compile allocates 2-5 GB regardless of lane width (78-window scan
    graph), so test_ed25519 peaks ~8 GB and test_pallas_msm ~9.6 GB
    when several shapes compile in one file.  Per-TEST clearing would
    cap this but forces minutes of recompiles per file; the full-suite
    peak is bounded by the heaviest single file, not suite length."""
    yield
    jax.clear_caches()
    try:
        # glibc holds freed compile arenas forever otherwise; RSS
        # observed 15+ GB without this pair, ~8 GB with clear_caches
        # alone
        import ctypes
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:
        pass
    log = os.environ.get("COMETBFT_TPU_RSS_LOG")
    if log:
        with open(log, "a") as f:
            f.write(f"{_rss_kb()}\t{request.module.__name__}\n")
