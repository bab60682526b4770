"""XLA compile-cost hook: count and time every compilation into the
devprof cold-compile ledger (libs/devprof.py).

jax.monitoring fires duration events per compile phase
(``/jax/core/compile/jaxpr_trace_duration``, ``..._to_mlir_module_-
duration``, ``backend_compile_duration``) in the thread that triggered
the compile.  Those events carry no label, so the device-dispatch
wrappers in ops/ (ed25519, secp256k1, sharding) enter a thread-local
``dispatch_scope(kind, shape)`` around their jitted calls; any compile
the call triggers is attributed to that (kind, shape) — the unit the
ledger classifies first-vs-recompile by.  Compiles outside any scope
(merkle hashing, incidental jnp ops) land under kind="other".

jax.monitoring listeners cannot be unregistered individually, so this
module registers exactly ONE process-lifetime listener, lazily on the
first install(); it forwards to whichever ledger is currently
installed and drops events when none is (uninstall() = seam to None).
With no ledger installed dispatch_scope returns a shared null context
— the flightrec near-zero-cost discipline.
"""

from __future__ import annotations

import os
import threading

from ..libs import lockrank

_COMPILE_PREFIX = "/jax/core/compile/"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_mtx = lockrank.RankedLock("compile_hook")
_listener_registered = False
_ledger = None                      # DevprofRecorder | None
_tls = threading.local()


class _NullScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SCOPE = _NullScope()


class _Scope:
    __slots__ = ("_label", "_prev")

    def __init__(self, label):
        self._label = label

    def __enter__(self):
        self._prev = getattr(_tls, "label", None)
        _tls.label = self._label
        return self

    def __exit__(self, *exc):
        _tls.label = self._prev
        return False


def compile_scope(kind: str, shape=None):
    """Label any XLA compile triggered inside the with-block; free (a
    shared null context) when no ledger is installed."""
    if _ledger is None:
        return _NULL_SCOPE
    return _Scope((kind, tuple(shape) if shape is not None else None))


def dispatch_scope(kind: str, shape=None):
    """compile_scope around one DISPATCH of the labelled program, which
    the ledger also counts (DevprofRecorder.program_event)."""
    led = _ledger
    if led is None:
        return _NULL_SCOPE
    label = (kind, tuple(shape) if shape is not None else None)
    led.program_event(*label)
    return _Scope(label)


def _on_event_duration(event: str, duration: float, **kw) -> None:
    led = _ledger
    if led is None or not event.startswith(_COMPILE_PREFIX):
        return
    label = getattr(_tls, "label", None)
    kind, shape = label if label is not None else ("other", None)
    led.compile_event(kind, shape, duration,
                      backend=(event == _BACKEND_EVENT))


def install(ledger) -> None:
    """Point the process-lifetime listener at `ledger` (a
    DevprofRecorder), registering it with jax.monitoring on first use.
    Degrades to a no-op when jax is absent."""
    global _ledger, _listener_registered
    with _mtx:
        _ledger = ledger
        if not _listener_registered:
            try:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(
                    _on_event_duration)
                _listener_registered = True
            except Exception:
                pass


def uninstall() -> None:
    """Detach the ledger; the registered listener stays (it cannot be
    removed) but drops every event until the next install()."""
    global _ledger
    with _mtx:
        _ledger = None


def ledger():
    return _ledger


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  A whole RLC program costs minutes to compile for the
    chip, so every entry point that dispatches (the node CLI,
    chip_smoke.py, the test harness) calls this once before its first
    jit.  JAX_COMPILATION_CACHE_DIR, when set, is read by JAX itself
    and nothing here overrides it; otherwise the cache lives in
    <checkout>/.jax_cache (git-ignored) — a fixed path, because the
    path is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
