"""The device programs of a cell, kept as executables.  A STOP-GAP: the
first PR that gives the program an executable or export cache of its own
deletes this file's store (PERF.md sections 2 and 7).

Every run of a cell is a new process, and in a new process the six RLC
programs of a cell take eight minutes to TRACE AND LOWER before JAX's
persistent cache is even asked (PERF.md section 6) - more than the 360 s
a run may last.  So the first run of a cell in a checkout builds the
programs through the program's own jitted functions, keeps the compiled
executables (jax.experimental.serialize_executable, what the persistent
cache itself stores) beside the compile cache, and every later run loads
them and puts a dispatcher where ops/ed25519's module-level jitted
functions stood.

What a dispatcher serves is what the PROGRAM dispatches: it keys each
executable by the shapes and types of the arguments the program handed
it.  During set-up (the warm-up pass) a call it has no executable for is
lowered from those very arguments, compiled, kept and served; in the
measured window such a call goes to the program's own function, where it
traces and compiles as it always did - and shows in compiles_in_window.
expected_programs / _arg_shapes below are only a HINT for building ahead
(trace one at a time, compile in threads, a copy of chip_smoke.py's,
PR 22): a hint that has gone stale builds an executable nobody calls and
the warm-up pass learns the real one.

The store stands aside where the program says it keeps its executables
itself (ops/compile_hook.KEEPS_EXECUTABLES true) and for any jitted
function ops/ed25519 no longer has: the dispatcher then only counts
calls.  A program that first compiles inside a dispatch of the warm-up
pass may trip the program's own dispatch watchdog; the run then reads
`correct` false and the next run, its store full, is sound: learning is
a fallback that is loud, not a path to rely on.

The store's key covers everything a lowered program can depend on:
every source file of the package (ops/ imports crypto/, and what is
traced in is not knowable without tracing), the versions of JAX, jaxlib
and the device's runtime (libtpu), the device kind, and the environment
switches that choose kernels or flags.  A checkout that differs in any
of these builds its own.  Entries are pickles, as JAX's own serialised
executables are: the directory is the compile cache's, inside the
checkout or where JAX_COMPILATION_CACHE_DIR points, and nowhere else.
"""

from __future__ import annotations

import glob
import hashlib
import os
import pickle
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# attribute of ops/ed25519 that holds each kind's jitted function
JITTED = {
    "ed25519_rlc": "_rlc_jitted",
    "ed25519_a_tables": "_a_tables_jitted",
    "ed25519_rlc_cached": "_rlc_cached_jitted",
}

# environment that changes what is lowered or compiled: the program's
# own switches and the compiler's flags (not TPU_*: the runtime's names
# for a machine's topology and addresses differ from machine to machine)
ENV_PREFIXES = ("COMETBFT_TPU_", "XLA_FLAGS", "LIBTPU_INIT_ARGS")
ENV_JAX_SKIP = ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")


def expected_programs(n_vals: int, window_blocks: int) -> list[tuple]:
    """The device programs a catch-up of full windows triggers at this
    validator count, as (kind, K, N): widths from ops/ed25519.pad_width,
    never from a guess.  A hint for building ahead (see above).

    Windows hold verify_commit_light's signatures (it stops past 2/3 of
    the power); apply-time validate_block checks the FULL LastCommit,
    whose first 2/3 the window already put in the verdict cache, so the
    remainder goes through the batch seam as a batch of its own.  The
    first sighting of a validator set takes the fused kernel, later ones
    build the A tables once and take the cached-A kernel."""
    from cometbft_tpu.crypto.ed25519 import ATableCache
    from cometbft_tpu.ops import ed25519 as dev

    signers = n_vals * 2 // 3 + 1        # equal powers: first past 2/3
    rest = n_vals - signers
    out = []

    def batch(n_keys, n_sigs):
        k, n = dev.pad_width(n_keys + 1), dev.pad_width(n_sigs)
        out.append(("ed25519_rlc", k, n))
        if k >= ATableCache.MIN_K:
            out.append(("ed25519_a_tables", k))
            out.append(("ed25519_rlc_cached", k, n))

    batch(signers, signers * window_blocks)
    if rest:
        batch(rest, rest)
    return list(dict.fromkeys(out))


def _arg_shapes(prog: tuple) -> tuple:
    """(shape, dtype name) of each argument of a program, as ops/ed25519
    packs them today: the hint's half of a signature."""
    kind, *dims = prog
    if kind == "ed25519_rlc":
        k, n = dims
        return (((8, k), "uint32"), ((8, n), "uint32"),
                ((52, k), "int32"), ((52, k), "bool"),
                ((26, n), "int32"), ((26, n), "bool"))
    if kind == "ed25519_a_tables":
        return (((8, dims[0]), "uint32"),)
    if kind == "ed25519_rlc_cached":
        k, n = dims
        return (((17, 4, 20, k), "int32"), ((), "bool"),
                ((8, n), "uint32"), ((52, k), "int32"),
                ((52, k), "bool"), ((26, n), "int32"), ((26, n), "bool"))
    raise ValueError(f"no argument shapes known for {prog}")


def _plain(fn):
    """The program's own function under a dispatcher, or fn itself."""
    return getattr(fn, "original", fn)


def signature(args) -> tuple:
    """What an executable is keyed by: (shape, dtype name) of each
    argument as the program hands it over."""
    import numpy as np

    return tuple((tuple(getattr(a, "shape", ())),
                  np.dtype(getattr(a, "dtype", type(a))).name)
                 for a in args)


def store_key(device_kind: str) -> str:
    import jax
    import jaxlib

    h = hashlib.sha256()
    pkg = os.path.join(REPO, "cometbft_tpu")
    for root, dirs, files in os.walk(pkg):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, "rb") as f:
                    h.update(os.path.relpath(path, pkg).encode() + b"\0"
                             + f.read() + b"\0")
    runtime = getattr(jax.devices()[0].client, "platform_version", "")
    h.update(f"{jax.__version__}/{jaxlib.__version__}/{runtime}/"
             f"{device_kind}".encode())
    for k in sorted(os.environ):
        if k.startswith(ENV_PREFIXES) or (
                k.startswith("JAX_") and k not in ENV_JAX_SKIP):
            h.update(f"\0{k}={os.environ[k]}".encode())
    return h.hexdigest()[:20]


def store_dir(compile_cache_dir: str, device_kind: str,
              workload: str) -> str:
    """<compile cache>/benchmark_exec/<key>/<cell>: a cell loads its own
    programs and nobody else's (a loaded executable takes device memory
    and seconds of set-up)."""
    return os.path.join(compile_cache_dir, "benchmark_exec",
                        store_key(device_kind), workload)


def _path(directory: str, kind: str, sig: tuple) -> str:
    return os.path.join(directory, kind + "-" + hashlib.sha256(
        repr(sig).encode()).hexdigest()[:16] + ".execz")


def _keep(directory: str, kind: str, sig: tuple, exe) -> None:
    from jax.experimental import serialize_executable as se

    path = _path(directory, kind, sig)
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        # XLA-path programs serialise to 160-220 MB, a tenth of it
        # compressed
        f.write(zlib.compress(pickle.dumps((kind, sig, se.serialize(exe))),
                              1))
    os.replace(tmp, path)


class _Dispatcher:
    """Stands where a module-level jitted function stood."""

    def __init__(self, kind: str, original, directory: str | None):
        self.kind = kind
        self.original = original
        self.directory = directory      # None: the store stands aside
        self.table: dict = {}
        self.learning = directory is not None
        self.misses = 0
        self.learned: list = []         # (signature, lower s, compile s)
        self.calls: list = []           # (perf_counter, signature)

    def __call__(self, *args):
        sig = signature(args)
        self.calls.append((time.perf_counter(), sig))
        fn = self.table.get(sig)
        if fn is None and self.learning:
            fn = self._learn(sig, args)
        if fn is None:
            self.misses += 1
            return self.original(*args)
        return fn(*args)

    def _learn(self, sig, args):
        from cometbft_tpu.ops import compile_hook

        t0 = time.perf_counter()
        low = self.original.lower(*args)
        t1 = time.perf_counter()
        # the ledger's label is (K, N): the last dimension of the first
        # two arguments that have one
        dims = tuple(shape[-1] for shape, _ in sig if shape)[:2]
        with compile_hook.compile_scope(self.kind, dims):
            exe = low.compile()
        self.learned.append((sig, t1 - t0, time.perf_counter() - t1))
        _keep(self.directory, self.kind, sig, exe)
        self.table[sig] = exe
        return exe

    def __getattr__(self, name):          # .lower and the like
        return getattr(self.original, name)


def ensure(programs: list[tuple], directory: str, workers: int,
           log=lambda rec: None) -> dict:
    """Load every executable kept in `directory`, build ahead what the
    hint names and the store lacks (trace serial, compile in threads),
    then install the dispatchers.  Returns the seconds of each part."""
    import jax
    from jax.experimental import serialize_executable as se

    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    aside = bool(getattr(compile_hook, "KEEPS_EXECUTABLES", False))
    kinds = {k: a for k, a in JITTED.items() if hasattr(dev, a)}
    dispatchers = {k: _Dispatcher(k, _plain(getattr(dev, a)),
                                  None if aside else directory)
                   for k, a in kinds.items()}
    rec = {"phase": "programs", "dir": directory, "aside": aside,
           "absent": sorted(set(JITTED) - set(kinds)), "loaded": 0,
           "built": [], "load_s": 0.0, "trace_lower_s": 0.0,
           "backend_compile_s": 0.0, "build_wall_s": 0.0}
    if not aside:
        os.makedirs(directory, exist_ok=True)
        t0 = time.perf_counter()
        for path in sorted(glob.glob(os.path.join(directory, "*.execz"))):
            try:
                with open(path, "rb") as f:
                    kind, sig, (payload, in_tree, out_tree) = pickle.loads(
                        zlib.decompress(f.read()))
                if kind in dispatchers:
                    # one-device programs, as the program dispatches them
                    dispatchers[kind].table[sig] = se.deserialize_and_load(
                        payload, in_tree, out_tree,
                        execution_devices=jax.devices()[:1])
                    rec["loaded"] += 1
            except Exception as e:          # noqa: BLE001 - a bad entry
                log({"phase": "programs", "dropped": os.path.basename(path),
                     "error": f"{type(e).__name__}: {e}"[:200]})
                os.remove(path)
        rec["load_s"] = time.perf_counter() - t0
        _build_ahead(programs, dispatchers, directory, workers, rec)
    for kind, d in dispatchers.items():
        setattr(dev, kinds[kind], d)
    log({k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in rec.items()})
    rec["dispatchers"] = dispatchers
    return rec


def _build_ahead(programs, dispatchers, directory, workers, rec) -> None:
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    todo = []
    for prog in programs:
        d = dispatchers.get(prog[0])
        if d is None:
            continue
        sig = tuple((tuple(s), t) for s, t in _arg_shapes(prog))
        if sig not in d.table:
            todo.append((prog, d, sig))
    if not todo:
        return

    def compile_(prog, d, sig, low):
        t1 = time.perf_counter()
        with compile_hook.compile_scope(prog[0], prog[1:]):
            exe = low.compile()
        secs = time.perf_counter() - t1
        _keep(directory, prog[0], sig, exe)
        d.table[sig] = exe
        return secs

    # the XLA-path programs (a width no Pallas block divides) compile
    # longest: lower them first so that overlaps the most tracing
    todo.sort(key=lambda t: dev._pallas_blk(t[0][1]) is not None)
    t0 = time.perf_counter()
    # each compile holds gigabytes of host memory while it runs
    with ThreadPoolExecutor(max(1, min(workers, 6)),
                            thread_name_prefix="compile") as ex:
        futs = []
        for prog, d, sig in todo:
            # tracing and lowering hold the GIL: one at a time.  Six
            # threads tracing at once took 20 times as long.
            t1 = time.perf_counter()
            low = d.original.lower(*(jax.ShapeDtypeStruct(s, jnp.dtype(t))
                                     for s, t in sig))
            dt = time.perf_counter() - t1
            rec["trace_lower_s"] += dt
            futs.append((prog, dt, ex.submit(compile_, prog, d, sig, low)))
        for prog, dt, fut in futs:
            secs = fut.result()
            rec["backend_compile_s"] += secs
            rec["built"].append({"program": list(prog),
                                 "trace_lower_s": round(dt, 2),
                                 "compile_s": round(secs, 2)})
    rec["build_wall_s"] = time.perf_counter() - t0


def stop_learning(dispatchers: dict) -> dict:
    """Set-up is over: from here on a call with no executable is the
    program's own business.  Returns what the warm-up pass had to learn
    (seconds of lowering and of compiling, and the signatures)."""
    out = {"trace_lower_s": 0.0, "backend_compile_s": 0.0, "learned": []}
    for kind, d in dispatchers.items():
        d.learning = False
        for sig, low_s, comp_s in d.learned:
            out["trace_lower_s"] += low_s
            out["backend_compile_s"] += comp_s
            out["learned"].append([kind, [list(s[0]) for s in sig]])
    return out


def uninstall() -> None:
    from cometbft_tpu.ops import ed25519 as dev

    for attr in JITTED.values():
        if hasattr(dev, attr):
            setattr(dev, attr, _plain(getattr(dev, attr)))
