"""Which device programs a catch-up from forging peers dispatches: catch-up's
own (programs.expected_programs) and, at every reject, the per-signature
program at the bucket of one verify window.

programs.py's store serves the three kinds its JITTED map names, and
that map is not this file's to edit.  So the per-signature program
(ops/ed25519._jitted, kind `ed25519_persig`) gets a dispatcher of
programs.py's own class from here: loaded from, or built ahead and kept
in, a directory of its own beside the cell's store (programs.ensure
would unpickle an entry of a kind it does not serve only to skip it).
Built in set-up, the program's first dispatch inside a pass compiles
nothing, and the dispatch watchdog has nothing to wait out.  On the v5e
it lowers in about 20 s and compiles in about 40 (PERF.md section 6).
"""

from __future__ import annotations

import glob
import os
import pickle
import time
import zlib

from benchmark import programs

KIND = "ed25519_persig"
ATTR = "_jitted"


def buckets(n_vals: int, window_blocks: int) -> list[int]:
    """The per-signature program's batch bucket for one verify window's
    signatures: what crypto/batch._device_verify packs a rejected window
    to (ops/sharding.auto_bucket on one device)."""
    from cometbft_tpu.ops import ed25519 as dev

    return [dev.bucket_size((n_vals * 2 // 3 + 1) * window_blocks)]


def _arg_shapes(bucket: int) -> tuple:
    """As crypto/ed25519.pack_batch hands them over: A and R words, s
    and h limbs."""
    return (((8, bucket), "uint32"), ((8, bucket), "uint32"),
            ((16, bucket), "uint32"), ((16, bucket), "uint32"))


def ensure(bucket_list: list[int], directory: str,
           log=lambda rec: None) -> dict:
    """Load what `directory` keeps, build ahead what it lacks, install
    the dispatcher.  Returns the seconds of each part and the
    dispatcher; where the program has no such function the dispatcher
    is None and the per-signature program traces where it is first
    called."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable as se

    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    rec = {"phase": "programs", "kind": KIND, "dir": directory,
           "loaded": 0, "built": [], "load_s": 0.0, "trace_lower_s": 0.0,
           "backend_compile_s": 0.0, "dispatcher": None}
    if not hasattr(dev, ATTR):
        log({k: v for k, v in rec.items() if k != "dispatcher"})
        return rec
    aside = bool(getattr(compile_hook, "KEEPS_EXECUTABLES", False))
    d = programs._Dispatcher(KIND, programs._plain(getattr(dev, ATTR)),
                             None if aside else directory)
    if not aside:
        os.makedirs(directory, exist_ok=True)
        t0 = time.perf_counter()
        for path in sorted(glob.glob(os.path.join(directory, "*.execz"))):
            try:
                with open(path, "rb") as f:
                    kind, sig, (payload, in_tree, out_tree) = pickle.loads(
                        zlib.decompress(f.read()))
                if kind == KIND:
                    d.table[sig] = se.deserialize_and_load(
                        payload, in_tree, out_tree,
                        execution_devices=jax.devices()[:1])
                    rec["loaded"] += 1
            except Exception as e:          # noqa: BLE001 - a bad entry
                log({"phase": "programs", "dropped": os.path.basename(path),
                     "error": f"{type(e).__name__}: {e}"[:200]})
                os.remove(path)
        rec["load_s"] = time.perf_counter() - t0
        for bucket in bucket_list:
            sig = tuple((tuple(s), t) for s, t in _arg_shapes(bucket))
            if sig in d.table:
                continue
            t1 = time.perf_counter()
            low = d.original.lower(*(jax.ShapeDtypeStruct(s, jnp.dtype(t))
                                     for s, t in sig))
            t2 = time.perf_counter()
            with compile_hook.compile_scope(KIND, (bucket,)):
                exe = low.compile()
            t3 = time.perf_counter()
            programs._keep(directory, KIND, sig, exe)
            d.table[sig] = exe
            rec["trace_lower_s"] += t2 - t1
            rec["backend_compile_s"] += t3 - t2
            rec["built"].append({"program": [KIND, bucket],
                                 "trace_lower_s": round(t2 - t1, 2),
                                 "compile_s": round(t3 - t2, 2)})
    setattr(dev, ATTR, d)
    log({k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in rec.items() if k != "dispatcher"})
    rec["dispatcher"] = d
    return rec


def uninstall() -> None:
    from cometbft_tpu.ops import ed25519 as dev

    if hasattr(dev, ATTR):
        setattr(dev, ATTR, programs._plain(getattr(dev, ATTR)))
