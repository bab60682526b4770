"""BatchVerifier: the accelerator seam.

The reference dispatches batch verification by key type and falls back to
per-signature CPU verify below a threshold
(/root/reference/crypto/batch/batch.go:12-35, types/validation.go:13).
Here the same seam routes to either:

- TpuBatchVerifier: one jitted JAX program verifying the whole batch on
  the accelerator (per-signature verdicts come out as a bitmap), or
- CpuBatchVerifier: host loop, used below the device threshold and as the
  parity oracle in tests.

Unlike the reference (which refuses mixed-keytype batches,
types/validation.go:18 AllKeysHaveSameType), mixed batches are split by
key type and each sub-batch is dispatched to its own verifier.
"""

from __future__ import annotations

import os
from typing import Protocol

from ..libs import metrics as libmetrics
from ..libs.trace import span as trace_span
from . import ed25519 as ed
from . import sigcache


class BatchVerifier(Protocol):
    def add(self, pubkey, msg: bytes, sig: bytes) -> None: ...
    def verify(self) -> tuple[bool, list[bool]]: ...
    def count(self) -> int: ...


class _SigCollector:
    """Shared add/count scaffolding: items are (pubkey_bytes, msg, sig).

    verify() wraps the subclass _verify_items() and POPULATES the
    signature-verdict cache with every computed verdict — batch
    verifiers are a resolution seam (crypto/sigcache.py); consulting
    is the callers' job (types/validation partitions before building
    the verifier), so a miss is never double-counted here."""

    KEY_TYPE = "ed25519"

    def __init__(self):
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def add(self, pubkey, msg: bytes, sig: bytes) -> None:
        pk = pubkey.bytes() if hasattr(pubkey, "bytes") else bytes(pubkey)
        self._items.append((pk, msg, sig))

    def count(self) -> int:
        return len(self._items)

    def verify(self) -> tuple[bool, list[bool]]:
        ok, verdicts = self._verify_items()
        if self._items:
            sigcache.insert_many(self._items, verdicts,
                                 key_type=self.KEY_TYPE)
        return ok, verdicts


class _CpuLoopVerifier(_SigCollector):
    """Host-side per-signature loop (parity oracle for a device path);
    subclasses provide _check(pk, msg, sig) -> bool."""

    def _verify_items(self) -> tuple[bool, list[bool]]:
        verdicts = []
        for pk, m, s in self._items:
            try:
                verdicts.append(bool(self._check(pk, m, s)))
            except ValueError:
                verdicts.append(False)
        return all(verdicts) and bool(verdicts), verdicts


class CpuEd25519BatchVerifier(_CpuLoopVerifier):
    """ZIP-215 host loop (crypto/ed25519_ref)."""

    def _check(self, pk, m, s):
        from . import ed25519_ref as ref
        return ref.verify(pk, m, s)


class TpuEd25519BatchVerifier(_SigCollector):
    """Packs the batch into uint32 arrays and runs the device kernel.

    Batch sizes are bucketed (ops/ed25519.BATCH_BUCKETS) so the jitted
    kernel compiles once per bucket; slots past the real batch are masked.
    """

    def _verify_items(self) -> tuple[bool, list[bool]]:
        if not self._items:
            return False, []
        return _device_verify([i[0] for i in self._items],
                              msgs=[i[1] for i in self._items],
                              sigs=[i[2] for i in self._items])


# sentinel: "no precomputed RLC packing" (None is a real pack_rlc
# result meaning structural reject, so it cannot double as the default)
_NO_PACK = object()


def _count_verified(program: str, n: int) -> None:
    """n signatures got their verdict from a device program: "rlc"
    (the whole batch accepted in one equation) or "persig" (the
    per-signature kernel, after a reject or for a batch of one).
    Counted here, where windows and seam batches both pass, so the
    count holds whatever plan made the dispatches."""
    dm = libmetrics.device_metrics()
    if dm is not None:
        dm.signatures_verified.labels(program).add(n)


def _device_verify(pubkeys: list[bytes], parsed=None, packed=_NO_PACK,
                   device=None, *, msgs=None,
                   sigs=None) -> tuple[bool, list[bool]]:
    """Shared device dispatch for any Edwards-domain batch: RLC fast
    path first, per-signature kernel for verdict localization on
    failure — the reference's verifyCommitBatch -> verifyCommitSingle
    pattern (/root/reference/types/validation.go:115).  `packed`
    accepts a pack_rlc result computed ahead of time (the overlapped
    pipeline packs window N+1 while window N is on device).

    An ed25519 batch comes as `msgs` and `sigs`: pack_rlc hashes for
    itself, and parse_and_hash's rows, which only the per-signature
    kernel needs, are computed at the reject - as rare as a forged
    signature.  A caller whose h is no SHA-512 (the sr25519 bridge)
    brings `parsed` instead.

    `device` commits the dispatch to one specific mesh device (the
    pipeline's round-robin placement, crypto/dispatch.py); with
    device=None and a configured mesh, a large window instead SPLITS
    across every device — one RLC program per chip
    (crypto/mesh.maybe_split_verify), falling back to the
    batch-axis-sharded per-signature kernel for localization."""
    import numpy as np

    from ..ops import ed25519 as dev
    from ..ops import sharding

    n = len(pubkeys)
    if n >= 2:
        rlc_ok = None
        if packed is _NO_PACK and device is None:
            from . import mesh

            rlc_ok = mesh.maybe_split_verify(pubkeys, parsed,
                                             msgs=msgs, sigs=sigs)
        if rlc_ok is None:
            if packed is _NO_PACK:
                with trace_span("verify", "host_pack", batch=n) as sp:
                    packed, packer = ed.pack_rlc_named(
                        pubkeys, msgs, sigs, parsed=parsed)
                    sp.note(packer=packer)
            rlc_ok = packed is not None and \
                ed.rlc_verify(packed, device=device)
        if rlc_ok:
            _count_verified("rlc", n)
            return True, [True] * n
        from ..libs import flightrec

        dm = libmetrics.device_metrics()
        if dm is not None:
            dm.rlc_fallbacks.inc()
        flightrec.record(flightrec.EV_RLC_FALLBACK, batch=n)
    # localisation: an RLC reject (or a batch of one) is judged signature
    # by signature.  The span covers the whole arm; inside it the
    # Python hash, the pack, the per-signature program's enqueue and the
    # wait for its verdicts each have their own (trace.LOCALIZE_STAGES)
    bucket = dev.bucket_size(n) if device is not None \
        else sharding.auto_bucket(n)
    with trace_span("verify", "localize", batch=n, bucket=bucket) as loc:
        if parsed is None:
            # the per-signature kernel wants every h, hashed in Python
            # under the packing span's name, so the packing metrics see
            # a seam made slow by a forged signature
            with trace_span("verify", "host_pack", batch=n,
                            packer="python"):
                parsed = ed.parse_and_hash(pubkeys, msgs, sigs)
        with trace_span("verify", "persig_pack", bucket=bucket):
            a, r, s, h, valid = ed.pack_batch(
                pubkeys, [b""] * n, [b""] * n, bucket, parsed=parsed)
        with trace_span("verify", "persig_dispatch", bucket=bucket):
            if device is not None:
                import jax

                a, r, s, h = (jax.device_put(x, device)
                              for x in (a, r, s, h))
                verdict = dev.verify_batch_device(a, r, s, h)
            else:
                verdict = sharding.verify_batch_sharded(a, r, s, h)
        with trace_span("verify", "persig_readback", bucket=bucket):
            verdict = np.asarray(verdict)
        _count_verified("persig", n)
        verdict = verdict & valid
        out = verdict[:n].tolist()
        loc.note(bad=out.count(False))
    return all(out) and bool(out), out


def _device_verify_hash(pubkeys: list[bytes], msgs: list[bytes], parsed,
                        packed=_NO_PACK,
                        device=None) -> tuple[bool, list[bool]]:
    """_device_verify with FUSED hash-to-scalar: h = SHA512(R||A||M)
    mod L, the per-pubkey aggregation and the A-side recode all run on
    device (ops/ed25519.rlc_verify_hash_kernel) — no digest ever
    crosses back to the host, including the per-signature localization
    kernel on a reject.  `parsed` is a parse_batch result
    ((r_enc, s) | None; no h).  Raises ValueError("message exceeds
    max_blocks") when a message outgrows the static block bucket — the
    dispatch layer's host-fallback trigger."""
    import numpy as np

    from ..ops import ed25519 as dev

    n = len(pubkeys)
    if n >= 2:
        rlc_ok = None
        if packed is _NO_PACK and device is None:
            from . import mesh

            rlc_ok = mesh.maybe_split_verify_hash(pubkeys, msgs, parsed)
        if rlc_ok is None:
            if packed is _NO_PACK:
                with trace_span("verify", "host_pack", batch=n):
                    packed = ed.pack_rlc_device_hash(
                        pubkeys, msgs, [b""] * n, parsed=parsed)
            rlc_ok = packed is not None and \
                ed.rlc_verify_hash(packed, device=device)
        if rlc_ok:
            _count_verified("rlc", n)
            return True, [True] * n
        from ..libs import flightrec

        dm = libmetrics.device_metrics()
        if dm is not None:
            dm.rlc_fallbacks.inc()
        flightrec.record(flightrec.EV_RLC_FALLBACK, batch=n)
    bucket = dev.bucket_size(n)
    a, r, s, bh, bl, nb, valid = ed.pack_batch_device_hash(
        pubkeys, msgs, [b""] * n, bucket, parsed=parsed)
    if device is not None:
        import jax

        a, r, s, bh, bl, nb = (jax.device_put(x, device)
                               for x in (a, r, s, bh, bl, nb))
    verdict = np.asarray(dev.verify_batch_hash_device(a, r, s, bh, bl,
                                                      nb))
    _count_verified("persig", n)
    verdict = verdict & valid
    out = verdict[:n].tolist()
    return all(out) and bool(out), out


class CpuSecp256k1BatchVerifier(_CpuLoopVerifier):
    """Parity oracle for the secp256k1 device path."""

    KEY_TYPE = "secp256k1"

    def _check(self, pk, m, s):
        from . import secp256k1 as sk
        return sk.PubKey(pk).verify_signature(m, s)


class TpuSecp256k1BatchVerifier(_SigCollector):
    """ECDSA batch on the device.  Default path: the unified MSM
    engine (ops/msm.py + ops/secp256k1.msm_verify_kernel) — the whole
    commit's checks become two shared-table multi-products (u1·G
    against a baked G window table, u2·Q against QTableCache-resident
    per-key tables), ~1250 field-muls/signature vs ~4224 for the
    ladder.  ECDSA admits no RLC whole-batch equation (each check
    compares an x-coordinate), so verdicts stay per-signature — which
    also means rejects need no localization round.  Set
    COMETBFT_TPU_SECP_MSM=0 to fall back to the per-signature Straus
    ladder (ops/secp256k1.verify_kernel) — the bench A/B arm and the
    operator escape hatch.  The reference refuses to batch secp256k1
    at all (crypto/batch/batch.go:12)."""

    KEY_TYPE = "secp256k1"

    def _verify_items(self) -> tuple[bool, list[bool]]:
        import numpy as np

        from ..ops import ed25519 as ed_dev
        from ..ops import secp256k1 as dev
        from . import secp256k1 as sk

        n = len(self._items)
        if n == 0:
            return False, []
        pubkeys = [i[0] for i in self._items]
        msgs = [i[1] for i in self._items]
        sigs = [i[2] for i in self._items]
        if sk.msm_enabled():
            from . import mesh
            out = mesh.maybe_split_secp_verify(pubkeys, msgs, sigs)
            if out is None:
                out = sk.verify_msm_batch(pubkeys, msgs, sigs)
            return all(out) and bool(out), out
        bucket = ed_dev.bucket_size(n)      # same bucketing discipline
        packed = sk.pack_batch(pubkeys, msgs, sigs, bucket)
        valid = packed[-1]
        verdict = np.asarray(dev.verify_batch_device(*packed[:-1]))
        verdict = verdict & valid
        out = verdict[:n].tolist()
        return all(out) and bool(out), out


class CpuSr25519BatchVerifier(_CpuLoopVerifier):
    """Parity oracle for the sr25519 device path."""

    KEY_TYPE = "sr25519"

    def _check(self, pk, m, s):
        from . import sr25519 as sr
        return sr.PubKey(pk).verify_signature(m, s)


class TpuSr25519BatchVerifier(_SigCollector):
    """sr25519 batches on the ed25519 device kernels: ristretto points
    re-encoded in Edwards form, Merlin challenges in place of the
    SHA-512 challenge (see crypto/sr25519.to_edwards_inputs; the
    reference's analog is sr25519.BatchVerifier in batch.go)."""

    KEY_TYPE = "sr25519"

    def _verify_items(self) -> tuple[bool, list[bool]]:
        from . import sr25519 as sr

        n = len(self._items)
        if n == 0:
            return False, []
        # host: ristretto decode + transcript challenges; parsed feeds
        # the SAME packers as ed25519 (ed_pub stands in for pubkeys[i],
        # k for the hash h)
        ed_pubs, parsed = [], []
        for pk, m, s in self._items:
            t = sr.to_edwards_inputs(pk, m, s)
            if t is None:
                ed_pubs.append(b"\x00" * 32)
                parsed.append(None)
            else:
                a_ed, r_ed, s_int, k = t
                ed_pubs.append(a_ed)
                parsed.append((r_ed, s_int, k))
        return _device_verify(ed_pubs, parsed)


# device threshold: below this many signatures the host loop wins (the
# reference's analog is batchVerifyThreshold=2, types/validation.go:13;
# ours is higher because the device round-trip has fixed cost).
DEVICE_THRESHOLD = int(os.environ.get("COMETBFT_TPU_BATCH_THRESHOLD", "8"))

# secp256k1 has no RLC batch equation — its device kernel verifies
# per-signature Straus chains, so the per-sig device advantage is far
# smaller than ed25519's and the fixed dispatch cost dominates small
# batches: the crossover against the host loop sits well above the
# ed25519 one.  Not measured on the current code (PERF.md).
SECP_DEVICE_THRESHOLD = int(os.environ.get(
    "COMETBFT_TPU_SECP_THRESHOLD", "96"))


def _device_threshold(key_type: str) -> int:
    if key_type == "secp256k1":
        return max(DEVICE_THRESHOLD, SECP_DEVICE_THRESHOLD)
    return DEVICE_THRESHOLD


def safe_verify(pub_key, msg: bytes, sig: bytes) -> bool:
    """verify_signature with backend errors mapped to invalid.

    The single source of truth for how malformed input or an
    unavailable native backend (bls12381 without its .so) is handled:
    every host single-verify loop — here, types/validation.py's commit
    loop, and DeferredSigBatch — must agree, or the same commit could
    crash one path and merely fail another.

    Routes through the signature-verdict cache: a triple verified
    anywhere in the process (vote stream, a batch window, a previous
    commit check) answers here for one SHA-256; a fresh verdict is
    inserted so the NEXT consumer gets the hit."""
    v = sigcache.get(pub_key, msg, sig)
    if v is not None:
        return v
    try:
        v = bool(pub_key.verify_signature(msg, sig))
    except Exception:
        v = False
    sigcache.insert(pub_key, msg, sig, v)
    return v

# the reference batches only ed25519 & sr25519 (crypto/batch/batch.go:
# 12-35); we also batch secp256k1 on device (a BASELINE.json target)
_SUPPORTED = {"ed25519", "sr25519", "secp256k1"}

_CPU_BY_TYPE = {"ed25519": CpuEd25519BatchVerifier,
                "sr25519": CpuSr25519BatchVerifier,
                "secp256k1": CpuSecp256k1BatchVerifier}
_TPU_BY_TYPE = {"ed25519": TpuEd25519BatchVerifier,
                "sr25519": TpuSr25519BatchVerifier,
                "secp256k1": TpuSecp256k1BatchVerifier}


def supports_batch_verifier(key_type: str) -> bool:
    return key_type in _SUPPORTED


def create_batch_verifier(key_type: str = "ed25519", n_hint: int = 0,
                          provider: str | None = None) -> BatchVerifier:
    provider = provider or os.environ.get("COMETBFT_TPU_PROVIDER", "auto")
    if key_type not in _SUPPORTED:
        raise ValueError(f"no batch verifier for key type {key_type}")
    if provider == "cpu":
        return _CPU_BY_TYPE[key_type]()
    if provider == "tpu":
        return _TPU_BY_TYPE[key_type]()
    # auto: pick by expected batch size (per-keytype crossover — secp
    # lacks an RLC equation, so its device win starts much later)
    if n_hint and n_hint < _device_threshold(key_type):
        return _CPU_BY_TYPE[key_type]()
    return _TPU_BY_TYPE[key_type]()


class MixedBatchVerifier:
    """Routes a mixed-keytype batch to per-type verifiers.

    The reference refuses mixed batches outright
    (types/validation.go:18); handling them on-device is a BASELINE.json
    target, so this wrapper keys each added signature by pubkey type and
    merges verdicts in insertion order.
    """

    def __init__(self, provider: str | None = None):
        self._provider = provider
        self._items: dict[str, list] = {}
        self._order: list[tuple[str, int] | None] = []
        self._singles: list[tuple[object, bytes, bytes]] = []

    def add(self, pubkey, msg: bytes, sig: bytes) -> None:
        kt = pubkey.type() if hasattr(pubkey, "type") else "ed25519"
        if not supports_batch_verifier(kt):
            # no batch kernel for this key type: fall back to the key's own
            # single-verify at verify() time instead of erroring mid-add
            self._order.append(None)
            self._singles.append((pubkey, msg, sig))
            return
        items = self._items.setdefault(kt, [])
        self._order.append((kt, len(items)))
        items.append((pubkey, msg, sig))

    def count(self) -> int:
        return len(self._order)

    def _verify_subtype(self, kt: str, items) -> list[bool]:
        sub = create_batch_verifier(kt, n_hint=len(items),
                                    provider=self._provider)
        for pk, msg, sig in items:
            sub.add(pk, msg, sig)
        return sub.verify()[1]

    def verify(self) -> tuple[bool, list[bool]]:
        # per-type verifiers are created HERE so n_hint can route
        # sub-threshold sub-batches (e.g. a lone secp256k1 validator in
        # an ed25519 set) to the cheap host loop instead of a device
        # dispatch + cold kernel compile.  Sub-batches of DIFFERENT key
        # types are independent programs, so they dispatch
        # concurrently: the device pipelines them and the host loops
        # release the GIL in OpenSSL/numpy.
        results = {}
        if len(self._items) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                    max_workers=len(self._items),
                    thread_name_prefix="mixed-batch") as ex:
                futs = {kt: ex.submit(self._verify_subtype, kt, items)
                        for kt, items in self._items.items()}
                results = {kt: f.result() for kt, f in futs.items()}
        else:
            for kt, items in self._items.items():
                results[kt] = self._verify_subtype(kt, items)
        singles = iter(self._singles)
        out = []
        for slot in self._order:
            if slot is None:
                pk, msg, sig = next(singles)
                out.append(safe_verify(pk, msg, sig))
            else:
                kt, i = slot
                out.append(results[kt][i])
        return all(out) and bool(out), out
