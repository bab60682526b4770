"""Mesh-aware verify dispatch: the multi-device shipping layer.

Promotes the dryrun/validation artifacts (ops/sharding.py,
ops/msm_shard.py, __graft_entry__.dryrun_multichip) into the dispatch
path crypto/batch.py and crypto/dispatch.py actually run.  Three
shapes of parallelism, per ops/sharding.py's design note:

- per-signature verdict kernel: embarrassingly parallel along the
  batch axis — sharded over the 1-D mesh with ONE verdict-bitmap
  gather (ops/sharding.verify_batch_sharded; buckets auto-sized so the
  mesh divides them, ops/sharding.auto_bucket);
- RLC whole-batch kernel: stays single-chip per dispatch.  With >1
  chip a multi-commit window SPLITS ACROSS chips — contiguous chunks,
  one RLC program per chip (split_rlc_verify), each program placed by
  committing its packed inputs to its device.  Chunk verdicts preserve
  the per-chunk reject structure, so a reject localizes with the
  sharded per-signature kernel exactly like the single-chip fallback;
- window round-robin: crypto/dispatch.VerifyPipeline(devices=...)
  rotates depth-K windows over the mesh with per-device in-flight
  tracking and a per-device drain-to-host fault path.

Everything here is CPU-verifiable on the 8-virtual-device mesh
(tests/conftest.py forces xla_force_host_platform_device_count=8); the
same code runs unchanged on a real TPU mesh.  Multi-device dispatch is
OPT-IN via the COMETBFT_TPU_MESH_DEVICES knob or explicit device
lists — see ops/sharding.mesh_device_list.
"""

from __future__ import annotations

import os

import numpy as np

# one RLC program per chip only pays once each chip's chunk amortizes
# its own dispatch + per-chunk pack; below this window size the
# single-device RLC (or the sharded per-signature kernel) wins
MIN_SPLIT = int(os.environ.get("COMETBFT_TPU_MESH_MIN_SPLIT", "256"))


def split_spans(n: int, ndev: int) -> list[tuple[int, int]]:
    """Contiguous near-equal [start, end) chunks, every chunk
    non-empty; fewer spans than devices when n < ndev."""
    ndev = max(1, min(ndev, n))
    base, rem = divmod(n, ndev)
    spans, start = [], 0
    for i in range(ndev):
        end = start + base + (1 if i < rem else 0)
        spans.append((start, end))
        start = end
    return spans


def _healthy_devices(devices):
    """Filter the mesh rotation through the process health registry
    (crypto/devhealth.py): quarantined chips drop out of the split.
    Falls back to the full list when no registry is installed or when
    EVERY chip is benched — a split onto quarantined chips is still
    better than an unannounced behavior change here; the pipeline's
    brownout path is what actually owns the all-dead case."""
    from . import devhealth

    reg = devhealth.registry()
    if reg is None:
        return devices
    usable = [d for i, d in enumerate(devices) if reg.usable(str(i))]
    return usable if usable else devices


def _count_dispatch(i: int, n: int = 0) -> None:
    from ..libs import devprof
    from ..libs import metrics as libmetrics

    dm = libmetrics.device_metrics()
    if dm is not None:
        dm.mesh_dispatches.labels(str(i)).inc()
    # split-RLC chunks bypass the pipeline's per-device accounts; a
    # counter-track sample keeps them visible on the devprof timeline
    rec = devprof.recorder()
    if rec is not None:
        rec.counter("mesh_split_chunk_sigs/dev%d" % i, n)


def split_rlc_verify(pubkeys: list[bytes], parsed, devices,
                     use_cache: bool | None = None, *, msgs=None,
                     sigs=None):
    """One multi-commit window split ACROSS the mesh: chunk i packs on
    the host, commits to devices[i], and dispatches its own RLC
    program; every chip's program is in flight before any verdict is
    read back.  The batch comes as `msgs` and `sigs` (parsed=None:
    pack_rlc hashes for itself) or as parse_and_hash's rows.  Returns
    the per-chunk bool list (len == number of spans), or None when any
    chunk fails structural packing — the caller localizes per
    signature either way."""
    from . import ed25519 as ed

    n = len(pubkeys)
    spans = split_spans(n, len(devices))
    packs = []
    for a, b in spans:
        if parsed is not None:
            packed = ed.pack_rlc(pubkeys[a:b], None, None,
                                 parsed=parsed[a:b])
        else:
            packed = ed.pack_rlc(pubkeys[a:b], msgs[a:b], sigs[a:b])
        if packed is None:
            return None
        packs.append(packed)
    outs = []
    for i, (packed, dev_) in enumerate(zip(packs, devices)):
        outs.append(ed.rlc_verify_async(packed, use_cache=use_cache,
                                        device=dev_))
        _count_dispatch(i, spans[i][1] - spans[i][0])
    return [bool(np.asarray(o)) for o in outs]


def maybe_split_verify(pubkeys: list[bytes], parsed,
                       min_split: int | None = None, *, msgs=None,
                       sigs=None):
    """The crypto/batch._device_verify hook: None when the mesh split
    does not apply (mesh off, too few devices, window under
    MIN_SPLIT); otherwise the whole-window RLC verdict (True = every
    chunk verified; False = some chunk rejected, localize)."""
    n = len(pubkeys)
    if n < (min_split if min_split is not None else MIN_SPLIT):
        return None
    from ..ops import sharding

    devices = sharding.mesh_device_list(None)
    if devices is None:
        return None
    devices = _healthy_devices(devices)
    if len(devices) < 2:
        return None
    verdicts = split_rlc_verify(pubkeys, parsed, devices, msgs=msgs,
                                sigs=sigs)
    if verdicts is None:
        return False
    return all(verdicts)


def split_rlc_verify_hash(pubkeys: list[bytes], msgs: list[bytes],
                          parsed, devices):
    """split_rlc_verify for the fused hash-to-scalar kernel: each
    chunk's pack carries its own message blocks (blocks_hi/lo travel to
    that chunk's chip with the rest of the pack), so the device-hash
    mode splits across a mesh exactly like the host-hash mode.
    `parsed` is a parse_batch result ((r_enc, s) | None).  Propagates
    pack_rlc_device_hash's ValueError on an oversized message."""
    from . import ed25519 as ed

    n = len(pubkeys)
    spans = split_spans(n, len(devices))
    packs = []
    for a, b in spans:
        packed = ed.pack_rlc_device_hash(pubkeys[a:b], msgs[a:b],
                                         [b""] * (b - a),
                                         parsed=parsed[a:b])
        if packed is None:
            return None
        packs.append(packed)
    outs = []
    for i, (packed, dev_) in enumerate(zip(packs, devices)):
        outs.append(ed.rlc_verify_hash_async(packed, device=dev_))
        _count_dispatch(i, spans[i][1] - spans[i][0])
    return [bool(np.asarray(o)) for o in outs]


def maybe_split_verify_hash(pubkeys: list[bytes], msgs: list[bytes],
                            parsed, min_split: int | None = None):
    """maybe_split_verify for the device-hash mode (see
    crypto/batch._device_verify_hash)."""
    n = len(pubkeys)
    if n < (min_split if min_split is not None else MIN_SPLIT):
        return None
    from ..ops import sharding

    devices = sharding.mesh_device_list(None)
    if devices is None:
        return None
    devices = _healthy_devices(devices)
    if len(devices) < 2:
        return None
    verdicts = split_rlc_verify_hash(pubkeys, msgs, parsed, devices)
    if verdicts is None:
        return False
    return all(verdicts)


def verify_batch_mesh(pubkeys: list[bytes], parsed):
    """Per-signature verdicts with the batch axis sharded over the
    mesh and the bucket auto-sized from device_count() — the
    embarrassingly-parallel path, one verdict-bitmap gather."""
    from ..ops import ed25519 as dev  # noqa: F401 (bucket constants)
    from ..ops import sharding
    from . import ed25519 as ed

    n = len(pubkeys)
    bucket = sharding.auto_bucket(n)
    a, r, s, h, valid = ed.pack_batch(pubkeys, [b""] * n, [b""] * n,
                                      bucket, parsed=parsed)
    verdict = np.asarray(sharding.verify_batch_sharded(a, r, s, h))
    return (verdict & valid)[:n].tolist()


def split_secp_verify(pubkeys: list[bytes], msgs: list[bytes],
                      sigs: list[bytes], devices):
    """split_rlc_verify for the unified secp256k1 MSM path: chunk i
    packs on the host (Joye-Tunstall recode + distinct-key table
    lookup through the QTableCache, keyed per device so each chip
    keeps its own resident copy) and dispatches its own MSM program;
    all chips are in flight before any verdict is read back.  Returns
    per-signature verdicts in submission order — the MSM verdicts are
    already per-signature, so unlike the RLC split there is no
    localization round to run on reject."""
    from . import secp256k1 as sk

    n = len(pubkeys)
    spans = split_spans(n, len(devices))
    outs = []
    for i, ((a, b), dev_) in enumerate(zip(spans, devices)):
        outs.append(sk.verify_msm_async(pubkeys[a:b], msgs[a:b],
                                        sigs[a:b], device=dev_))
        _count_dispatch(i, b - a)
    verdicts: list[bool] = []
    for verdict, valid, m in outs:
        out = np.asarray(verdict) & valid
        verdicts.extend(bool(v) for v in out[:m])
    return verdicts


def maybe_split_secp_verify(pubkeys: list[bytes], msgs: list[bytes],
                            sigs: list[bytes],
                            min_split: int | None = None):
    """The TpuSecp256k1BatchVerifier hook: None when the mesh split
    does not apply (mesh off, too few devices, window under
    MIN_SPLIT); otherwise the per-signature verdict list."""
    n = len(pubkeys)
    if n < (min_split if min_split is not None else MIN_SPLIT):
        return None
    from ..ops import sharding

    devices = sharding.mesh_device_list(None)
    if devices is None:
        return None
    devices = _healthy_devices(devices)
    if len(devices) < 2:
        return None
    return split_secp_verify(pubkeys, msgs, sigs, devices)
