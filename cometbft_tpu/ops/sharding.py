"""Multi-chip signature verification: the batch IS the sequence axis
(SURVEY §5 "long-context"): shard it over a 1-D `jax.sharding.Mesh`
and let XLA insert the verdict collectives over ICI.

This is the production analog of __graft_entry__.dryrun_multichip: the
per-signature kernel is embarrassingly parallel along the batch axis
(each signature verifies independently), so data-parallel sharding
needs no communication until the final verdict gather.  The RLC
whole-batch kernel stays single-chip per dispatch — with >1 chip the
caller splits commits ACROSS chips (one RLC per chip) instead, which
preserves the per-commit verdict structure.

Tests exercise this on the 8-virtual-device CPU mesh from
tests/conftest.py; the driver's dryrun does the same with the full
verify step.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import compile_hook
from . import ed25519 as dev


def device_count() -> int:
    return len(jax.devices())


def mesh_device_list(k: int | None = None):
    """Devices the DISPATCH layer round-robins windows over
    (crypto/dispatch.VerifyPipeline, crypto/mesh), or None for the
    single-device path.

    k > 1 asks for that many devices (clamped to what exists);
    k == 1 forces single-device; k None/0 defers to the
    COMETBFT_TPU_MESH_DEVICES env knob, which itself defaults to
    single-device — multi-device dispatch is OPT-IN, so a process that
    happens to see a virtual CPU mesh (tests force 8 devices) keeps its
    existing behavior unless a caller or the operator turns the mesh
    on.  0 via the env knob means "all local devices"."""
    if k is None or k == 0:
        raw = os.environ.get("COMETBFT_TPU_MESH_DEVICES")
        if raw is None:
            return None
        k = int(raw)
    devs = list(jax.devices())
    if k <= 0:
        k = len(devs)
    k = min(k, len(devs))
    return devs[:k] if k > 1 else None


def auto_bucket(n: int, n_devices: int | None = None) -> int:
    """Batch bucket for n signatures that the mesh divides evenly:
    dev.bucket_size rounded up to a multiple of the device count, so a
    sharded dispatch never sees a ragged shard.  Buckets and meshes are
    almost always both powers of two, in which case this IS
    dev.bucket_size."""
    b = dev.bucket_size(n)
    nd = n_devices if n_devices is not None else device_count()
    if nd > 1 and b % nd:
        b = math.lcm(b, nd)
    return b


@functools.lru_cache(maxsize=1)
def _mesh() -> Mesh:
    return Mesh(np.array(jax.devices()), ("sig",))


@functools.lru_cache(maxsize=1)
def _sharded_verify():
    """Jitted verify step with batch-axis input/output shardings; the
    jit shards plain numpy inputs itself."""
    mesh = _mesh()
    shard_in = NamedSharding(mesh, P(None, "sig"))
    out = NamedSharding(mesh, P("sig"))
    return jax.jit(dev.verify_kernel,
                   in_shardings=(shard_in,) * 4,
                   out_shardings=out)


def verify_batch_sharded(a_words, r_words, s_limbs, h_limbs):
    """Per-signature verdicts with the batch axis sharded over every
    local device.  Caller guarantees batch % n_devices == 0 (pack to a
    bucket that divides; dev.BATCH_BUCKETS are powers of two)."""
    n = device_count()
    if n < 2 or a_words.shape[-1] % n != 0:
        return dev.verify_batch_device(a_words, r_words, s_limbs, h_limbs)
    with compile_hook.dispatch_scope("ed25519_persig_sharded",
                                     a_words.shape[-1:]):
        return _sharded_verify()(a_words, r_words, s_limbs, h_limbs)
