"""The shipping Pallas MSM kernels under shard_map: multi-chip RLC.

Parallelism layout (SURVEY.md §5 "long-context"): the signature/lane
axis is the sequence axis of this domain — it shards across the mesh.
Each device decompresses its own key/nonce shard, builds its own window
tables, and runs the window-major Straus kernel on its local lanes
(ops/pallas_msm.msm_window_major).  The per-device result is a
(4, 20, out_l) accumulator POINT whose lane-sum is the device's partial
MSM; the cross-device reduction is elliptic-curve group addition, NOT
an elementwise psum, so the combine is an all_gather of the tiny
accumulators (4*20*out_l int32 = 10 KB/device) followed by the fused
fold/verify epilogue on the gathered tensor — replicated compute that
costs microseconds and keeps the verdict bit identical on every chip.

Collective traffic per verify: one all_gather of ~10 KB/device on each
MSM side + a 4-byte psum for the decompression-ok bit — ICI-trivial
against the multi-ms local MSM, which is why lane sharding scales
linearly until local widths fall under one Pallas block (128 lanes).

The reference scales commit verification only across CPU cores inside
one process (its BatchVerifier has no cross-machine story at all);
this module is the TPU-pod equivalent the blocksync/light pipelines
call through crypto/batch.py when a mesh is configured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _gather_lanes(part, axis: str):
    """(4, 20, out_l) per-device point partials -> (4, 20, n*out_l)."""
    parts = jax.lax.all_gather(part, axis)        # (n, 4, 20, out_l)
    n, c, l, w = parts.shape
    return jnp.moveaxis(parts, 0, 2).reshape(c, l, n * w)


def sharded_msm(tab, mags, negs, *, mesh, axis: str = "sig",
                interpret=False, blk=None, use_pallas: bool = True):
    """One lane-sharded MSM: per-device window-major Straus kernel on
    the local table/digit shard, all_gather of the accumulator points,
    local tree fold — returns the replicated (4, 20, 1) MSM point.

    The interpret-mode validation surface for the CPU mesh: interpret
    compile cost scales with grid steps (windows x blocks unrolled),
    so callers validate with SYNTHETIC few-window digit tensors — the
    kernel's correctness argument is window-count-independent.

    use_pallas=False swaps the per-shard Straus scan to the XLA path
    (ops/ed25519._msm_scan_xla) while keeping the sharding layout, the
    accumulator-point all_gather, and the group-addition fold — the
    multi-chip-specific machinery — identical.  That is the budget
    surface for the driver dryrun: one interpret-mode Pallas compile
    costs minutes on a single core (the MULTICHIP_r05 rc=124 lesson),
    and the Pallas kernel body is proven by the tier-1 interpret
    parity test (tests/test_pallas_msm.py) and on the chip."""
    from jax import shard_map

    from . import ed25519 as dev
    from . import pallas_msm as pm

    ndev = mesh.shape[axis]
    assert tab.shape[-1] % ndev == 0, (tab.shape, ndev)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, None, None, axis), P(None, axis),
                  P(None, axis)),
        out_specs=P(), check_vma=False)
    def run(tab_l, mags_l, negs_l):
        if use_pallas:
            b = blk or pm.blk_for(tab_l.shape[-1])
            part = pm.msm_window_major(tab_l, mags_l, negs_l,
                                       interpret=interpret, blk=b)
        else:
            part = dev._msm_scan_xla(tab_l, mags_l, negs_l)
        return dev._tree_reduce(_gather_lanes(part, axis), 1)

    return run(tab, mags, negs)


def sharded_bucket_msm(tab, mags, negs, *, mesh, axis: str = "sig",
                       width: int = 5):
    """sharded_msm with the generic engine's bucket (Pippenger) arm as
    the per-device core: each device bucket-accumulates and folds its
    local lane shard (ops/msm.bucket_msm over tab[1] = -P, the same
    base-point plane the digit streams are aimed at), then the tiny
    per-device accumulator POINTS all_gather and tree-fold exactly like
    the Straus form — bucket accumulation shards across the mesh for
    free because buckets are per-device-local and the cross-device
    combine stays group addition on out_l = 1 partials."""
    from jax import shard_map

    from . import ed25519 as dev
    from . import msm as engine

    ndev = mesh.shape[axis]
    assert tab.shape[-1] % ndev == 0, (tab.shape, ndev)
    spec = engine.ed25519_spec()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, None, None, axis), P(None, axis),
                  P(None, axis)),
        out_specs=P(), check_vma=False)
    def run(tab_l, mags_l, negs_l):
        part, _ = engine.bucket_msm(spec, (tab_l[1], None),
                                    mags_l, negs_l, width)
        return dev._tree_reduce(_gather_lanes(part, axis), 1)

    return run(tab, mags, negs)


def rlc_verify_sharded(a_words, r_words, a_mag, a_neg, r_mag, r_neg,
                       *, mesh, axis: str = "sig", interpret=False,
                       blk=None):
    """Whole-batch RLC verify with BOTH MSM sides lane-sharded over
    `mesh`: the multi-chip form of ops/ed25519.rlc_verify_kernel.

    Inputs are the pack_rlc arrays with widths divisible by the mesh
    size.  Table build / decompression run the shipping per-backend
    path (_msm_tables: Pallas on TPU, XLA elsewhere); the Straus scan
    runs pallas_msm.msm_window_major explicitly so interpret-mode
    validation on a CPU mesh exercises the REAL kernel, not the XLA
    fallback.  blk must divide the per-device lane width.
    """
    from jax import shard_map

    from . import ed25519 as dev
    from . import pallas_msm as pm

    ndev = mesh.shape[axis]
    for arr in (a_words, a_mag, a_neg):
        assert arr.shape[-1] % ndev == 0, (arr.shape, ndev)
    for arr in (r_words, r_mag, r_neg):
        assert arr.shape[-1] % ndev == 0, (arr.shape, ndev)

    def _local_msm(words, mags, negs):
        tab, ok = dev._msm_tables(words)
        b = blk or pm.blk_for(tab.shape[-1])
        assert b is not None and tab.shape[-1] % b == 0, \
            (tab.shape, b, "per-device width must admit a block")
        part = pm.msm_window_major(tab, mags, negs,
                                   interpret=interpret, blk=b)
        return part, ok

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, axis),) * 6,
        out_specs=P(),
        # the gathered fold is replicated by construction; the rep
        # checker can't see through pallas_call, so tell it ourselves
        check_vma=False)
    def run(aw, rw, am, an, rm, rn):
        pa, ok_a = _local_msm(aw, am, an)
        pr, ok_r = _local_msm(rw, rm, rn)
        ga = _gather_lanes(pa, axis)
        gr = _gather_lanes(pr, axis)
        ok = (ok_a & ok_r).astype(jnp.int32)
        n_ok = jax.lax.psum(ok, axis)
        n_tot = jax.lax.psum(jnp.ones((), jnp.int32), axis)
        w = ga.shape[-1]
        tile = 128 if w % 128 == 0 else w     # small CPU-mesh shapes
        verdict = pm.fold_verify(ga, gr, interpret=interpret, tile=tile)
        return verdict & (n_ok == n_tot)

    return run(a_words, r_words, a_mag, a_neg, r_mag, r_neg)
