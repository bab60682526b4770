"""The main path's device programs, compiled for a described TPU v5e.

No chip is attached here; the TPU compiler is, and it compiles for a
topology that is described (jax.experimental.topologies).  What these
tests guard is what interpret mode cannot see: that Mosaic still
accepts each Pallas kernel at the block and width the product runs it
at (a slice off the tiling, a kernel over its VMEM bound and a program
over HBM are all refused here, at no chip time), and that each whole
program still contains the kernels it is meant to — a silent drop to
the XLA path shows as a changed tpu_custom_call count.

Tier-1 holds the five kernels (tens of seconds each); the whole RLC
programs take minutes each and are marked slow.  Nothing runs, so
nothing here says a kernel computes the right answer: chip_smoke.py on
the chip does.

Everything that touches the TPU library happens inside fixtures and
tests, never at import: under xdist every worker imports this file and
only one may load the library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from cometbft_tpu.ops import ed25519 as dev
from cometbft_tpu.ops import pallas_decompress as pd
from cometbft_tpu.ops import pallas_msm

W = 8192          # one 10,000-validator commit: 6,667 signers pad here
BLK = 512         # pallas_msm.BLK, the block the product takes at W

# (width, block) each kernel is held at: the widest side of the main
# path, and the narrowest width pad_width returns on the chip - one
# block of 128 lanes, where a 175-validator commit's apply-time
# remainder (58 signatures) and every small batch land
SIDES = pytest.mark.parametrize("w, blk", [(W, BLK), (128, 128)],
                                ids=["8192x512", "128x128"])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_compile_cache, monkeypatch):
    """compile(fn, *shapes) -> the compiled program's text, with the
    program taking the branches it takes on a TPU."""
    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    # a kernel's trace is a few hundred thousand equations, and every
    # one kept alive makes the collector's passes over the next
    # kernel's tracing slower: no test here reads another's trace
    jax.clear_caches()


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_


def _point(w):
    return ((4, 20, w), I32)


def _table(w):
    return ((17, 4, 20, w), I32)


@SIDES
def test_decompress(compile_for_chip, w, blk):
    assert dev._pallas_blk(w, cap=pd.BLK) == blk
    text = compile_for_chip(lambda e: pd.decompress(e, blk=blk),
                            ((8, w), U32))
    assert _kernels(text) == 1


@SIDES
def test_table17_neg(compile_for_chip, w, blk):
    assert dev._pallas_blk(w) == blk
    text = compile_for_chip(lambda p: pallas_msm.table17_neg(p, blk=blk),
                            _point(w))
    assert _kernels(text) == 1


@SIDES
@pytest.mark.parametrize("nwin", [52, 26])   # A side (256-bit), R side
def test_msm_window_major(compile_for_chip, nwin, w, blk):
    text = compile_for_chip(
        lambda t, m, n: pallas_msm.msm_window_major(t, m, n, blk=blk),
        _table(w), ((nwin, w), I32), ((nwin, w), BOOL))
    assert _kernels(text) == 1


_FOLDS: dict = {}         # partial shapes -> the fold's compiled text


@SIDES
def test_fold_verify(compile_for_chip, w, blk):
    # the partials msm_window_major hands the fold: one global
    # accumulator of _out_lanes(blk) lanes whatever the width, so one
    # block a side gives the fold the 128 lanes the widest side does,
    # and one compile serves every width that agrees
    part = ((4, 20, pallas_msm._out_lanes(blk)), I32)
    assert part[0][-1] == 128
    if part not in _FOLDS:
        _FOLDS[part] = compile_for_chip(pallas_msm.fold_verify, part, part)
    assert _kernels(_FOLDS[part]) == 1


def _rlc(k, n):
    return (dev.rlc_verify_kernel,
            ((8, k), U32), ((8, n), U32), ((52, k), I32), ((52, k), BOOL),
            ((26, n), I32), ((26, n), BOOL))


def _rlc_cached(k, n):
    return (dev.rlc_verify_kernel_cached_a,
            ((17, 4, 20, k), I32), ((), BOOL), ((8, n), U32),
            ((52, k), I32), ((52, k), BOOL),
            ((26, n), I32), ((26, n), BOOL))


def _persig(n):
    return (dev.verify_kernel, ((8, n), U32), ((8, n), U32),
            ((16, n), U32), ((16, n), U32))


@pytest.mark.slow
@pytest.mark.parametrize("program, kernels", [
    # an A side of 192 lanes, which no block divides: the XLA Straus
    # scan.  On the chip pad_width returns no such width any more (176
    # keys pad to 256); a caller that packs at a width of its own still
    # gets a program, with the kernels of the R side alone
    (_rlc(192, 6144), 3),
    # one 10,000-validator commit, Pallas on both sides
    (_rlc(8192, 8192), 7),
    # the full 10k blocksync window against cached A tables
    (_rlc_cached(8192, 327680), 5),
    # per-signature localisation at its largest bucket
    (_persig(16384), 1),
    # the narrowest programs pad_width gives the chip, one block a
    # side (a 175-validator commit's 58-signature apply-time remainder):
    # the kernel counts of the wide programs, no drop to the XLA path
    (_rlc(128, 128), 7),
    (_rlc_cached(128, 128), 5),
], ids=["rlc-192x6144", "rlc-8192x8192", "rlc_cached-8192x327680",
        "persig-16384", "rlc-128x128", "rlc_cached-128x128"])
def test_whole_program(compile_for_chip, program, kernels):
    fn, *shapes = program
    assert _kernels(compile_for_chip(fn, *shapes)) == kernels
