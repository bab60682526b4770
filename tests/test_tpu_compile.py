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

    return compile_


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_
POINT = ((4, 20, W), I32)
TABLE = ((17, 4, 20, W), I32)


def test_decompress(compile_for_chip):
    text = compile_for_chip(lambda e: pd.decompress(e, blk=BLK),
                            ((8, W), U32))
    assert _kernels(text) == 1


def test_table17_neg(compile_for_chip):
    text = compile_for_chip(lambda p: pallas_msm.table17_neg(p, blk=BLK),
                            POINT)
    assert _kernels(text) == 1


@pytest.mark.parametrize("nwin", [52, 26])   # A side (256-bit), R side
def test_msm_window_major(compile_for_chip, nwin):
    text = compile_for_chip(
        lambda t, m, n: pallas_msm.msm_window_major(t, m, n, blk=BLK),
        TABLE, ((nwin, W), I32), ((nwin, W), BOOL))
    assert _kernels(text) == 1


def test_fold_verify(compile_for_chip):
    # the partials msm_window_major hands the fold at this width
    part = jax.eval_shape(
        lambda t, m, n: pallas_msm.msm_window_major(t, m, n, blk=BLK),
        *(jax.ShapeDtypeStruct(s, d) for s, d in
          (TABLE, ((52, W), I32), ((52, W), BOOL))))
    text = compile_for_chip(pallas_msm.fold_verify,
                            (part.shape, part.dtype),
                            (part.shape, part.dtype))
    assert _kernels(text) == 1


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
    # a 48-block window of 175-validator commits: 176 keys pad to 192,
    # which no block divides, so the A side is the XLA Straus scan
    (_rlc(192, 6144), 3),
    # one 10,000-validator commit, Pallas on both sides
    (_rlc(8192, 8192), 7),
    # the full 10k blocksync window against cached A tables
    (_rlc_cached(8192, 327680), 5),
    # per-signature localisation at its largest bucket
    (_persig(16384), 1),
], ids=["rlc-192x6144", "rlc-8192x8192", "rlc_cached-8192x327680",
        "persig-16384"])
def test_whole_program(compile_for_chip, program, kernels):
    fn, *shapes = program
    assert _kernels(compile_for_chip(fn, *shapes)) == kernels
