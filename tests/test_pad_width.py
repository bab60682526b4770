"""ops/ed25519.pad_width: the bucket grid of an MSM side, and which
kernels the widths it returns reach.

Where the Pallas kernels lower for real (a TPU: _pallas_capable) every
width it returns has a Pallas block, so no RLC batch packed there lowers
to the XLA Straus scan; off the chip (every other test here, the CPU
product path) the grid is the one the compiled programs and the
persistent cache were built on, pinned below.  Nothing is compiled or
dispatched here.
"""

import pytest

from cometbft_tpu.ops import ed25519 as dev
from cometbft_tpu.ops import pallas_msm

NS = list(range(1, 1025)) + list(range(1025, 20001, 37)) + [20000]


def _grid(n):
    """A pinned copy of the grid as it was before the chip got widths
    of its own: 8..192 verbatim, then (128, 160, 192) << L."""
    for w in (8, 16, 32, 64, 96, 128, 160, 192):
        if n <= w:
            return w
    lvl = 1
    while True:
        for base in (128, 160, 192):
            if n <= base << lvl:
                return base << lvl
        lvl += 1


@pytest.fixture
def on_chip(monkeypatch):
    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)


@pytest.fixture
def off_chip(monkeypatch):
    monkeypatch.setattr(dev, "_pallas_capable", lambda: False)


# -- (a) on the chip: every width has a Pallas block -----------------------

def _holds_n(n, w):
    return w >= n


def _has_a_block(n, w):
    return pallas_msm.blk_for(w) is not None and \
        dev._pallas_blk(w) is not None


def _no_narrower_than_the_grid(n, w):
    return w >= _grid(n)


def _smallest_such_width(n, w):
    return all(pallas_msm.blk_for(v) is None for v in range(_grid(n), w))


def _the_grid_above_320(n, w):
    return n <= 320 or w == _grid(n)


def _monotone(n, w):
    return n == 1 or dev.pad_width(n - 1) <= w


@pytest.mark.parametrize("holds", [
    _holds_n, _has_a_block, _no_narrower_than_the_grid,
    _smallest_such_width, _the_grid_above_320, _monotone],
    ids=lambda f: f.__name__.strip("_"))
def test_on_the_chip_every_width(on_chip, holds):
    assert [n for n in NS if not holds(n, dev.pad_width(n))] == []


@pytest.mark.parametrize("n,want", [
    (1, 128), (58, 128), (59, 128), (118, 128), (128, 128), (129, 256),
    (176, 256), (256, 256), (257, 384), (320, 384), (321, 384),
    (385, 512), (3744, 4096), (6667, 8192)])
def test_on_the_chip_widths(on_chip, n, want):
    assert dev.pad_width(n) == want


@pytest.mark.parametrize("blk,n,want", [
    (64, 58, 64),        # a narrow test block: 64 has a block again
    (64, 90, 128),       # 96 has none at 64 lanes a block
    (64, 176, 192),
    (1024, 58, 128),     # a wider block changes nothing below it
    (0, 58, 64),         # no block is legal at all: the grid's own
    (-5, 300, 320)])
def test_the_rule_follows_blk_for(on_chip, monkeypatch, blk, n, want):
    """The widths come from pallas_msm.blk_for, not from a second
    table: an override of the block cannot make the two disagree."""
    monkeypatch.setattr(pallas_msm, "BLK", blk)
    w = dev.pad_width(n)
    assert w == want
    assert blk <= 0 or pallas_msm.blk_for(w) is not None


# -- (b) off the chip: today's grid, exactly -------------------------------

@pytest.mark.parametrize("n,want", [
    (1, 8), (8, 8), (9, 16), (58, 64), (59, 64), (65, 96), (118, 128),
    (176, 192), (193, 256), (300, 320), (321, 384), (3744, 4096),
    (4097, 5120), (6667, 8192)])
def test_off_the_chip_widths_are_pinned(off_chip, n, want):
    assert dev.pad_width(n) == want


def test_off_the_chip_the_grid_is_unchanged_for_every_n(off_chip):
    assert [n for n in NS if dev.pad_width(n) != _grid(n)] == []


def test_this_backend_is_off_the_chip():
    """Tier-1 runs on the CPU backend: the programs every other test
    compiles keep the widths they had."""
    assert not dev._pallas_capable()
    assert [dev.pad_width(n) for n in (5, 58, 176)] == [8, 64, 192]


# -- (c) what those widths lower to ----------------------------------------

def _stages(plan):
    return [plan["fold"]] + [plan[side][stage] for side in ("a", "r")
                             for stage in ("decompress", "tables", "msm")]


@pytest.mark.parametrize("k,n", [(128, 128), (128, 4096), (256, 256)])
def test_on_the_chip_every_stage_is_pallas(on_chip, k, n):
    assert _stages(dev.rlc_kernel_plan(k, n)) == ["pallas"] * 7
    assert dev.rlc_kernel_name(k, n) == "pallas"


def test_no_width_pad_width_returns_has_an_xla_stage(on_chip):
    widths = sorted({dev.pad_width(n) for n in NS})
    assert widths[:3] == [128, 256, 384]
    assert [w for w in widths
            if set(_stages(dev.rlc_kernel_plan(w, w))) != {"pallas"}] == []


@pytest.mark.parametrize("k,n,want", [
    (64, 64, "xla"),        # both sides below a block
    (192, 6144, "xla"),     # one side on the XLA path is enough
    (128, 128, "pallas")])
def test_kernel_name_on_the_chip(on_chip, k, n, want):
    assert dev.rlc_kernel_name(k, n) == want


@pytest.mark.parametrize("k,n", [(64, 64), (128, 128), (128, 4096)])
def test_kernel_name_off_the_chip_is_xla(off_chip, k, n):
    assert dev.rlc_kernel_name(k, n) == "xla"
    assert "pallas" not in _stages(dev.rlc_kernel_plan(k, n))
