"""chip_smoke.py driven on the CPU at 7 validators.

The device lane is replaced by a host judge (VerifyPipeline
._device_dispatch and TpuEd25519BatchVerifier._verify_items): a real lane
on XLA-CPU cold-compiles minutes per program shape and has no place in
tier-1.  What these tests pin is the smoke's own logic — that every
phase prints its line, that the counter checks hold when the windows go
down the device path, and that they FAIL when the device is hidden: on
a CPU, after a dispatch fault, and when the verdict cache answers.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

from cometbft_tpu.crypto import batch as cb  # noqa: E402
from cometbft_tpu.crypto import dispatch  # noqa: E402
from cometbft_tpu.crypto import ed25519 as ed  # noqa: E402
from cometbft_tpu.crypto import sigcache  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

N_VALS = 7
N_BLOCKS = 16


def _judge(triples):
    verdicts = [ed.PubKey(bytes(pk)).verify_signature(m, s)
                for pk, m, s in triples]
    return all(verdicts) and bool(verdicts), verdicts


def host_judge_window(self, win, device=None):
    """Stands in for VerifyPipeline._device_dispatch: (ok, verdicts)
    from the host verifier, one bool per staged item."""
    return _judge((dispatch._pk_bytes(pk), m, s)
                  for pk, m, s in win.items)


def host_judge_batch(self):
    """Stands in for TpuEd25519BatchVerifier._verify_items (the
    single-commit batch seam), with the device path's accounting: the
    RLC program is dispatched, a reject counts one fallback and the
    per-signature program localises it."""
    from cometbft_tpu.libs import flightrec
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.ops import compile_hook

    n = len(self._items)
    ok, verdicts = _judge(self._items)
    with compile_hook.dispatch_scope("ed25519_rlc", (n, n)):
        pass
    if not ok:
        libmetrics.device_metrics().rlc_fallbacks.inc()
        flightrec.record(flightrec.EV_RLC_FALLBACK, batch=n)
        with compile_hook.dispatch_scope("ed25519_persig", (n,)):
            pass
    return ok, verdicts


@pytest.fixture
def stub_device(monkeypatch):
    """The host judges in place of the device, thresholds low enough
    that 7 validators reach the device lane."""
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD",
                        4)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 2)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        host_judge_window)
    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                        host_judge_batch)


@pytest.fixture
def inst():
    inst = chip_smoke.install_instruments()
    yield inst
    chip_smoke.uninstall_instruments()


@pytest.fixture
def chain():
    return chip_smoke.build_chain("vals7", N_VALS, N_BLOCKS, seed=22)


def _lines(capsys) -> list[dict]:
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_phases_print_and_counters_hold(stub_device, inst, chain, capsys):
    bs = chip_smoke.phase_blocksync(chain, inst, min_device_windows=1)
    assert bs["app_hash_equal"] and bs["ok"]
    assert bs["pipeline"]["device_windows"] >= 1
    assert bs["pipeline"]["drained_windows"] == 0
    assert set(bs["window_paths"]) <= {"device", "host"}
    assert bs["window_paths"]["device"] >= 1
    ref = chip_smoke.phase_reference(chain, workers=1)
    assert ref["signatures"] == N_VALS * N_BLOCKS and ref["rejected"] == 0
    tam = chip_smoke.phase_tamper(chain, inst)
    assert tam["error"].startswith(f"wrong signature (#{tam['index']})")
    assert tam["rlc_fallbacks"] == 1 and tam["persig_dispatches"] == 1
    lt = chip_smoke.phase_light(chain, inst, n_headers=8, window=4)
    assert lt["headers"] == 8 and lt["window_paths"]["device"] >= 2
    progs = chip_smoke.phase_programs(inst, pallas_from=4096)
    assert progs["distinct_programs"] >= 2
    phases = [ln["phase"] for ln in _lines(capsys)]
    assert phases == ["blocksync", "reference", "tamper", "light",
                      "programs"]


def test_main_fails_on_cpu(capsys):
    assert chip_smoke.main([]) != 0
    last = _lines(capsys)[-1]
    assert last["ok"] is False and "phase" not in last


def test_dispatch_fault_fails_on_drained_windows(stub_device, inst, chain,
                                                 monkeypatch, capsys):
    def boom(self, win, device=None):
        raise RuntimeError("injected dispatch fault")

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="drained_windows"):
        chip_smoke.phase_blocksync(chain, inst, min_device_windows=1)
    line = _lines(capsys)[-1]
    assert line["ok"] is False and line["app_hash_equal"]
    assert line["pipeline"]["faults"] >= 1


def test_skipped_cache_reset_fails_on_cache_windows(stub_device, inst,
                                                    chain, monkeypatch):
    monkeypatch.setattr(sigcache, "reset", lambda: None)
    with pytest.raises(chip_smoke.SmokeFailure, match="path=cache"):
        chip_smoke.phase_blocksync(chain, inst, min_device_windows=1)


def test_expected_programs_take_widths_from_the_packer():
    # 10,000 validators: windows hold 6,667 signatures per commit, the
    # apply-time remainder is 3,333; the FULL commit pads to 10,240
    progs = chip_smoke.expected_programs(10_000, 8, 1, tamper=True)
    assert ("ed25519_rlc", 8192, 65536) in progs
    assert ("ed25519_rlc_cached", 4096, 4096) in progs
    assert ("ed25519_rlc", 10240, 10240) in progs
    assert ("ed25519_persig", 16384) in progs
    # one block: one window, and no LastCommit to check at apply
    assert chip_smoke.expected_programs(10_000, 1, 1, tamper=False) == [
        ("ed25519_rlc", 8192, 8192)]
