"""The benchmark's harness driven on the CPU at 7 validators.

As in tests/test_chip_smoke.py the device lane is a host judge: a real
lane on XLA-CPU cold-compiles minutes per program shape.  What is pinned
here is the yardstick itself: the reference's arithmetic, the shape of
the last line, that a run without a chip is refused as a measurement,
that `failed` and `correct` notice a run that left the device or a timed
path that is broken underneath, and that a configuration, a cell and a
metric are added as files with no edit to an existing one.
"""

import json
import os
import random
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, programs, reference, work, xplane  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

from cometbft_tpu.crypto import batch as cb  # noqa: E402
from cometbft_tpu.crypto import dispatch  # noqa: E402
from cometbft_tpu.crypto import ed25519 as ed  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

TINY = {"name": "tiny7", "validators": 7, "power": 10, "tx_bytes": 64,
        "txs_per_block": 3, "chain_blocks": 16, "chain_id": "tiny-chain"}


def _judge(triples):
    verdicts = [ed.PubKey(bytes(pk)).verify_signature(m, s)
                for pk, m, s in triples]
    return all(verdicts) and bool(verdicts), verdicts


def host_judge_window(self, win, device=None):
    """Stands in for VerifyPipeline._device_dispatch, with the device
    path's dispatch accounting."""
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    with compile_hook.dispatch_scope(
            "ed25519_rlc_cached", (8, dev.pad_width(len(win.items)))):
        pass
    return _judge((dispatch._pk_bytes(pk), m, s) for pk, m, s in win.items)


def host_judge_batch(self):
    """Stands in for TpuEd25519BatchVerifier._verify_items, with the
    device path's dispatch accounting."""
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    n = dev.pad_width(len(self._items))
    with compile_hook.dispatch_scope("ed25519_rlc", (n, n)):
        pass
    return _judge(self._items)


@pytest.fixture
def stub_device(monkeypatch):
    """Host judges in place of the device, thresholds low enough that 7
    validators reach the device lane, and no device program to build."""
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD", 4)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 2)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        host_judge_window)
    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                        host_judge_batch)
    monkeypatch.setattr(programs, "expected_programs", lambda n, w: [])


@pytest.fixture
def tiny(tmp_path):
    """A manifest in a temporary directory whose one cell is the tiny
    configuration under the real `catchup` mix and the real metrics."""
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (tmp_path / "tiny7.json").write_text(json.dumps(TINY))
    manifest = dict(real)
    manifest["configs"] = [{"name": "tiny7", "source": "test",
                            "file": "tiny7.json", "reduced": [],
                            "why": "test"}]
    manifest["workloads"] = [{"name": "tiny7.catchup", "config": "tiny7",
                              "traffic": "catchup", "chips": 1,
                              "why": "test"}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def _run(manifest, trace=False, workload="tiny7.catchup", seed=2 ** 31 + 5):
    import time

    rc, result = harness.run_cell(workload, seed, 0.2, trace,
                                  time.perf_counter(), check_chip=False,
                                  manifest_path=manifest)
    assert rc == 0
    return result


# -- the reference's arithmetic ------------------------------------------------

@pytest.mark.parametrize("round_,nanos", [(0, 0), (3, 0), (0, 123456789),
                                          (7, 999999999)])
def test_reference_sign_bytes_equal_the_programs(round_, nanos):
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.timestamp import Timestamp

    rng = random.Random(round_ * 1000 + nanos)
    bh, ph = rng.randbytes(32), rng.randbytes(32)
    height, total, secs = rng.randrange(1, 1 << 40), rng.randrange(1, 99), \
        1_700_000_000 + rng.randrange(10 ** 6)
    want = canonical.vote_sign_bytes(
        "chain-x", canonical.PRECOMMIT, height, round_,
        BlockID(bh, PartSetHeader(total, ph)), Timestamp(secs, nanos))
    assert reference.vote_sign_bytes("chain-x", height, round_, bh, total,
                                     ph, secs, nanos) == want


def test_reference_orders_validators_as_the_program():
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet

    rng = random.Random(7)
    keys = [ed.PrivKey.generate(rng.randbytes(32)).pub_key()
            for _ in range(9)]
    # equal powers, as every configuration here: the program orders a
    # set by address alone, upstream by power first (PERF.md section 7)
    powers = [10] * 9
    vs = ValidatorSet([Validator(k, p) for k, p in zip(keys, powers)])
    order = reference.validator_order([k.bytes() for k in keys], powers)
    assert [keys[i].bytes() for i in order] == [
        v.pub_key.bytes() for v in vs.validators]


def test_reference_verifier_rejects_a_flipped_bit():
    k = ed.PrivKey.generate(b"\x05" * 32)
    sig = k.sign(b"msg")
    assert reference.verify(k.pub_key().bytes(), b"msg", sig)
    bad = bytes([sig[0] ^ 1]) + sig[1:]
    assert not reference.verify(k.pub_key().bytes(), b"msg", bad)
    assert not reference.verify(k.pub_key().bytes(), b"msh", sig)


def test_verify_bytes_counts_what_the_protocol_hands_over():
    # 96 bytes a signature (key, R, S) plus the sign-bytes hashed
    assert work.verify_bytes(1, 0) == 96
    assert work.verify_bytes(3744, 110) == 3744 * 206
    assert work.verify_bytes(53336, 117) == 53336 * 213


# -- the reduction from a trace -----------------------------------------------------

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 14000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_rlc_verify_kernel_cached_a(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_rlc_verify_kernel(456)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_sha256_blocks(789)" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.1" } }
  event_metadata { key: 5 value { id: 5 name: "custom-call.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "benchmark_mark" } }
}
"""


def test_xplane_reduction_busy_idle_kernels_and_breakdown(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    trace = xplane.load(str(path))
    assert trace["mark_ns"] == 1000
    # the trace's ns 1000 is perf_counter 100.0; the programs cover
    # [0,4] u [10,12] u [14,15] us after it, and the profiler stopped
    # at 17 us: gaps [4,10], [12,14] and [15,17]
    spans = [("blocksync.apply", 100.0000035, 100.0000105),
             ("blocksync.decode", 100.0000119, 100.0000130),
             ("blocksync.store", 100.0000150, 100.0000171)]
    red = xplane.reduce(trace, spans, mark_perf=100.0,
                        stop_perf=100.000017)
    assert red["n_device_planes"] == 1 and red["n_op_events"] == 4
    assert red["busy_s"] == pytest.approx(7e-6)
    assert red["window_s"] == pytest.approx(17e-6)
    assert red["range_perf"] == pytest.approx((100.0, 100.000017))
    assert red["kinds"] == {
        "ed25519_rlc_cached": {"seconds": pytest.approx(4e-6), "count": 1},
        "ed25519_rlc": {"seconds": pytest.approx(2e-6), "count": 1}}
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(5e-6)
    assert ops["custom-call.2"] == pytest.approx(3e-6)
    # the known programs' runs on the host's clock, in the device's order
    assert [(k, w) for _, k, _, w in red["programs"]] == [
        ("ed25519_rlc_cached", True), ("ed25519_rlc", True)]
    assert red["programs"][1][0] == pytest.approx(100.00001)
    assert red["programs"][1][2] == pytest.approx(2e-6)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["blocksync.apply"] == pytest.approx(6e-6)
    assert gaps["blocksync.decode"] == pytest.approx(2e-6)
    assert gaps["blocksync.store"] == pytest.approx(2e-6)
    # with no operations in the trace the programs are listed
    for dev in trace["devices"].values():
        dev["op_seconds"] = {}
    red = xplane.reduce(trace, spans, mark_perf=100.0)
    assert dict(red["breakdown"]["device_ops"])[
        "jit_rlc_verify_kernel_cached_a"] == pytest.approx(4e-6)


def test_kernel_time_is_weighed_by_the_windows_mix_of_dispatches():
    # a slice caught one verify window (4 ms) and three remainders, the
    # first cut by the slice's edge; the window made 6 and 191 of them
    programs = [[10.000, "ed25519_rlc_cached", 0.010, False],
                [10.020, "ed25519_rlc_cached", 0.004, True],
                [10.100, "ed25519_rlc_cached", 0.022, True],
                [10.200, "ed25519_rlc_cached", 0.024, True]]
    calls = [(9.5, "ed25519_rlc_cached", 64), (9.9, "ed25519_a_tables", 64),
             (9.98, "ed25519_rlc_cached", 64),
             (10.019, "ed25519_rlc_cached", 4096),
             (10.09, "ed25519_rlc_cached", 64),
             (10.19, "ed25519_rlc_cached", 64),
             (10.6, "ed25519_rlc_cached", 64)]
    classes = work.match_dispatches(programs, calls)
    assert classes == {4096: {"seconds": pytest.approx(0.004), "count": 1},
                       64: {"seconds": pytest.approx(0.046), "count": 2}}
    profile = {"classes": {4096: {**classes[4096], "sigs": 3744},
                           64: {**classes[64], "sigs": 58}}}
    secs, sigs = work.kernel_mix(profile, {4096: 6, 64: 191})
    assert secs == pytest.approx(6 * 0.004 + 191 * 0.023)
    assert sigs == 6 * 3744 + 191 * 58
    # a class the slice did not catch adds its signatures and no time
    profile["classes"][4096] = {"seconds": 0.0, "count": 0, "sigs": 3744}
    secs, sigs = work.kernel_mix(profile, {4096: 6, 64: 191})
    assert secs == pytest.approx(191 * 0.023)
    assert sigs == 6 * 3744 + 191 * 58
    # a program the host saw no dispatch of: nothing is matched
    assert work.match_dispatches(
        [[10.0, "ed25519_rlc", 0.01, True]], calls) is None
    assert work.kernel_mix({}, {64: 3}) is None


def test_xplane_short_names_of_operations():
    line = ("%while.6780 = (s32[]{:T(128)}, s32[4,20,64]{2,1,0}) "
            "while((s32[]{:T(128)}) %tuple.3), condition=%c, body=%b")
    assert xplane._short(line) == "%while.6780"
    assert xplane._short("fusion.1") == "fusion.1"


def test_a_reader_with_nothing_to_read_returns_nothing():
    from benchmark import readers

    run = harness.Run("w", {}, {}, 1, 1.0, {})
    for name in ("device_idle_pct.sync", "verify_us_per_sig.sync",
                 "verify_roofline.sync", "decode_ms_per_block.sync",
                 "dispatches_per_block.sync"):
        assert readers.read_metric(name, run) is None


# -- a whole run, with everything but the chip --------------------------------------------

def test_last_line_on_a_cpu_run_names_the_cpu(stub_device, tiny, capsys):
    result = _run(tiny)
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 15
    assert set(result["metrics"]) == {"sync_blocks_per_s", "setup_s"}
    assert result["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert all(v["limit"] == 0 for v in result["compared"].values())
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1])["correct"] is True
    assert err.splitlines()[-1].startswith("compared ")


def test_traced_run_reports_the_span_and_counter_metrics(stub_device, tiny,
                                                         monkeypatch):
    # the CPU has no device plane: the trace-borne metrics are left out,
    # the span- and counter-borne ones are there
    monkeypatch.setattr(harness.Profile, "start", lambda self: setattr(
        self, "t0", 0.0))
    monkeypatch.setattr(harness.Profile, "stop", lambda self: setattr(
        self, "t1", 0.0))
    monkeypatch.setitem(TINY, "profile_seconds", 0.05)
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)
    result = _run(tiny, trace=True)
    got = result["metrics"]
    for name in ("apply_ms_per_block.sync", "store_ms_per_block.sync",
                 "decode_ms_per_block.sync", "collect_ms_per_block.sync",
                 "dispatches_per_block.sync", "fixture_s.setup",
                 "trace_lower_s.setup", "import_s.setup"):
        assert name in got, name
    assert got["compiles_in_window.sync"]["value"] == 0
    assert "device_idle_pct.sync" not in got
    assert "busy_s" not in result["device"]


def test_run_py_refuses_to_measure_without_a_chip(capsys):
    rc = bench_run.main(["--workload", "qa175.catchup", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc == harness.EXIT_NO_CHIP
    out = capsys.readouterr().out
    assert '"correct"' not in out and '"metrics"' not in out


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "qa175.catchup",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode not in (0, None)
    assert '"correct"' not in p.stdout


# -- the timed path broken underneath: `correct` comes out false ---------------------------

def test_window_forced_off_the_device_raises_failed(stub_device, tiny,
                                                    monkeypatch):
    def boom(self, win, device=None):
        raise RuntimeError("injected dispatch fault")

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch", boom)
    result = _run(tiny)
    assert result["failed"] > 0 and result["correct"] is False
    assert result["compared"]["blocks_off_device"]["value"] > 0
    # the host drained the windows: what is stored is still right
    assert result["compared"]["blocks_hash_differs"]["value"] == 0


def test_control_host_provider_comes_out_not_correct(stub_device, tiny,
                                                     monkeypatch):
    # the control: the program with its own host path switched on - the
    # step that would tempt a later PR - verifies everything and stores
    # the right chain, and is refused because the chip did not do it
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD",
                        10 ** 9)
    result = _run(tiny)
    assert result["correct"] is False
    assert result["compared"]["blocks_off_device"]["value"] > 0
    assert result["compared"]["sigs_ref_rejected"]["value"] == 0


def test_one_remainder_batch_left_to_the_host_is_not_correct(stub_device,
                                                             tiny,
                                                             monkeypatch):
    # a partial fallback: one apply-time remainder batch of the window's
    # pass is verified by the host loop.  Every signature has a verdict
    # and the chain is right; only a dispatch is missing
    state = {"n": 0}

    def sometimes_host(self):
        state["n"] += 1
        if state["n"] == 15 + 7:            # past the warm-up pass's 15
            return cb.CpuEd25519BatchVerifier._verify_items(self)
        return host_judge_batch(self)

    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                        sometimes_host)
    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_check",
                        cb.CpuEd25519BatchVerifier._check, raising=False)
    result = _run(tiny)
    assert state["n"] >= 30
    c = result["compared"]
    assert result["correct"] is False and result["failed"] == 1
    assert c["sigs_off_device"]["value"] == 2       # 7 - 5 signers
    assert c["blocks_off_device"]["value"] == 1
    assert all(v["value"] == 0 for k, v in c.items()
               if k not in ("sigs_off_device", "blocks_off_device"))


@pytest.fixture
def short_timeout(monkeypatch):
    real = harness.load_json

    def load(path):
        d = real(path)
        if path.endswith(os.path.join("traffic", "catchup.json")):
            d["pass_timeout_s"] = 3
        return d

    monkeypatch.setattr(harness, "load_json", load)


def test_fault_state_returned_unchanged(stub_device, tiny, short_timeout,
                                        monkeypatch):
    from cometbft_tpu.state.execution import BlockExecutor

    real = BlockExecutor.apply_verified_block
    calls = {"n": 0}

    def unchanged(self, state, block_id, block, **kw):
        calls["n"] += 1
        # the source applies through apply_block; only syncing nodes
        # come here.  From the window's first pass on, return the state
        # as it came
        if calls["n"] > 16:
            return state
        return real(self, state, block_id, block, **kw)

    monkeypatch.setattr(BlockExecutor, "apply_verified_block", unchanged)
    result = _run(tiny)
    assert result["correct"] is False
    assert result["compared"]["blocks_missing"]["value"] > 0
    assert result["failed"] > 0


def test_fault_half_of_the_blocks_left_out(stub_device, tiny, short_timeout,
                                           monkeypatch):
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor

    real = BlocksyncReactor._apply_window
    state = {"windows": 0}

    def half(self, blocks, window, parts_ids, commits, verified):
        state["windows"] += 1
        if state["windows"] == 1:           # the warm-up pass's window
            return real(self, blocks, window, parts_ids, commits, verified)
        # half of the verified window is applied, the rest left out
        out = real(self, blocks, window, parts_ids, commits,
                   max(1, verified // 2))
        self._stop_sync.set()
        return out

    monkeypatch.setattr(BlocksyncReactor, "_apply_window", half)
    result = _run(tiny)
    assert result["correct"] is False
    assert result["compared"]["blocks_missing"]["value"] > 0
    assert result["failed"] > 0


def test_fault_an_answer_altered_where_it_is_produced(stub_device, tiny,
                                                      short_timeout,
                                                      monkeypatch):
    state = {"windows": 0}

    def lying(self, win, device=None):
        ok, verdicts = host_judge_window(self, win, device)
        state["windows"] += 1
        if state["windows"] > 3:            # past the warm-up pass
            verdicts = [False] + list(verdicts[1:])
            ok = False
        return ok, verdicts

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch", lying)
    result = _run(tiny)
    assert result["correct"] is False
    assert result["compared"]["blocks_missing"]["value"] > 0


# -- the executable store ----------------------------------------------------------------------

def test_store_key_follows_sources_and_switches(monkeypatch, tmp_path):
    pkg = tmp_path / "cometbft_tpu"
    (pkg / "ops" / "sub").mkdir(parents=True)
    (pkg / "crypto").mkdir()
    (pkg / "ops" / "ed25519.py").write_text("A = 1\n")
    (pkg / "ops" / "sub" / "k.py").write_text("B = 1\n")
    (pkg / "crypto" / "ed25519_ref.py").write_text("C = 1\n")
    monkeypatch.setattr(programs, "REPO", str(tmp_path))
    for k in list(os.environ):
        if k.startswith("COMETBFT_TPU_PALLAS"):
            monkeypatch.delenv(k)
    base = programs.store_key("TPU v5 lite")
    assert programs.store_key("TPU v5 lite") == base
    assert programs.store_key("TPU v6 lite") != base
    # a module ops/ imports, a sub-package of ops/, a kernel switch
    (pkg / "crypto" / "ed25519_ref.py").write_text("C = 2\n")
    k1 = programs.store_key("TPU v5 lite")
    (pkg / "ops" / "sub" / "k.py").write_text("B = 2\n")
    k2 = programs.store_key("TPU v5 lite")
    monkeypatch.setenv("COMETBFT_TPU_PALLAS_MSM", "0")
    k3 = programs.store_key("TPU v5 lite")
    assert len({base, k1, k2, k3}) == 4
    # what the driver sets for its own use changes nothing
    monkeypatch.setenv("BENCH_RUN", "7")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert programs.store_key("TPU v5 lite") == k3


def test_build_ahead_hint_matches_what_the_program_hands_over(monkeypatch):
    # the hint (programs._arg_shapes) against the arguments the program's
    # own packers and A-table cache hand its three jitted functions
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519 as dev

    keys = [ed.PrivKey.generate(bytes([i + 1]) * 32) for i in range(5)]
    msgs = [b"m%d" % i for i in range(12)]
    pks = [keys[i % 5].pub_key().bytes() for i in range(12)]
    sigs = [keys[i % 5].sign(m) for i, m in enumerate(msgs)]
    packed = ed.pack_rlc(pks, msgs, sigs)
    k, n = packed[0].shape[-1], packed[1].shape[-1]
    assert (k, n) == (dev.pad_width(5 + 1), dev.pad_width(12))
    tab, _ = jax.eval_shape(dev._msm_tables,
                            jax.ShapeDtypeStruct((8, k), jnp.uint32))
    seen = {}

    def recorder(kind, out):
        def fn(*args):
            seen[kind] = programs.signature(args)
            return out()
        return fn

    for kind, out in (
            ("ed25519_rlc", lambda: jnp.bool_(True)),
            ("ed25519_a_tables", lambda: (jnp.zeros(tab.shape, tab.dtype),
                                          jnp.bool_(True))),
            ("ed25519_rlc_cached", lambda: jnp.bool_(True))):
        monkeypatch.setattr(dev, programs.JITTED[kind], recorder(kind, out))
    monkeypatch.setattr(ed, "_A_TABLE_CACHE", ed.ATableCache())
    assert ed.rlc_verify(packed, use_cache=False)
    assert ed.rlc_verify(packed, use_cache=True)
    assert set(seen) == set(programs.JITTED)
    for kind, sig in seen.items():
        dims = (k,) if kind == "ed25519_a_tables" else (k, n)
        assert sig == tuple((tuple(shape), t) for shape, t in
                            programs._arg_shapes((kind, *dims))), kind


def test_dispatcher_learns_in_setup_loads_later_and_stands_aside(
        monkeypatch, tmp_path):
    import jax
    import numpy as np

    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    traced = {"n": 0}

    def toy(a, b):
        traced["n"] += 1
        return a * 2 + b

    monkeypatch.setattr(dev, "_toy_jitted", jax.jit(toy), raising=False)
    monkeypatch.setattr(programs, "JITTED", {"toy": "_toy_jitted",
                                             "gone": "_no_such_jitted"})
    store = str(tmp_path / "store")
    a, b = np.arange(6, dtype=np.int32).reshape(2, 3), np.int32(5)
    try:
        # a first run: nothing kept, no hint; the set-up's call learns
        rec = programs.ensure([], store, workers=1)
        assert rec["loaded"] == 0 and rec["absent"] == ["gone"]
        d = rec["dispatchers"]["toy"]
        assert np.array_equal(dev._toy_jitted(a, b), a * 2 + 5)
        assert d.misses == 0 and len(d.learned) == 1
        learned = programs.stop_learning(rec["dispatchers"])
        assert learned["learned"] == [["toy", [[2, 3], []]]]
        # in the window another shape is the program's own business
        assert np.array_equal(dev._toy_jitted(a[:1], b), a[:1] * 2 + 5)
        assert d.misses == 1 and len(d.learned) == 1
        assert [sig for _, sig in d.calls] == [
            (((2, 3), "int32"), ((), "int32")),
            (((1, 3), "int32"), ((), "int32"))]
        programs.uninstall()
        assert not isinstance(dev._toy_jitted, programs._Dispatcher)
        # a later run loads it and traces nothing
        traced["n"] = 0
        rec = programs.ensure([], store, workers=1)
        assert rec["loaded"] == 1
        programs.stop_learning(rec["dispatchers"])
        assert np.array_equal(dev._toy_jitted(a, b), a * 2 + 5)
        assert traced["n"] == 0 and rec["dispatchers"]["toy"].misses == 0
        programs.uninstall()
        # the program keeps its executables itself: the store stands aside
        monkeypatch.setattr(compile_hook, "KEEPS_EXECUTABLES", True,
                            raising=False)
        rec = programs.ensure([], store, workers=1)
        d = rec["dispatchers"]["toy"]
        assert rec["aside"] and rec["loaded"] == 0 and not d.table
        assert np.array_equal(dev._toy_jitted(a, b), a * 2 + 5)
        assert d.misses == 1 and not d.learned and len(d.calls) == 1
    finally:
        programs.uninstall()


# -- driven by data -------------------------------------------------------------------------------

def test_config_cell_and_metric_are_added_as_files(stub_device, tmp_path):
    """A configuration, a cell and two per-layer metrics (one a .json
    that names a general reader, one a .py) dropped beside the real
    ones are found by name and run, with no edit to an existing file."""
    bdir = os.path.join(REPO, "benchmark")
    added = {
        os.path.join(bdir, "configs", "zz_tmp9.json"): json.dumps(
            {**TINY, "name": "zz_tmp9", "chain_blocks": 8}),
        os.path.join(bdir, "traffic", "zz_tmpmix.json"): json.dumps(
            {"mode": "catchup", "rate_metric": "sync_blocks_per_s",
             "pass_timeout_s": 30}),
        os.path.join(bdir, "metrics", "zz_tmp_store.sync.json"): json.dumps(
            {"reader": "span_ms_per_unit", "span": "blocksync.store"}),
        os.path.join(bdir, "metrics", "zz_tmp_passes.sync.py"):
            "def read(run):\n    return run.counters['passes']\n",
    }
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bdir) for p in fs
              if "__pycache__" not in dp and not p.startswith(".")}
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    manifest["configs"].append(
        {"name": "zz_tmp9", "source": "test",
         "file": "benchmark/configs/zz_tmp9.json", "reduced": [],
         "why": "test"})
    manifest["workloads"].append(
        {"name": "zz_tmp9.zz_tmpmix", "config": "zz_tmp9",
         "traffic": "zz_tmpmix", "chips": 1, "why": "test"})
    for name in ("zz_tmp_store.sync", "zz_tmp_passes.sync"):
        manifest["per_layer"].append(
            {"name": name, "unit": "x", "better": "lower",
             "source": "program_span", "layer": "store",
             "moves": "sync_blocks_per_s",
             "workloads": ["zz_tmp9.zz_tmpmix"]})
    # the manifest's files resolve from its own directory
    for p in ("benchmark",):
        os.symlink(os.path.join(REPO, p), tmp_path / p)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    try:
        for p, text in added.items():
            with open(p, "w") as f:
                f.write(text)
        result = _run(str(path), trace=True, workload="zz_tmp9.zz_tmpmix")
    finally:
        for p in added:
            if os.path.exists(p):
                os.remove(p)
    assert result["correct"] is True
    assert result["metrics"]["zz_tmp_store.sync"]["value"] > 0
    assert result["metrics"]["zz_tmp_passes.sync"]["value"] >= 1
    assert "apply_ms_per_block.sync" in result["metrics"]
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(bdir) for p in fs
             if "__pycache__" not in dp and not p.startswith(".")}
    assert after == before


def test_manifest_keeps_to_the_contract():
    import re

    m = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    e2e = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in e2e
    for e in m["end_to_end"]:
        assert name.match(e["name"]) and 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    for e in m["per_layer"]:
        assert name.match(e["name"]) and e["moves"] in e2e
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", e["name"] + ".json")) or \
            os.path.exists(os.path.join(
                REPO, "benchmark", "metrics", e["name"] + ".py"))
    for c in m["configs"]:
        cfg = harness.load_json(os.path.join(REPO, c["file"]))
        assert all(k in cfg for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in m["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        harness.load_cell(w["name"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
