"""The engine's stage spans in a traced run (chipbench/enginespans.py): on
hand-made events with known answers, on a program without them, and on a
small trace recorded on the chip."""
import gzip
import os
import shutil
import types

import pytest

import _paths  # noqa: F401
from chipbench import enginespans, registry, work
from chipbench.model import Model

PHI4 = Model(n_layers=32, d_model=3072, n_heads=24, n_kv_heads=8,
             head_dim=128, d_ff=8192, vocab=200064, rope_theta=1e4,
             rope_fraction=0.75, norm_eps=1e-6)
PEAKS = work.peaks_for("TPU v5 lite")
OLD_FIXTURE = os.path.join(_paths.HERE, "data", "v5e_phi4_chat_trace.json.gz")
FIXTURE = os.path.join(_paths.HERE, "data",
                       "v5e_phi4_chat_engine_trace.json.gz")


def _op(t0, t1, name, cat="custom-call"):
    return (float(t0), float(t1), name, "jit(run)/pallas_call:", cat)


def _trace(ops, host, engine):
    return {"devices": {"/device:TPU:0": {"ops": sorted(ops),
                                          "modules": []}},
            "host": host, "engine": sorted(engine,
                                           key=lambda s: (s[1], -s[2]))}


def test_decode_attn_roofline_hand_made():
    """Two steps in the window attend 1000 and 3000 positions over 4 slots
    each; the kernel runs 20 + 30 us in the window.  A dispatch and a
    kernel run past the window, and a GEMM, are left out."""
    host = [("bench.step", 0.0, 100.0, 0), ("bench.step", 100.0, 200.0, 1)]
    engine = [("engine.decode.dispatch", 10.0, 12.0,
               {"kv_positions": 1000.0, "slots": 4.0}),
              ("engine.decode.dispatch", 110.0, 112.0,
               {"kv_positions": 3000.0, "slots": 4.0}),
              ("engine.decode.dispatch", 250.0, 252.0,
               {"kv_positions": 9e9, "slots": 4.0})]
    ops = [_op(20, 40, "paged_decode_partials.1"),
           _op(120, 150, "paged_decode_partials.2"),
           _op(150, 190, "matmul.3"),
           _op(260, 270, "paged_decode_partials.1")]
    got = enginespans.decode_attn_roofline(_trace(ops, host, engine), PHI4,
                                           PEAKS)
    nbytes = 2 * (2 * 4000 * 8 * 128 + 2 * 8 * 24 * 128) * 32
    flops = 4 * 4000 * 24 * 128 * 32
    bound = max(nbytes / PEAKS.hbm_bytes, flops / PEAKS.bf16_flops)
    assert bound == nbytes / PEAKS.hbm_bytes          # bandwidth-bound
    assert got == pytest.approx(100 * bound / 50e-6)
    assert enginespans.decode_attn_roofline(
        _trace(ops, host, []), PHI4, PEAKS) is None
    assert enginespans.decode_attn_roofline(
        _trace(ops[2:3], host, engine), PHI4, PEAKS) is None


def _stage_trace():
    """One step of 100 us: the chip is busy over [10, 30) and [60, 80)."""
    host = [("bench.step", 0.0, 100.0, 0)]
    ops = [_op(10, 30, "fusion.1", "loop fusion"),
           _op(60, 80, "fusion.2", "loop fusion")]
    engine = [("engine.step", 1.0, 99.0, {}),
              ("engine.admit", 2.0, 40.0, {}),
              ("engine.prefill", 5.0, 35.0, {}),
              ("engine.prefill.fetch", 28.0, 35.0, {}),
              ("engine.decode.dispatch", 45.0, 50.0, {}),
              ("engine.decode.fetch", 50.0, 85.0, {}),
              ("engine.retire", 90.0, 95.0, {})]
    return _trace(ops, host, engine)


def test_idle_by_stage_hand_made():
    """60 us idle; each piece goes to the innermost span covering it, and
    the 2 us outside engine.step go to engine.step, uncovered."""
    got = enginespans.idle_by_stage(_stage_trace())
    assert got["idle_s"] == pytest.approx(60e-6)
    assert got["covered_s"] == pytest.approx(58e-6)
    want = {"engine.step": 17, "engine.admit": 8, "engine.prefill": 5,
            "engine.prefill.fetch": 5, "engine.decode.dispatch": 5,
            "engine.decode.fetch": 15, "engine.retire": 5}
    assert got["stages"] == pytest.approx({k: v * 1e-6
                                           for k, v in want.items()})


def test_host_time_hand_made():
    got = enginespans.host_time(_stage_trace())
    assert got["steps"] == 1
    assert got["step_s"] == pytest.approx(98e-6)
    assert got["host_s"] == pytest.approx((98 - 7 - 35) * 1e-6)


def test_host_ms_per_step_reads_the_counters():
    stats = types.SimpleNamespace(engine_steps=4, host_s=0.02)
    assert enginespans.host_ms_per_step(stats) == pytest.approx(5.0)
    stats.engine_steps = 0
    assert enginespans.host_ms_per_step(stats) is None


def test_readers_find_nothing_in_a_program_without_the_spans(
        tmp_path, monkeypatch):
    """A program without the counters or the spans (as before them) gives
    every new metric None, with no trace, and with a chip trace that
    holds only the harness's spans."""
    monkeypatch.setattr(enginespans, "TRACE_DIR", str(tmp_path))
    run = types.SimpleNamespace(stats=types.SimpleNamespace(), window={},
                                model=PHI4)
    names = [m["name"] for m in registry.load(_paths.ROOT)["per_layer"]
             if m["name"].startswith(("host_ms_per_step.",
                                      "decode_attn_roofline."))]
    assert len(names) == 6
    readers = [registry.reader(_paths.BENCH, n) for n in names]
    assert [r(run) for r in readers] == [None] * 6
    dest = tmp_path / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(OLD_FIXTURE, dest / "host.trace.json.gz")
    with gzip.open(OLD_FIXTURE, "rt") as f:
        assert "engine." not in f.read()
    assert [r(run) for r in readers] == [None] * 6


def test_recorded_chip_trace_with_engine_spans():
    """Four engine steps of phi4-mini-3.8b.chat on a v5e (one with a
    prefill), cut from a traced run: the engine spans cover at least 90%
    of the chip's idle time inside bench.step, and the decode kernel's
    roofline share reads inside (0, 100]."""
    trace = enginespans.load(FIXTURE)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert [h[3] for h in trace["host"] if h[0] == "bench.step"] == \
        [0, 1, 2, 3]
    steps = [s for s in trace["engine"] if s[0] == "engine.step"]
    assert len(steps) == 4
    assert sum(s[0] == "engine.prefill" for s in trace["engine"]) >= 1
    idle = enginespans.idle_by_stage(trace)
    assert idle["idle_s"] > 0
    assert idle["covered_s"] >= 0.9 * idle["idle_s"]
    assert sum(idle["stages"].values()) == pytest.approx(idle["idle_s"])
    got = enginespans.decode_attn_roofline(trace, PHI4, PEAKS)
    assert 0 < got <= 100
    host = enginespans.host_time(trace)
    assert host["steps"] == 4 and 0 < host["host_s"] < host["step_s"]
