"""The trace reduction (chipbench/devtrace.py): on hand-made events with
known answers, and on a small trace recorded on the chip."""
import os

import pytest

import _paths  # noqa: F401
from chipbench import devtrace, work
from chipbench.harness import StepLog
from chipbench.model import Model

FIXTURE = os.path.join(_paths.HERE, "data", "v5e_phi4_chat_trace.json.gz")
PHI4 = Model(n_layers=32, d_model=3072, n_heads=24, n_kv_heads=8,
             head_dim=128, d_ff=8192, vocab=200064, rope_theta=1e4,
             rope_fraction=1.0, norm_eps=1e-6)
PEAKS = work.peaks_for("TPU v5 lite")


def _op(t0, t1, name, tf_op="jit(run)/x", cat="loop fusion"):
    return (float(t0), float(t1), name, tf_op, cat)


def test_intervals():
    u = devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert devtrace.length(u) == 7
    assert devtrace.overlap(u, [(2, 6)]) == 2
    assert devtrace.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_reduce_hand_made():
    """One step of 100 us: a decode program [10, 70) whose scan (a while
    op spanning its body) holds a GEMM kernel [20, 40) and the paged
    decode kernel [40, 60); then 30 us idle while the host fetches."""
    ops = [_op(10, 70, "while.1", cat="while"),
           _op(20, 40, "matmul.3", "jit(run)/pallas_call:", "custom-call"),
           _op(40, 60, "paged_decode_partials.2", "jit(run)/pallas_call:",
               "custom-call"),
           _op(60, 70, "fusion.9")]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [(10.0, 70.0, "jit_run(1)")]}},
        "host": [("bench.submit", 0.0, 2.0, None),
                 ("bench.step", 2.0, 102.0, 0),
                 ("bench.wait", 102.0, 110.0, None)]}
    steps = [StepLog(start=0, end=0, decode=[100] * 4)]
    got = devtrace.reduce(trace, steps, PHI4, PEAKS, chips=1)
    assert got["window_s"] == pytest.approx(110e-6)
    assert got["busy_s"] == pytest.approx(60e-6)
    assert got["idle_share"] == pytest.approx(100 * (1 - 60 / 100))
    assert got["program_s"] == {"decode": pytest.approx(60e-6)}
    flops = work.decode_flops(PHI4, [100] * 4)
    assert got["decode_mfu"] == pytest.approx(
        100 * flops / (60e-6 * PEAKS.bf16_flops))
    bound = work.bound_s(work.gemm_flops(PHI4, 4) + work.head_flops(PHI4, 4),
                         work.gemm_bytes(PHI4, 4) + work.head_bytes(PHI4, 4),
                         PEAKS)
    assert got["gemm_roofline"] == pytest.approx(100 * bound / 20e-6)
    assert got["chunk_attn_roofline"] is None
    assert got["prefill_mfu"] is None
    names = [n for n, _ in got["device_ops"]]
    assert not any(n.startswith("while") for n in names)
    assert names[0] in ("matmul pallas_call",
                        "paged_decode_partials pallas_call")
    # idle: [0, 10) in bench.submit/step, [70, 110) mostly the step's end
    gaps = dict((round(s * 1e6), n) for n, s in got["idle_gaps"])
    assert gaps[40] == "bench.step" and gaps[10] == "bench.step"


def test_reduce_recorded_chip_trace():
    """Four engine steps of phi4-mini-3.8b.chat on a v5e (a prefill and
    decode steps), cut from a traced run: the reduction agrees with a
    microsecond grid laid over the raw events."""
    import gzip
    import json

    import numpy as np
    trace = devtrace.load(FIXTURE)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert [h[3] for h in trace["host"] if h[0] == "bench.step"] == \
        [0, 1, 2, 3]
    steps = [StepLog(start=0, end=0, decode=[300] * 24) for _ in range(4)]
    steps[0].prefill = {256: [(0, 200)]}
    got = devtrace.reduce(trace, steps, PHI4, PEAKS, chips=1)

    with gzip.open(FIXTURE, "rt") as f:
        raw = json.load(f)["traceEvents"]
    host = [e for e in raw if e.get("name", "").startswith("bench.")]
    lo = min(e["ts"] for e in host)
    hi = max(e["ts"] + e["dur"] for e in host)
    grid = np.zeros(int(hi - lo) + 1, bool)
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in raw
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    for e in raw:
        if e.get("ph") == "X" and \
                threads.get((e["pid"], e["tid"])) == "XLA Ops":
            a = max(int(round(e["ts"] - lo)), 0)
            b = min(int(round(e["ts"] + e["dur"] - lo)), len(grid))
            grid[a:b] = True
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-6)
    assert got["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=2e-3)
    assert 0 < got["busy_s"] < got["window_s"]
    assert set(got["program_s"]) >= {"decode", "prefill"}
    assert 0 < got["gemm_roofline"] <= 100
    assert 0 < got["decode_mfu"] <= 100 and 0 < got["prefill_mfu"] <= 100
    assert got["chunk_attn_roofline"] is None
    labels = [n for n, _ in got["device_ops"]]
    assert "paged_decode_partials pallas_call" in labels
    assert len(labels) == devtrace.TOP
    assert all(n == "bench.step" for n, _ in got["idle_gaps"])
