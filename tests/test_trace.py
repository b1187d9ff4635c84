"""Observability layer: request-level tracing + MFU/MBU attribution.

Covers serving/trace.py end to end through the engine: span nesting and
ordering under the overlapped host loop, ring-buffer eviction, Chrome
trace-event schema validity (Perfetto-loadable), token identity with
tracing on vs off across chunked / prefix-warm / speculative traffic,
TTFT reconstruction from the trace alone, the per-phase MFU/MBU
derivation (stats.phase_util vs trace.derive_phase_metrics agreeing),
the Reservoir sampling satellite, and the Prometheus text snapshot.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import PEAKS
from repro.configs import get_config
from repro.core.precision import FP32
from repro.models import lm
from repro.serving import (ChunkedPrefillPolicy, DeadlinePolicy,
                           InferenceEngine, Request, Reservoir,
                           SamplingParams, SpecConfig, Tracer,
                           derive_phase_metrics, make_policy, percentile,
                           prometheus_text, spec_support_reason,
                           validate_chrome_trace)
from repro.serving.stats import EngineStats
from repro.serving.trace import PID_ENGINE, PID_REQUEST


@pytest.fixture(scope="module")
def model():
    cfg = get_config("phi4-mini-3.8b").reduced()
    params = lm.init_lm(jax.random.key(0), cfg, jnp.float32)
    return cfg, params


def _prompts(cfg, lengths, seed=11):
    """lengths entries may be ints (drawn here) or ready-made prompts."""
    rng = np.random.default_rng(seed)
    return [n if isinstance(n, np.ndarray)
            else rng.integers(0, cfg.vocab, n, dtype=np.int32)
            for n in lengths]


def _run(cfg, params, *, tracer=None, overlap=False, scheduler=None,
         prefix_cache=False, spec=None, lengths=(6, 13, 20), max_new=4,
         uid0=0, deadline_ms=0.0):
    engine = InferenceEngine(
        cfg, params, batch_size=2, max_seq=64, policy=FP32,
        overlap=overlap, scheduler=scheduler, prefix_cache=prefix_cache,
        spec=spec, tracer=tracer)
    for i, p in enumerate(_prompts(cfg, lengths)):
        engine.submit(Request(
            uid=uid0 + i, prompt=p, max_new_tokens=max_new,
            deadline_ms=deadline_ms or None,
            sampling=SamplingParams(temperature=0.8, top_k=20, seed=i)
            if i % 2 else SamplingParams()))
    done = engine.run()
    return engine, {r.uid - uid0: list(r.output) for r in done}


@pytest.fixture(scope="module")
def traced_run(model):
    """One shared overlapped traced run, reused by the schema / ordering /
    reconstruction tests (compilation dominates, so share it)."""
    cfg, params = model
    tracer = Tracer()
    engine, out = _run(cfg, params, tracer=tracer, overlap=True,
                       scheduler=DeadlinePolicy(), deadline_ms=60_000.0)
    return tracer, engine, out


def test_disabled_tracer_is_noop(model):
    cfg, params = model
    tracer = Tracer(enabled=False)
    assert not tracer
    _, traced = _run(cfg, params, tracer=tracer)
    _, plain = _run(cfg, params, tracer=None)
    assert len(tracer.events) == 0
    assert traced == plain


def test_ring_buffer_evicts_oldest():
    tracer = Tracer(capacity=4)
    for i in range(10):
        tracer.instant(f"e{i}", float(i), tid=0)
    assert len(tracer) == 4
    assert tracer.dropped == 6
    assert [e["name"] for e in tracer.events] == ["e6", "e7", "e8", "e9"]
    doc = tracer.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 6


def test_chrome_trace_schema(traced_run, tmp_path):
    tracer, _, _ = traced_run
    assert len(tracer.events) > 0
    doc = tracer.chrome_trace()
    assert validate_chrome_trace(doc) == []
    # survives a JSON round trip (what Perfetto actually loads)
    path = tmp_path / "trace.json"
    n = tracer.write(str(path))
    assert n == len(tracer.events)
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    for ev in loaded["traceEvents"]:
        if ev["ph"] == "X":
            assert ev["dur"] >= 0


def test_span_ordering_and_nesting(traced_run):
    """Exported timestamps are monotonic even under the overlapped loop,
    and every retired request's lifecycle instants sit inside its
    request span on the request's own track."""
    tracer, engine, out = traced_run
    doc = tracer.chrome_trace()
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)
    spans = {}          # uid -> request span
    firsts = {}         # uid -> first_token instant
    for e in doc["traceEvents"]:
        if e.get("cat") == "request" and e["name"] == "request":
            spans[e["tid"]] = e
        if e["name"] == "first_token":
            firsts[e["tid"]] = e
    assert set(spans) == set(out)
    for uid, span in spans.items():
        assert span["pid"] == PID_REQUEST
        assert uid in firsts
        assert span["ts"] <= firsts[uid]["ts"] <= span["ts"] + span["dur"]
    # engine rows exist too: step spans on the engine pid
    steps = [e for e in doc["traceEvents"] if e.get("cat") == "step"]
    assert steps and all(e["pid"] == PID_ENGINE for e in steps)
    assert {"prefill", "decode_step", "engine_step"} <= {
        e["name"] for e in steps}
    assert any(e["name"] == "decode_dispatch" for e in steps)


def test_ttft_reconstruction(traced_run):
    """TTFT recomputed from the trace alone (first_token instant minus
    request-span start) must match the value the span carries."""
    tracer, _, _ = traced_run
    doc = tracer.chrome_trace()
    spans = {e["tid"]: e for e in doc["traceEvents"]
             if e.get("cat") == "request" and e["name"] == "request"}
    firsts = {e["tid"]: e for e in doc["traceEvents"]
              if e["name"] == "first_token"}
    assert spans
    for uid, span in spans.items():
        ttft_ms = (firsts[uid]["ts"] - span["ts"]) / 1e3
        assert ttft_ms == pytest.approx(span["args"]["ttft_ms"], abs=0.1)


@pytest.mark.parametrize("mode", ["chunked", "warm_prefix", "spec"])
def test_token_identity_traced_vs_untraced(model, mode):
    """Tracing is a pure observer: identical committed tokens with the
    tracer attached, across the hook-heavy paths (chunked prefill,
    prefix-cache warm admission, speculative decode)."""
    cfg, params = model
    kw = {}
    if mode == "chunked":
        kw = dict(scheduler=ChunkedPrefillPolicy(8), lengths=(30, 6, 25))
    elif mode == "warm_prefix":
        rng = np.random.default_rng(4)
        shared = rng.integers(0, cfg.vocab, 24, dtype=np.int32)
        lengths = tuple([np.concatenate([shared, t]) for t in
                         _prompts(cfg, (4, 6, 5), seed=5)])
        kw = dict(prefix_cache=True, lengths=lengths,
                  scheduler=make_policy("fcfs", cache_aware=True))
    elif mode == "spec":
        if spec_support_reason(cfg) is not None:
            pytest.skip(spec_support_reason(cfg))
        kw = dict(spec=SpecConfig(draft="self", k=2))
    tracer = Tracer()
    _, traced = _run(cfg, params, tracer=tracer, **kw)
    _, plain = _run(cfg, params, tracer=None, **kw)
    assert traced == plain
    assert len(tracer.events) > 0
    assert validate_chrome_trace(tracer.chrome_trace()) == []


def test_warm_prefix_emits_warm_hit(model):
    """Second pass over a shared prefix emits warm_hit instants and the
    prefill_chunk/prefill spans mark recompute vs first admission."""
    cfg, params = model
    rng = np.random.default_rng(4)
    shared = rng.integers(0, cfg.vocab, 24, dtype=np.int32)
    tails = _prompts(cfg, (4, 6), seed=5)
    tracer = Tracer()
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, prefix_cache=True,
                             scheduler=make_policy("fcfs", cache_aware=True),
                             tracer=tracer)
    uid = 0
    for round_ in range(2):
        for t in tails:
            engine.submit(Request(uid=uid, max_new_tokens=3,
                                  prompt=np.concatenate([shared, t])))
            uid += 1
        engine.run()
    names = [e["name"] for e in tracer.events]
    assert "warm_hit" in names
    hit = next(e for e in tracer.events if e["name"] == "warm_hit")
    assert hit["args"]["cached_prefix"] > 0


def test_spec_trace_annotations(model):
    cfg, params = model
    if spec_support_reason(cfg) is not None:
        pytest.skip(spec_support_reason(cfg))
    tracer = Tracer()
    _run(cfg, params, tracer=tracer, spec=SpecConfig(draft="self", k=2))
    verifies = [e for e in tracer.events if e["name"] == "spec_verify"]
    drafts = [e for e in tracer.events if e["name"] == "spec_draft"]
    assert verifies and drafts
    for v in verifies:
        a = v["args"]
        assert a["phase"] == "verify"
        assert a["proposed"] >= a["accepted"] >= 0
        assert 0.0 <= a["accept_rate"] <= 1.0


def test_phase_util_and_trace_derivation_agree(traced_run):
    """stats.phase_util() (the counters) and derive_phase_metrics (the
    trace) are two routes to the same per-phase numbers.  The CPU is not
    in the peak table, so both keep the counts and report MFU/MBU as
    None ("not measured")."""
    _, engine, _ = traced_run
    tracer = engine.tracer
    st = engine.stats()
    assert st.device_kind == jax.devices()[0].device_kind
    assert PEAKS.get(st.device_kind) is None
    pu = st.phase_util()
    assert "prefill" in pu and "decode" in pu
    for row in pu.values():
        assert row["time_s"] > 0 and row["tokens"] > 0
        assert row["flops"] > 0 and row["hbm_bytes"] > 0
        assert row["mfu"] is None and row["mbu"] is None
    derived = derive_phase_metrics(
        tracer.events,
        flops_per_token=st.model_flops_per_token,
        weight_bytes=st.weight_bytes_per_device,
        kv_bytes_per_token=st.kv_bytes_per_token,
        peaks=PEAKS.get(st.device_kind))
    for phase in ("prefill", "decode"):
        assert phase in derived
        for key in ("time_s", "tokens", "flops", "hbm_bytes"):
            assert derived[phase][key] == pytest.approx(
                pu[phase][key], rel=1e-6), (phase, key)
        assert derived[phase]["mfu"] is None
        assert derived[phase]["mbu"] is None
    d = st.to_dict()
    assert d["phase_util"] == pu
    assert d["model_flops_per_token"] > 0
    assert d["kv_bytes_per_token"] > 0


def test_phase_util_with_explicit_v5e_peaks(traced_run):
    """Given a device's peaks explicitly, both routes report the same
    positive MFU/MBU: achieved rate over the table's bf16 / HBM peak."""
    _, engine, _ = traced_run
    st = engine.stats()
    v5e = PEAKS["TPU v5 lite"]
    pu = st.phase_util(peaks=v5e)
    derived = derive_phase_metrics(
        engine.tracer.events,
        flops_per_token=st.model_flops_per_token,
        weight_bytes=st.weight_bytes_per_device,
        kv_bytes_per_token=st.kv_bytes_per_token, peaks=v5e)
    for phase in ("prefill", "decode"):
        row = pu[phase]
        assert row["mfu"] == pytest.approx(
            row["flops"] / (row["time_s"] * v5e.bf16_flops), rel=1e-9)
        assert row["mbu"] == pytest.approx(
            row["hbm_bytes"] / (row["time_s"] * v5e.hbm_bw), rel=1e-9)
        assert row["mfu"] > 0 and row["mbu"] > 0
        for key in ("mfu", "mbu"):
            assert derived[phase][key] == pytest.approx(row[key],
                                                        rel=1e-6)


def test_spec_engine_attributes_verify_phase(model):
    cfg, params = model
    if spec_support_reason(cfg) is not None:
        pytest.skip(spec_support_reason(cfg))
    engine, _ = _run(cfg, params, spec=SpecConfig(draft="self", k=2))
    pu = engine.stats().phase_util(peaks=PEAKS["TPU v5 lite"])
    assert "verify" in pu and "decode" not in pu
    assert pu["verify"]["mfu"] > 0


def test_reservoir_keeps_late_outliers():
    """The old sliding window dropped early history; a reservoir keeps
    every sample equally likely, so late outliers reach p99 AND early
    samples survive a long tail of later ones."""
    r = Reservoir(capacity=64, seed=0)
    for _ in range(64):
        r.add(1.0)
    for _ in range(10_000):
        r.add(1000.0)
    assert len(r) == 64 and r.seen == 10_064
    assert percentile(r, 99) == 1000.0
    # early samples are not certainly evicted (the window would keep 0)
    # with capacity/seen ≈ 0.6% each over 64 slots this holds w.h.p. for
    # the fixed seed; determinism is asserted below so it cannot flake
    r2 = Reservoir(capacity=64, seed=0)
    for _ in range(64):
        r2.add(1.0)
    for _ in range(10_000):
        r2.add(1000.0)
    assert list(r) == list(r2)


def test_stats_sample_fields_are_reservoirs():
    st = EngineStats()
    assert isinstance(st.ttft_ms, Reservoir)
    assert isinstance(st.queue_wait_ms, Reservoir)
    d = st.to_dict()
    for key in ("ttft_p99_ms", "queue_wait_p99_ms", "decode_step_p99_ms",
                "decode_stall_p99_ms", "encode_latency_p99_ms",
                "draft_time_ms_p99", "spec_path_depth_p99"):
        assert key in d, key


def test_prometheus_text_snapshot(traced_run):
    _, engine, _ = traced_run
    text = prometheus_text(engine.stats().to_dict())
    assert "serving_ar_tok_s" in text
    assert 'serving_phase_flops{phase="decode"}' in text
    # MFU on a device without peaks is "not measured": never exported
    assert "serving_phase_mfu" not in text
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, val = line.rsplit(" ", 1)
            float(val)          # every sample parses
