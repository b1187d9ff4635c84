"""Observability layer: stage spans on the profiler's clock and
request-level tracing.

Covers serving/trace.py end to end through the engine: the stage spans'
nesting in one synchronous step, their capture by a real jax.profiler
session (with the decode step's `kv_positions`), token identity with a
profiler session or a ring Tracer on vs off, the `engine_steps` /
`host_s` counters the spans feed, span nesting and ordering under the
overlapped host loop, ring-buffer eviction, Chrome trace-event schema
validity (Perfetto-loadable), TTFT reconstruction from the trace alone,
the Reservoir sampling satellite, and the Prometheus text snapshot.
"""
import glob
import gzip
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.precision import FP32
from repro.models import lm
from repro.serving import (ChunkedPrefillPolicy, DeadlinePolicy,
                           InferenceEngine, Request, Reservoir,
                           SamplingParams, SpecConfig, Tracer, make_policy,
                           percentile, prometheus_text, spec_support_reason,
                           validate_chrome_trace)
from repro.serving import trace
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
    assert {trace.PREFILL, trace.DECODE_FETCH, trace.ENGINE_STEP} <= {
        e["name"] for e in steps}
    assert any(e["name"] == trace.DECODE_DISPATCH for e in steps)


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
    engine.chunk spans mark recompute vs first admission."""
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
    verifies = [e for e in tracer.events if e["name"] == trace.SPEC_VERIFY]
    drafts = [e for e in tracer.events if e["name"] == trace.SPEC_DRAFT]
    assert verifies and drafts
    for v in verifies:
        a = v["args"]
        assert a["slots"] >= 1
        assert a["proposed"] >= a["accepted"] >= 0
        assert 0.0 <= a["accept_rate"] <= 1.0


def _record_annotations(monkeypatch):
    """Stand in for jax.profiler.TraceAnnotation: -> the (depth, name,
    args) of every annotation, in the order they open."""
    log = []
    depth = [0]

    class Recorder:
        def __init__(self, name, **args):
            self.args = dict(args)
            log.append((depth[0], name, self.args))

        def __enter__(self):
            depth[0] += 1
            return self

        def set_metadata(self, **args):
            self.args.update(args)

        def __exit__(self, *exc):
            depth[0] -= 1

    monkeypatch.setattr(trace, "TraceAnnotation", Recorder)
    return log


def test_stage_spans_nest_in_one_sync_step(model, monkeypatch):
    """One synchronous step that admits a request and decodes it opens
    the stage spans in the order, and at the depth, the work nests."""
    cfg, params = model
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    prompt = _prompts(cfg, (6,))[0]
    engine.submit(Request(uid=7, prompt=prompt, max_new_tokens=4))
    log = _record_annotations(monkeypatch)
    engine.step()
    assert [(d, n) for d, n, _ in log] == [
        (0, trace.ENGINE_STEP),
        (1, trace.ADMIT),
        (2, trace.PREFILL), (3, trace.PREFILL_FETCH),
        (2, trace.RETIRE),
        (1, trace.RETIRE),
        (1, trace.BLOCKS),
        (1, trace.DECODE_DISPATCH), (1, trace.DECODE_FETCH),
        (1, trace.DECODE_COMMIT),
        (1, trace.RETIRE)]
    args = {n: a for _, n, a in log}
    assert args[trace.ENGINE_STEP] == {"n": 0, "queued": 1, "running": 0}
    assert args[trace.ADMIT] == {"admitted": 1}
    assert args[trace.PREFILL]["tokens"] == 6
    assert args[trace.PREFILL]["uids"] == [7]
    # the decode input sits at position 6 and attends positions 0..6
    assert args[trace.DECODE_DISPATCH]["kv_positions"] == 7
    assert args[trace.DECODE_DISPATCH]["uids"] == [7]
    log.clear()
    engine.step()
    assert log[0] == (0, trace.ENGINE_STEP,
                      {"n": 1, "queued": 0, "running": 1})
    assert [n for _, n, _ in log if n == trace.PREFILL] == []


@pytest.fixture(scope="module")
def profiled_run(model, tmp_path_factory):
    """The tiny engine served inside a real jax.profiler session: its
    tokens, stats, and the host events of the exported trace."""
    cfg, params = model
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        engine, tokens = _run(cfg, params)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    assert len(files) == 1, files
    with gzip.open(files[0], "rt") as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e.get("ph") == "X"
                  and str(e.get("name", "")).startswith("engine.")]
    return tokens, engine.stats(), events


def test_engine_spans_in_profiler_capture(profiled_run):
    """The stage spans land in the profiler's own trace file, with their
    args: one engine.step per step, and the decode steps' kv_positions
    summing to the KV positions the steps attended."""
    _, st, events = profiled_run
    names = {e["name"] for e in events}
    assert {trace.ENGINE_STEP, trace.ADMIT, trace.PREFILL,
            trace.PREFILL_FETCH, trace.DECODE_DISPATCH, trace.DECODE_FETCH,
            trace.DECODE_COMMIT, trace.RETIRE, trace.BLOCKS} <= names
    assert sum(e["name"] == trace.ENGINE_STEP
               for e in events) == st.engine_steps
    dispatch = [e for e in events if e["name"] == trace.DECODE_DISPATCH]
    assert len(dispatch) == st.decode_steps
    kv = [int(e["args"]["kv_positions"]) for e in dispatch]
    assert all(k > 0 for k in kv)
    assert sum(kv) == st.token_slot_steps


def test_tokens_identical_with_profiler_on_and_off(model, profiled_run):
    tokens, _, _ = profiled_run
    cfg, params = model
    _, plain = _run(cfg, params)
    assert tokens == plain


def test_host_s_and_engine_steps(model):
    """engine_steps counts step() calls; host_s is the part of their wall
    time spent outside the token fetches: positive, below the total."""
    cfg, params = model
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    for i, p in enumerate(_prompts(cfg, (6, 13, 20))):
        engine.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    walls = []
    while engine.has_work():
        t0 = time.perf_counter()
        engine.step()
        walls.append(time.perf_counter() - t0)
    st = engine.stats()
    assert st.engine_steps == len(walls)
    assert 0 < st.host_s < sum(walls)
    d = st.to_dict()
    assert d["engine_steps"] == len(walls) and d["host_s"] == st.host_s
    engine.reset_stats()
    assert engine.stats().engine_steps == 0
    assert engine.stats().host_s == 0.0


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
    assert "serving_engine_steps" in text and "serving_host_s" in text
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, val = line.rsplit(" ", 1)
            float(val)          # every sample parses
