"""Serving engine: session API correctness on one device.

Covers the InferenceEngine redesign: variable-length prompts across prefill
length buckets (output-exact vs direct unpadded decode), per-request
SamplingParams (greedy ≡ temperature 0 ≡ top-k 1, seeded reproducibility),
streaming-vs-batch equivalence, eos/max-new retirement under mixed lengths,
and the EngineStats telemetry counters.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.precision import FP32
from repro.models import lm
from repro.serving import (InferenceEngine, Request, SamplingParams,
                           ServingEngine)
from repro.serving import kv_cache as kv_mod
from repro.serving.kv_cache import insert_row, zero_caches
from repro.sharding.plan import UNSHARDED


def _direct_tokens(cfg, params, prompt, n_new, max_seq=64):
    """Reference: unpadded prefill + greedy decode loop."""
    batch = {"tokens": jnp.asarray(prompt)[None]}
    tok, caches, pos = lm.forward_prefill(params, batch, plan=UNSHARDED,
                                          cfg=cfg, policy=FP32,
                                          max_seq=max_seq)
    toks = [int(tok[0])]
    t, p = tok, pos
    for _ in range(n_new - 1):
        t, caches = lm.forward_decode(params, t, p, caches, plan=UNSHARDED,
                                      cfg=cfg, policy=FP32)
        p = p + 1
        toks.append(int(t[0]))
    return toks


def _phi4():
    cfg = get_config("phi4-mini-3.8b").reduced()
    params = lm.init_lm(jax.random.key(0), cfg, jnp.float32)
    return cfg, params


def _submit_all(engine, prompts, *, max_new=5, sampling=None, eos_id=None):
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=max_new,
                              eos_id=eos_id,
                              sampling=sampling(uid) if sampling
                              else SamplingParams()))


def test_engine_matches_direct_decode():
    """Tokens from the engine == tokens from a direct prefill+decode loop."""
    cfg, params = _phi4()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, 16, dtype=np.int32)
               for _ in range(3)]

    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    _submit_all(engine, prompts)
    done = sorted(engine.run(), key=lambda r: r.uid)
    assert len(done) == 3
    assert all(len(r.output) == 5 for r in done)
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 5) == req.output


def test_variable_length_prompts_across_buckets():
    """Prompts of differing lengths in one run, each output-exact vs the
    direct unpadded loop (pad-to-bucket must not leak into the math).
    Buckets step by 1.5x/2x rungs (8, 12, 16, 24, 32, ...)."""
    cfg, params = _phi4()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 8, 16, 23)]

    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, min_bucket=8)
    _submit_all(engine, prompts)
    done = sorted(engine.run(), key=lambda r: r.uid)
    assert [r.bucket for r in done] == [8, 8, 16, 24]
    assert [r.prompt_len for r in done] == [5, 8, 16, 23]
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 5) == req.output, (
            req.uid, req.prompt_len, req.bucket)
    # one compile per distinct (bucket, group size), not per request: the
    # two bucket-8 prompts prefill together in one batched call
    assert engine.stats().prefill_compiles == 3


def test_exact_length_buckets_for_recurrent_caches():
    """SSM / sliding-window archs must prefill at exact length (their state
    would absorb pad positions)."""
    cfg = get_config("gemma3-27b").reduced()          # sliding-window layers
    assert cfg.sliding_window > 0
    params = lm.init_lm(jax.random.key(1), cfg, jnp.float32)
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    assert engine.bucket_for(5) == 5 and engine.bucket_for(13) == 13
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (6, 11)]
    _submit_all(engine, prompts, max_new=3)
    done = sorted(engine.run(), key=lambda r: r.uid)
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 3) == req.output


def test_engine_continuous_batching_refills():
    """More requests than slots: finished slots must be reused."""
    cfg = get_config("gemma3-27b").reduced()
    params = lm.init_lm(jax.random.key(1), cfg, jnp.float32)
    rng = np.random.default_rng(5)
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    _submit_all(engine, [rng.integers(0, cfg.vocab, 8, dtype=np.int32)
                         for _ in range(5)], max_new=3)
    done = engine.run()
    assert len(done) == 5
    assert engine.steps_run < 5 * 3      # rows overlapped, not serialized
    assert engine.stats().slot_occupancy > 0.5


def test_temperature_zero_and_topk_one_are_greedy():
    """temperature=0 ≡ greedy; top_k=1 at high temperature ≡ greedy (the
    Gumbel draw over a single candidate is deterministic)."""
    cfg, params = _phi4()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (8, 16)]

    outs = {}
    for name, sp in (("greedy", SamplingParams()),
                     ("t0", SamplingParams(temperature=0.0, seed=9)),
                     ("top1", SamplingParams(temperature=2.0, top_k=1,
                                             seed=4))):
        engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                                 policy=FP32)
        _submit_all(engine, prompts, sampling=lambda uid: sp)
        outs[name] = [r.output for r in
                      sorted(engine.run(), key=lambda r: r.uid)]
    assert outs["greedy"] == outs["t0"] == outs["top1"]


def test_per_request_seed_reproducible():
    """Same seed => identical sampled tokens across engine runs; different
    seeds diverge."""
    cfg, params = _phi4()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (8, 16, 12)]

    def run_with(seed_fn):
        engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                                 policy=FP32)
        _submit_all(engine, prompts, max_new=8, sampling=lambda uid:
                    SamplingParams(temperature=1.0, top_k=0,
                                   seed=seed_fn(uid)))
        return [r.output for r in sorted(engine.run(), key=lambda r: r.uid)]

    a = run_with(lambda uid: 100 + uid)
    b = run_with(lambda uid: 100 + uid)
    c = run_with(lambda uid: 500 + uid)
    assert a == b                        # reproducible
    assert a != c                        # seed actually steers the draw
    greedy = [_direct_tokens(cfg, params, p, 8) for p in prompts]
    assert a != greedy                   # and it is not secretly greedy


def test_streaming_matches_run():
    """generate() yields exactly the tokens run() accumulates, with one
    is_last per request on its final token."""
    cfg, params = _phi4()
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 16, 23)]
    sampling = lambda uid: (SamplingParams(temperature=0.9, seed=uid)
                            if uid % 2 else SamplingParams())

    stream_engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                                    policy=FP32)
    _submit_all(stream_engine, prompts, sampling=sampling)
    streamed, last_seen = {}, {}
    for ev in stream_engine.generate():
        streamed.setdefault(ev.uid, []).append(ev.token)
        assert ev.uid not in last_seen, "token after is_last"
        if ev.is_last:
            last_seen[ev.uid] = True

    batch_engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                                   policy=FP32)
    _submit_all(batch_engine, prompts, sampling=sampling)
    done = {r.uid: r.output for r in batch_engine.run()}

    assert streamed == done
    assert set(last_seen) == set(done)


def test_eos_and_max_new_retirement_mixed_lengths():
    """eos_id truncates generation; max_new_tokens caps it; both under
    mixed prompt lengths in one batch."""
    cfg, params = _phi4()
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 16, 23)]

    probe = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                            policy=FP32)
    _submit_all(probe, prompts, max_new=8)
    ref = {r.uid: r.output for r in probe.run()}
    eos = ref[0][2]                      # retire uid 0 at its 3rd token

    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    _submit_all(engine, prompts, max_new=8, eos_id=eos)
    done = {r.uid: r for r in engine.run()}
    assert len(done) == 3
    for uid, req in done.items():
        assert len(req.output) <= 8
        if eos in ref[uid][:7]:
            cut = ref[uid].index(eos)
            assert req.output == ref[uid][:cut + 1], uid
        else:
            assert req.output == ref[uid]
    assert done[0].output[-1] == eos and len(done[0].output) == 3


def test_engine_stats_telemetry():
    """EngineStats: NAR/AR split, true (not padded) prompt token counts,
    TTFT per request, bucket hits."""
    cfg, params = _phi4()
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 16, 23)]
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, min_bucket=8)
    _submit_all(engine, prompts)
    done = engine.run()
    st = engine.stats()
    assert st.requests_submitted == st.requests_completed == 3
    assert st.nar_tokens == 5 + 16 + 23              # true lengths
    assert st.padded_nar_tokens == 8 + 16 + 24       # bucket lengths
    assert st.ar_tokens == sum(len(r.output) for r in done) - 3
    assert st.nar_time_s > 0 and st.ar_time_s > 0
    assert st.nar_tok_s > 0 and st.ar_tok_s > 0
    assert len(st.ttft_ms) == 3 and all(t > 0 for t in st.ttft_ms)
    assert st.ttft_p95_ms >= st.ttft_p50_ms > 0
    assert st.bucket_hits == {8: 1, 16: 1, 24: 1}
    assert 0 < st.slot_occupancy <= 1
    assert st.decode_step_p95_ms >= st.decode_step_p50_ms > 0
    assert st.kv_pool_blocks > 0 and st.peak_blocks_used > 0
    assert 0 < st.pool_utilization <= 1
    assert st.blocks_per_token >= 1.0
    assert st.preemptions == 0
    d = st.to_dict()
    assert d["nar_tok_s"] == st.nar_tok_s and d["bucket_hits"]["8"] == 1
    assert d["pool_utilization"] == st.pool_utilization
    engine.reset_stats()
    assert engine.stats().nar_tokens == 0
    assert engine.stats().kv_pool_blocks == st.kv_pool_blocks


def test_serving_engine_alias():
    """The pre-redesign name remains importable and is the same class."""
    assert ServingEngine is InferenceEngine


def test_insert_row():
    batch = {"k": jnp.zeros((2, 4, 8)), "v": jnp.zeros((2, 4, 8))}
    single = {"k": jnp.ones((2, 1, 8)), "v": 2 * jnp.ones((2, 1, 8))}
    out = insert_row(batch, single, 2)
    assert float(out["k"][:, 2].min()) == 1.0
    assert float(out["v"][:, 2].min()) == 2.0
    assert float(out["k"][:, 0].max()) == 0.0


def test_zero_caches_struct():
    st = {"a": jax.ShapeDtypeStruct((2, 3), jnp.bfloat16)}
    z = zero_caches(st)
    assert z["a"].shape == (2, 3) and z["a"].dtype == jnp.bfloat16


def test_zero_caches_compile_cached():
    """Repeated zero_caches over the same struct reuses the jitted zeros
    builders (one per distinct leaf) instead of re-jitting per call."""
    st = {"a": jax.ShapeDtypeStruct((4, 5), jnp.float32),
          "b": jax.ShapeDtypeStruct((4, 5), jnp.float32),
          "c": jax.ShapeDtypeStruct((2, 2), jnp.bfloat16)}
    zero_caches(st)
    n = len(kv_mod._ZEROS_CACHE)
    zero_caches(st)
    zero_caches({"d": jax.ShapeDtypeStruct((4, 5), jnp.float32)})
    assert len(kv_mod._ZEROS_CACHE) == n


def test_prefill_logits_argmax_is_greedy_token():
    """lm.prefill_logits exposes the scores forward_prefill's greedy pick
    takes the argmax of (the chip smoke test compares them across kernel
    dispatch modes)."""
    cfg, params = _phi4()
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 11), dtype=np.int32))
    z = lm.prefill_logits(params, {"tokens": toks}, plan=UNSHARDED,
                          cfg=cfg, policy=FP32)
    tok, _, _ = lm.forward_prefill(params, {"tokens": toks}, plan=UNSHARDED,
                                   cfg=cfg, policy=FP32, max_seq=16)
    assert z.shape == (2, cfg.padded_vocab) and z.dtype == jnp.float32
    assert np.all(np.asarray(z)[:, cfg.vocab:] < -1e29)
    np.testing.assert_array_equal(np.asarray(z).argmax(-1), np.asarray(tok))


def test_upload_snapshots_the_host_array_behind_a_busy_device():
    """`jnp.array(numpy)` takes no host copy: with the CPU device busy the
    transfer can run after the call returns and read a write made after
    it.  `upload` (tokens, positions, block tables, lanes) must hand the
    step the values as of the call."""
    from repro.serving.sampling import upload
    busy = jax.jit(lambda m: (m @ m) @ m)
    m = jnp.ones((1000, 1000), jnp.float32)
    busy(m).block_until_ready()
    for _ in range(20):
        pending = busy(m)
        host = np.zeros((2,), np.int32)
        dev = upload(host)
        host += 7
        assert int(np.asarray(dev).max()) == 0
    pending.block_until_ready()


def test_device_lane_uploads_a_copy():
    """The runner mutates its host lanes in place after dispatching a step;
    the device arrays handed to that step must not alias them (on the CPU
    backend `jnp.asarray` can share the numpy buffer, which made queued
    steps read later values under load)."""
    from repro.serving.sampling import device_lane
    for n in range(1, 41):
        lane = {"seed": np.zeros(n, np.int32)}
        dev = device_lane(lane)
        lane["seed"] += 7
        assert int(np.asarray(dev["seed"]).max()) == 0, n
