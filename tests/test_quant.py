"""Low-precision serving: weight-only int8 GEMMs + int8 paged KV.

Three layers of evidence, mirroring how the feature is built:

  kernel parity     the Pallas fused-GEMM variants (interpret mode) and the
                    paged-attention kernels must match the pure-jnp oracles
                    bit-for-bit-close on int8 operands — the dequant scale
                    lands in the fp32 accumulator at the same point in both.
  quantization      quantize_params covers exactly the dense GEMM leaves,
                    the dims tree stays aligned leaf-for-leaf, per-channel
                    reconstruction error is bounded by scale/2, and the
                    end-of-model logit error stays small per arch.
  engine            int8 KV decodes/chunk-prefills token-identically to
                    bf16 (same math, quantize-on-write + dequant-on-read);
                    both knobs survive COW sharing, speculative rollback
                    and preemption-recompute without leaking pool blocks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.precision import FP32
from repro.kernels import ops, ref
from repro.kernels.flash_decode import (paged_decode_attention,
                                        paged_decode_partials)
from repro.kernels.matmul import matmul, matmul_swiglu
from repro.launch import steps as steps_mod
from repro.models import lm
from repro.models.quantize import (QUANT_KEYS, quantize_params,
                                   quantize_param_dims)
from repro.optim.compression import quantize_int8_axiswise
from repro.serving import (InferenceEngine, Request, SamplingParams,
                           SpecConfig, make_policy)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


def _qweight(key, K, N):
    """A quantized weight pair the way quantize_params makes them."""
    w = _rand(key, (K, N))
    q, scale = quantize_int8_axiswise(w, axis=(1,))
    return w, q, scale


# --------------------------------------------------------------------------
# kernel parity: int8 weight tiles through the fused epilogues
# --------------------------------------------------------------------------

def test_matmul_int8_scale_vs_ref():
    a = _rand(0, (48, 96))
    _, q, scale = _qweight(1, 96, 64)
    out = matmul(a, q, b_scale=scale, block_m=32, block_n=32, block_k=32,
                 interpret=True)
    want = ref.fused_matmul_ref(a, q, w_scale=scale, compute_dtype=a.dtype,
                                out_dtype=a.dtype)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_matmul_int8_full_epilogue_vs_ref():
    """norm prologue + bias + activation + residual around the int8 dot:
    the dequant multiply must land before the bias in both paths."""
    a = _rand(0, (32, 64))
    _, q, scale = _qweight(1, 64, 48)
    gamma = 1.0 + 0.1 * _rand(2, (64,))
    bias = _rand(3, (48,))
    res = _rand(4, (32, 48))
    out = matmul(a, q, b_scale=scale, norm="rmsnorm", gamma=gamma, bias=bias,
                 activation="gelu", residual=res, block_m=16, block_n=16,
                 block_k=32, interpret=True)
    want = ref.fused_matmul_ref(a, q, w_scale=scale, norm="rmsnorm",
                                gamma=gamma,
                                bias=bias, activation="gelu", residual=res,
                                out_dtype=a.dtype)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_matmul_swiglu_int8_vs_ref():
    a = _rand(0, (32, 64))
    _, qg, sg = _qweight(1, 64, 48)
    _, qu, su = _qweight(2, 64, 48)
    out = matmul_swiglu(a, qg, qu, bg_scale=sg, bu_scale=su, block_m=16,
                        block_n=16, block_k=32, interpret=True)
    want = ref.fused_matmul_swiglu_ref(a, qg, qu, wg_scale=sg, wu_scale=su,
                                       out_dtype=a.dtype)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def _int8_pool(key, NB, BS, KV, D):
    """An int8 pool [NB, BS, KV*D] + per-block-per-head scales [NB, KV],
    quantized the way the cache scatters write them."""
    x = _rand(key, (NB, BS, KV, D))
    amax = jnp.abs(x).max(axis=(1, 3))
    s = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s[:, None, :, None]), -127, 127
                 ).astype(jnp.int8)
    return q.reshape(NB, BS, KV * D), s


@pytest.mark.parametrize("B,H,KV,D", [(2, 4, 4, 32), (3, 8, 2, 16)])
def test_paged_decode_int8_vs_ref(B, H, KV, D):
    NB, BS, MB = 6, 8, 3
    kq, ks = _int8_pool(0, NB, BS, KV, D)
    vq, vs = _int8_pool(1, NB, BS, KV, D)
    q = _rand(2, (B, H, D))
    rng = np.random.default_rng(3)
    tables = jnp.asarray(np.stack([rng.permutation(NB)[:MB]
                                   for _ in range(B)]).astype(np.int32))
    lengths = jnp.asarray([BS * MB, 5, 17][:B], jnp.int32)
    out = paged_decode_attention(q, kq[None], vq[None], 0, tables, lengths,
                                 k_scale=ks[None], v_scale=vs[None],
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kq, vq, tables, lengths,
                                          k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_paged_partials_int8_vs_ref():
    B, H, KV, D, NB, BS = 2, 4, 4, 32, 6, 8
    kq, ks = _int8_pool(0, NB, BS, KV, D)
    vq, vs = _int8_pool(1, NB, BS, KV, D)
    q = _rand(2, (B, H, D))
    tables = jnp.asarray([[0, 2, -1], [5, -1, -1]], jnp.int32)
    lengths = jnp.asarray([11, 8], jnp.int32)
    o, m, l = paged_decode_partials(q, kq[None], vq[None], 0, tables,
                                    lengths, k_scale=ks[None],
                                    v_scale=vs[None], interpret=True)
    ow, mw, lw = ref.paged_decode_partials_ref(q, kq, vq, tables, lengths,
                                               k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(m, mw, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(l, lw, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(o, ow, rtol=2e-5, atol=2e-4)


def test_int8_pool_matches_bf16_attention():
    """End-to-end quantize-then-attend: the int8 pool's output must sit
    within quantization error of attending over the original bf16 pool."""
    B, H, KV, D, NB, BS = 2, 4, 4, 32, 4, 8
    k = _rand(0, (NB, BS, KV, D)).reshape(NB, BS, KV * D)
    v = _rand(1, (NB, BS, KV, D)).reshape(NB, BS, KV * D)
    kq, ks = _int8_pool(0, NB, BS, KV, D)   # same draws as k/v above
    vq, vs = _int8_pool(1, NB, BS, KV, D)
    q = _rand(2, (B, H, D))
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lengths = jnp.asarray([16, 13], jnp.int32)
    exact = ref.paged_decode_attention_ref(q, k, v, tables, lengths)
    quant = ref.paged_decode_attention_ref(q, kq, vq, tables, lengths,
                                           k_scale=ks, v_scale=vs)
    err = float(jnp.abs(exact - quant).max())
    assert err < 0.05, f"int8 KV attention error {err} too large"


# --------------------------------------------------------------------------
# quantize_params: coverage, dims alignment, error bounds
# --------------------------------------------------------------------------

def _leaves_with_paths(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: (isinstance(x, dict)
                                 and set(x) == {"q", "scale"}))[0]


def test_quantize_params_coverage_and_dims():
    cfg = get_config("gpt-j").reduced()
    params = lm.init_lm(jax.random.key(0), cfg, jnp.bfloat16)
    qp = quantize_params(params)
    quantized = stayed = 0
    for path, leaf in _leaves_with_paths(qp):
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if isinstance(leaf, dict):
            assert leaf["q"].dtype == jnp.int8
            assert leaf["scale"].dtype == jnp.float32
            assert leaf["scale"].shape == (leaf["q"].shape[:-2]
                                           + leaf["q"].shape[-1:])
            quantized += 1
        else:
            assert name not in QUANT_KEYS or leaf.ndim != 3
            stayed += 1
    assert quantized > 0 and stayed > 0      # head + blocks vs norms/embed
    assert not isinstance(qp["embedding"]["embed"], dict)  # gather, not GEMM
    assert isinstance(qp["embedding"]["unemb"], dict)
    # dims tree maps through the same transform, leaf-for-leaf
    dims = quantize_param_dims(lm.lm_param_dims(cfg))
    struct = jax.eval_shape(quantize_params, params)
    is_dim = lambda x: (isinstance(x, tuple)      # axis-name tuple, not the
                        and all(e is None or isinstance(e, str)
                                for e in x))      # segments tuple-of-dicts
    assert (jax.tree_util.tree_structure(
                jax.tree.map(lambda x: 0, dims, is_leaf=is_dim))
            == jax.tree_util.tree_structure(
                jax.tree.map(lambda x: 0, struct)))


def test_quantize_reconstruction_bound():
    """Per-output-channel symmetric quantization: every element sits within
    half a quantization step of the original."""
    w, q, scale = _qweight(0, 128, 64)
    err = jnp.abs(w - q.astype(jnp.float32) * scale)
    assert bool((err <= 0.5 * scale + 1e-7).all())


@pytest.mark.parametrize("arch", ["gpt-j", "gpt3-xl", "phi4-mini-3.8b"])
def test_logit_error_bound(arch):
    """Quantizing the real (init-distribution) head weight moves no logit
    by more than 1% of the logit range."""
    cfg = get_config(arch).reduced()
    params = lm.init_lm(jax.random.key(0), cfg, jnp.float32)
    unemb = params["embedding"]["unemb"]
    qleaf = quantize_params(params)["embedding"]["unemb"]
    x = _rand(1, (4, unemb.shape[0]))
    z = x @ unemb
    zq = (x @ qleaf["q"].astype(jnp.float32)) * qleaf["scale"]
    span = float(jnp.abs(z).max())
    err = float(jnp.abs(z - zq).max())
    assert err < 0.01 * span, f"{arch}: logit error {err} vs span {span}"


# --------------------------------------------------------------------------
# steps: int8 KV decode/chunk parity with bf16
# --------------------------------------------------------------------------

def test_kv_int8_decode_chunk_token_parity():
    """Chunked admission + greedy decode through the serving steps: the
    int8 pool must commit the same tokens as the bf16 pool (FP32-policy
    archs keep quantization the only perturbation; at init-weight scale it
    stays below every argmax margin on this trace)."""
    cfg = get_config("gpt-j").reduced()
    B, max_seq, bs = 2, 64, 8
    nb = B * (max_seq // bs)
    params = lm.init_lm(jax.random.key(0), cfg, jnp.bfloat16)
    dshape = ShapeConfig("tq_decode", "decode", max_seq, B)

    def run(kv_dtype):
        step = steps_mod.make_decode_step(
            cfg, dshape, None, max_seq=max_seq, with_sampling=True,
            paged=(nb, bs), kv_cache_dtype=kv_dtype)
        chunk = steps_mod.make_chunk_prefill_step(
            cfg, ShapeConfig("tq_chunk", "decode", max_seq, 1), None,
            layout=step.aux["paged"], chunk_tokens=16, max_seq=max_seq,
            with_sampling=True, kv_cache_dtype=kv_dtype)
        caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              step.aux["cache_struct"])
        if kv_dtype == "int8":
            pools = [x for x in jax.tree.leaves(caches)
                     if x.dtype == jnp.int8]
            assert pools, "int8 cache layout did not materialize"
        layout = step.aux["paged"]
        rng = np.random.RandomState(7)
        prompt = rng.randint(10, 200, size=20).astype(np.int32)
        table = np.full((1, layout.max_blocks), -1, np.int32)
        table[0, :3] = [0, 1, 2]
        lane1 = {"temperature": jnp.zeros((1,), jnp.float32),
                 "top_k": jnp.zeros((1,), jnp.int32),
                 "seed": jnp.zeros((1,), jnp.int32),
                 "step": jnp.zeros((1,), jnp.int32)}
        tok = None
        for start in (0, 16):
            take = min(16, 20 - start)
            ch = np.zeros((1, 16), np.int32)
            ch[0, :take] = prompt[start:start + take]
            tok, caches, _ = chunk.fn(
                params, jnp.asarray(ch), jnp.asarray([start], jnp.int32),
                jnp.asarray([take], jnp.int32), caches, jnp.asarray(table),
                lane1)
        toks = [int(np.asarray(tok)[0])]
        full_table = np.full((B, layout.max_blocks), -1, np.int32)
        full_table[0] = table[0]
        pos = np.array([20, 0], np.int32)
        cur = np.array([toks[0], 0], np.int32)
        laneB = {k: jnp.zeros((B,), v.dtype) for k, v in lane1.items()}
        for _ in range(5):
            t_d, p_d, caches = step.fn(params, jnp.asarray(cur),
                                       jnp.asarray(pos), caches,
                                       jnp.asarray(full_table), laneB)
            cur = np.asarray(t_d)
            pos = np.asarray(p_d)
            toks.append(int(cur[0]))
        return toks

    assert run("int8") == run("bfloat16")


# --------------------------------------------------------------------------
# engine: both knobs end to end; COW / spec rollback / preemption
# --------------------------------------------------------------------------

_PARAMS_CACHE = {}


def _reduced(arch):
    if arch not in _PARAMS_CACHE:
        cfg = get_config(arch).reduced()
        _PARAMS_CACHE[arch] = (cfg, lm.init_lm(jax.random.key(0), cfg,
                                               jnp.float32))
    return _PARAMS_CACHE[arch]


def _trace(cfg, n=4, *, pre_len=24, max_new=6, sampled=(), seed=11):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, pre_len, dtype=np.int32)
    out = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab, 3 + i, dtype=np.int32)
        out.append(Request(
            uid=i,
            prompt=np.concatenate([shared, tail]) if i else shared.copy(),
            max_new_tokens=max_new,
            sampling=SamplingParams(temperature=0.8, top_k=8, seed=i)
            if i in sampled else SamplingParams()))
    return out


def _run(cfg, params, reqs, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_seq", 64)
    engine = InferenceEngine(cfg, params, policy=FP32, **kw)
    for r in reqs:
        engine.submit(r)
    done = {t.uid: t.output for t in engine.run()}
    return engine, done


def _no_leaks(engine):
    alloc, pc = engine.allocator, engine.prefix_cache
    cached = pc.cached_blocks if pc is not None else 0
    assert alloc.num_free == alloc.num_blocks - cached
    if pc is not None:
        assert all(alloc.refcount(b) == 1 for b in pc.index_blocks())
        pc.check()


def test_engine_e2e_both_knobs():
    """Both opt-ins through the full engine: requests complete, the stats
    report the dtypes and the byte shrink, and the run stays leak-free."""
    cfg, params = _reduced("gpt-j")
    base_eng, base = _run(cfg, params, _trace(cfg), prefix_cache=False)
    eng, done = _run(cfg, params, _trace(cfg), prefix_cache=False,
                     weight_dtype="int8", kv_dtype="int8")
    assert sorted(done) == sorted(base)
    assert all(len(done[u]) == len(base[u]) for u in base)
    st, bst = eng.stats(), base_eng.stats()
    assert (st.weight_dtype, st.kv_dtype) == ("int8", "int8")
    assert (bst.weight_dtype, bst.kv_dtype) == ("bfloat16", "bfloat16")
    assert 0 < st.weight_bytes_per_device < 0.62 * bst.weight_bytes_per_device
    assert 0 < st.kv_pool_bytes < 0.55 * bst.kv_pool_bytes
    assert "QUANT" in st.summary() and "QUANT" not in bst.summary()
    _no_leaks(eng)


def test_kv_int8_engine_token_identity():
    """int8 KV alone (weights bf16-exact in FP32 policy): token-identical
    to the bf16 pool across greedy AND sampled requests."""
    cfg, params = _reduced("gpt-j")
    mk = lambda: _trace(cfg, sampled=(1, 3))
    base = _run(cfg, params, mk(), prefix_cache=False)[1]
    _, got = _run(cfg, params, mk(), prefix_cache=False, kv_dtype="int8")
    assert got == base


def test_kv_int8_stacked_decode_kernel_matches_reference():
    """int8 pools, ten decode steps in blocks of 4 (each slot crosses two
    or three block boundaries): the paged kernel in interpret mode serves
    the jnp reference's tokens, greedy and sampled.  Both fold the current
    token in as the pool stores it — quantized, then dequantized by its
    block's scale (re-amaxed at offset 0, reused and clipped after)."""
    cfg, params = _reduced("gpt-j")
    outs = []
    for mode in ("interpret", "ref"):
        with ops.kernel_mode(mode):
            outs.append(_run(cfg, params, _trace(cfg, pre_len=9, max_new=10,
                                                 sampled=(1, 3)),
                             prefix_cache=False, kv_dtype="int8",
                             block_size=4)[1])
    assert outs[0] == outs[1]


def test_quantized_pool_cow_and_preemption():
    """Prefix sharing (COW on the partial tail) + a starved pool
    (preemption-recompute) over int8 pools: everything completes and no
    block leaks; re-quantization on recompute reproduces the same scales
    (pure function of token content), so outputs stay stable."""
    cfg, params = _reduced("gpt-j")
    mk = lambda: _trace(cfg, pre_len=20, max_new=8, sampled=(2,))
    _, base = _run(cfg, params, mk(), prefix_cache=True, kv_dtype="int8",
                   block_size=8, kv_pool_blocks=32,
                   scheduler=make_policy("fcfs", cache_aware=True))
    eng, got = _run(cfg, params, mk(), prefix_cache=True, kv_dtype="int8",
                    block_size=8, kv_pool_blocks=6,
                    scheduler=make_policy("fcfs", cache_aware=True))
    st = eng.stats()
    assert st.preemptions > 0
    assert got == base
    _no_leaks(eng)


def test_quantized_pool_spec_rollback():
    """Speculative decoding over int8 pools with a rejection-heavy draft:
    rollback truncates lengths only — rejected positions are re-quantized
    on overwrite per the offset-0 scale-reset rule — and greedy outputs
    match the non-spec int8 engine exactly."""
    cfg, params = _reduced("gpt-j")
    mk = lambda: _trace(cfg, pre_len=16, max_new=8)
    base = _run(cfg, params, mk(), prefix_cache=False, kv_dtype="int8")[1]
    spec = SpecConfig(draft="auto", k=3, draft_seed=1234)
    eng, got = _run(cfg, params, mk(), prefix_cache=False, kv_dtype="int8",
                    kv_pool_blocks=24, spec=spec)
    assert got == base
    assert eng.stats().spec_rounds > 0
    _no_leaks(eng)
