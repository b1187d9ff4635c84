"""Block-paged KV cache: allocator invariants, paged-vs-dense decode
attention parity (jnp reference and Pallas interpret mode), batched prefill
admission parity, and out-of-blocks preemption correctness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.precision import FP32
from repro.kernels import ops, ref
from repro.kernels.flash_decode import paged_decode_attention
from repro.models import lm
from repro.serving import InferenceEngine, Request, SamplingParams
from repro.serving.kv_cache import BlockAllocator
from repro.sharding.plan import UNSHARDED


# --------------------------------------------------------------------------
# BlockAllocator
# --------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(num_blocks=6, block_size=4)
    assert a.num_free == 6 and a.num_used == 0
    x = a.alloc(2)
    y = a.alloc(3)
    assert len(x) == 2 and len(y) == 3
    assert len(set(x) | set(y)) == 5          # distinct blocks
    assert a.num_used == 5 and a.peak_used == 5
    a.free(x)
    assert a.num_free == 3
    z = a.alloc(3)                            # freed blocks come back
    assert z is not None and a.num_free == 0
    a.free(y)
    a.free(z)
    assert a.num_free == 6 and a.peak_used == 6      # peak never dropped


def test_allocator_all_or_nothing_exhaustion():
    a = BlockAllocator(num_blocks=4, block_size=8)
    got = a.alloc(3)
    assert got is not None
    assert a.alloc(2) is None                 # only 1 free: no partial grant
    assert a.num_free == 1                    # failed alloc takes nothing
    assert a.alloc(1) is not None
    assert a.alloc(1) is None


def test_allocator_double_free_rejected():
    # RuntimeError, not assert: the guard must survive `python -O`
    a = BlockAllocator(num_blocks=3, block_size=2)
    x = a.alloc(2)
    a.free(x)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(x)
    with pytest.raises(RuntimeError, match="within batch"):
        a.free([a.alloc(1)[0]] * 2)


def test_allocator_blocks_for():
    a = BlockAllocator(num_blocks=8, block_size=16)
    assert a.blocks_for(1) == 1
    assert a.blocks_for(16) == 1
    assert a.blocks_for(17) == 2


# --------------------------------------------------------------------------
# paged decode attention vs the dense oracle
# --------------------------------------------------------------------------

def _paged_from_dense(dense_k, dense_v, lengths, *, num_blocks, block_size,
                      seed=0):
    """Scatter a dense [B, S, KV, D] cache into a shuffled block pool
    [NB, BS, KV*D] + per-slot tables (absent entries -1)."""
    rng = np.random.default_rng(seed)
    B, S, KV, D = dense_k.shape
    MB = -(-S // block_size)
    k_pool = np.zeros((num_blocks, block_size, KV, D), dense_k.dtype)
    v_pool = np.zeros_like(k_pool)
    tables = np.full((B, MB), -1, np.int32)
    free = list(rng.permutation(num_blocks))
    for b in range(B):
        for e in range(-(-int(lengths[b]) // block_size)):
            blk = int(free.pop())
            tables[b, e] = blk
            sl = dense_k[b, e * block_size:(e + 1) * block_size]
            k_pool[blk, :len(sl)] = sl
            sl = dense_v[b, e * block_size:(e + 1) * block_size]
            v_pool[blk, :len(sl)] = sl
    flat = (num_blocks, block_size, KV * D)
    return k_pool.reshape(flat), v_pool.reshape(flat), tables


def _stacked(pool, layer, seed=0):
    """`pool` as layer `layer` of a 3-layer stack whose other layers hold
    noise: a kernel that reads the wrong layer cannot pass."""
    rng = np.random.default_rng(seed)
    layers = [rng.standard_normal(pool.shape).astype(pool.dtype)
              for _ in range(3)]
    layers[layer] = pool
    return jnp.asarray(np.stack(layers))


@pytest.mark.parametrize("B,H,KV,D,BS,lengths", [
    (3, 4, 2, 16, 8, (5, 33, 17)),            # GQA, ragged
    (2, 4, 4, 16, 16, (1, 31)),               # MHA, length-1 edge
    (1, 8, 2, 32, 8, (40,)),                  # exactly full blocks
])
def test_paged_decode_matches_dense_oracle(B, H, KV, D, BS, lengths):
    """Paged reference AND Pallas paged kernel (interpret mode) == dense
    decode_attention_ref for ragged per-slot lengths."""
    rng = np.random.default_rng(11)
    S = -(-max(lengths) // BS) * BS
    NB = B * (-(-S // BS)) + 2
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    dk = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    dv = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    kp, vp, tab = _paged_from_dense(dk, dv, lengths, num_blocks=NB,
                                    block_size=BS)
    lengths = jnp.asarray(np.asarray(lengths, np.int32))

    want = ref.decode_attention_ref(jnp.asarray(q), jnp.asarray(dk),
                                    jnp.asarray(dv), lengths)
    got_ref = ref.paged_decode_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tab),
        lengths)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got_kernel = paged_decode_attention(
        jnp.asarray(q), _stacked(kp, 1), _stacked(vp, 1, seed=1), 1,
        jnp.asarray(tab), lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_partials_sharded_merge_matches_dense():
    """The multi-device path: each cache shard runs the paged *partials*
    kernel over its local pool slice (non-owned table entries masked to -1)
    and the T4 merge rule combines the shards — equal to the dense oracle,
    with the pool never gathered."""
    from repro.core.attention import merge_partials

    rng = np.random.default_rng(17)
    B, H, KV, D, BS = 2, 4, 2, 16, 8
    lengths = (11, 26)
    S, NB, shards = 32, 8, 2
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    dk = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    dv = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    kp, vp, tab = _paged_from_dense(dk, dv, lengths, num_blocks=NB,
                                    block_size=BS)
    lengths = jnp.asarray(np.asarray(lengths, np.int32))
    want = ref.decode_attention_ref(jnp.asarray(q), jnp.asarray(dk),
                                    jnp.asarray(dv), lengths)

    nb_loc = NB // shards
    parts = []
    for s_i in range(shards):
        start = s_i * nb_loc
        loc = tab - start
        present = (tab >= 0) & (loc >= 0) & (loc < nb_loc)
        loc = np.where(present, loc, -1).astype(np.int32)
        parts.append(ref.paged_decode_partials_ref(
            jnp.asarray(q), jnp.asarray(kp[start:start + nb_loc]),
            jnp.asarray(vp[start:start + nb_loc]), jnp.asarray(loc),
            lengths))
    # numpy mirror of the cross-device pmax/psum merge
    m_all = jnp.maximum(parts[0][1], parts[1][1])
    l_all = sum(l * jnp.exp(m - m_all) for _, m, l in parts)
    o_all = sum(o * jnp.exp(m - m_all)[..., None] for o, m, _ in parts)
    got = o_all / jnp.maximum(l_all, 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # single-shard partials + axis-free merge == the normalized kernel
    o, m, l = ref.paged_decode_partials_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tab),
        lengths)
    one = merge_partials(o, m, l, ())
    np.testing.assert_allclose(np.asarray(one), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_ops_dispatch_interpret():
    """ops.paged_decode_attention routes to the Pallas kernel under
    kernel_mode("interpret") and to the jnp oracle under "ref" — same
    numbers either way."""
    rng = np.random.default_rng(13)
    B, H, KV, D, BS = 2, 4, 2, 16, 8
    lengths = (9, 20)
    S, NB = 24, 8
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    dk = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    dv = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    kp, vp, tab = _paged_from_dense(dk, dv, lengths, num_blocks=NB,
                                    block_size=BS)
    args = (jnp.asarray(q), _stacked(kp, 2), _stacked(vp, 2, seed=1), 2,
            jnp.asarray(tab), jnp.asarray(np.asarray(lengths, np.int32)))
    with ops.kernel_mode("ref"):
        a = ops.paged_decode_attention(*args)
    with ops.kernel_mode("interpret"):
        b = ops.paged_decode_attention(*args)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    # the partials variant agrees between oracle and Pallas kernel too
    with ops.kernel_mode("ref"):
        ra = ops.paged_decode_partials(*args)
    with ops.kernel_mode("interpret"):
        rb = ops.paged_decode_partials(*args)
    for x, y in zip(ra, rb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# engine: batched prefill admission + preemption
# --------------------------------------------------------------------------

def _direct_tokens(cfg, params, prompt, n_new, max_seq=64):
    """Reference: unpadded batch-1 prefill + dense greedy decode loop."""
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


def test_batched_prefill_matches_sequential():
    """Four same-bucket prompts admitted as ONE batched prefill call produce
    exactly the tokens of four sequential unpadded prefill+decode runs."""
    cfg, params = _phi4()
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, cfg.vocab, 13, dtype=np.int32)
               for _ in range(4)]
    engine = InferenceEngine(cfg, params, batch_size=4, max_seq=64,
                             policy=FP32)
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
    done = sorted(engine.run(), key=lambda r: r.uid)
    assert len(done) == 4
    # all four shared one (bucket=16, group=4) compiled prefill
    assert engine.stats().prefill_compiles == 1
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 4) == req.output


def test_engine_pool_sized_to_active_tokens():
    """Block accounting: peak pool usage covers live tokens, not
    B x max_seq, and every block is back in the free list after retire."""
    cfg, params = _phi4()
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (6, 14)]
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, block_size=8)
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
    engine.run()
    st = engine.stats()
    dense_blocks = engine.B * (64 // 8)
    assert st.kv_pool_blocks == dense_blocks          # default capacity
    # peak usage: ceil((6+4)/8) + ceil((14+4)/8) = 2 + 3 blocks
    assert st.peak_blocks_used <= 5 < dense_blocks
    assert st.blocks_per_token >= 1.0
    assert engine.allocator.num_free == engine.allocator.num_blocks
    assert (engine.block_tables == -1).all()          # no stale table rows


def test_out_of_blocks_preemption_recovers_exactly():
    """A pool too small for the full batch forces recompute preemption; the
    preempted request is re-admitted and its final output matches the
    uncontended reference, with no leaked blocks."""
    cfg, params = _phi4()
    rng = np.random.default_rng(37)
    prompts = [rng.integers(0, cfg.vocab, 16, dtype=np.int32)
               for _ in range(3)]
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, block_size=8, kv_pool_blocks=5)
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=12))
    done = sorted(engine.run(), key=lambda r: r.uid)
    st = engine.stats()
    assert len(done) == 3
    assert st.preemptions > 0 and st.recompute_tokens > 0
    assert st.recompute_time_s > 0          # overhead split out of NAR time
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 12) == req.output
    assert engine.allocator.num_free == engine.allocator.num_blocks


def test_preemption_preserves_sampled_continuations():
    """Recompute preemption must also reproduce *sampled* sequences: the
    (seed, position)-keyed draws make the re-prefilled continuation land on
    the same tokens the uncontended engine produces."""
    cfg, params = _phi4()
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, cfg.vocab, 16, dtype=np.int32)
               for _ in range(3)]
    sampling = lambda uid: SamplingParams(temperature=1.0, seed=100 + uid)

    def run(**kw):
        engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                                 policy=FP32, **kw)
        for uid, p in enumerate(prompts):
            engine.submit(Request(uid=uid, prompt=p, max_new_tokens=10,
                                  sampling=sampling(uid)))
        return ({r.uid: r.output for r in engine.run()}, engine.stats())

    want, st_big = run()
    got, st_small = run(block_size=8, kv_pool_blocks=5)
    assert st_big.preemptions == 0
    assert st_small.preemptions > 0
    assert got == want


def test_pool_too_small_raises():
    """A single request that cannot ever fit the pool is a configuration
    error, not a hang."""
    cfg, params = _phi4()
    rng = np.random.default_rng(43)
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, block_size=8, kv_pool_blocks=2)
    engine.submit(Request(uid=0, prompt=rng.integers(0, cfg.vocab, 30,
                                                     dtype=np.int32),
                          max_new_tokens=4))
    with pytest.raises(RuntimeError, match="KV pool too small"):
        engine.run()


def test_dense_fallback_engine_parity():
    """paged=False — the layout a batch-sharded (dp > 1) mesh falls back
    to — still serves exactly through the batched-admission row scatter and
    the tables-free decode step."""
    cfg, params = _phi4()
    rng = np.random.default_rng(53)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (7, 13, 13)]
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, paged=False)
    assert engine.allocator is None and engine.layout is None
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
    done = sorted(engine.run(), key=lambda r: r.uid)
    assert len(done) == 3
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 4) == req.output
    st = engine.stats()
    assert st.kv_pool_blocks == 0 and st.pool_utilization == 0.0


def test_window_arch_keeps_dense_ring_and_frees_blocks():
    """Sliding-window layers fall back to the dense ring cache while global
    layers page; retirement still returns every block."""
    cfg = get_config("gemma3-27b").reduced()
    assert cfg.sliding_window > 0
    params = lm.init_lm(jax.random.key(1), cfg, jnp.float32)
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32)
    # only the full-context segments are paged
    segs = engine.layout.segments
    kinds = [k for k, _ in cfg.schedule]
    assert segs == tuple(k == "attn" for k in kinds)
    rng = np.random.default_rng(47)
    for uid in range(3):
        engine.submit(Request(uid=uid,
                              prompt=rng.integers(0, cfg.vocab, 9,
                                                  dtype=np.int32),
                              max_new_tokens=3))
    done = sorted(engine.run(), key=lambda r: r.uid)
    for req in done:
        assert _direct_tokens(cfg, params, req.prompt, 3) == req.output
    assert engine.allocator.num_free == engine.allocator.num_blocks


# --------------------------------------------------------------------------
# decode: the stacked pool is read in place, the new rows written after
# --------------------------------------------------------------------------

def _decode_layer_case(kv_dtype):
    """A two-layer stacked pool (int8: quantized per block and head) and a
    batch of two slots: slot 0's token opens a block (offset 0), slot 1's
    lands mid-block."""
    cfg, params = _phi4()
    KV, hd, BS, NB, L = cfg.n_kv_heads, cfg.head_dim, 4, 8, 2
    rng = np.random.default_rng(61)
    raw = [rng.standard_normal((L, NB, BS, KV, hd)).astype(np.float32)
           for _ in range(2)]
    if kv_dtype == "int8":
        pool = {}
        for key, x in zip(("k", "v"), raw):
            s = np.maximum(np.abs(x).max(axis=(2, 4)), 1e-30) / 127.0
            q = np.clip(np.round(x / s[:, :, None, :, None]), -127, 127)
            pool[key] = jnp.asarray(q.reshape(L, NB, BS, KV * hd), jnp.int8)
            pool[key + "s"] = jnp.asarray(s, jnp.float32)
    else:
        pool = {key: jnp.asarray(x.reshape(L, NB, BS, KV * hd), jnp.bfloat16)
                for key, x in zip(("k", "v"), raw)}
    tables = jnp.asarray([[3, 5, -1, -1], [0, 6, 2, -1]], jnp.int32)
    pos = jnp.asarray([4, 9], jnp.int32)
    x = jnp.asarray(rng.standard_normal((2, cfg.d_model)), jnp.float32)
    p = jax.tree.map(lambda a: a[1], params["segments"][0])["attn"]
    return cfg, p, pool, tables, pos, x


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_decode_layer_matches_write_then_attend(kv_dtype, mode):
    """One layer of the decode scan — attention over the positions before
    the token with the token folded in as one more online-softmax term,
    then the row written after the scan — equals the order it replaces:
    the token written into its block first (int8: an offset-0 row re-amaxes
    its block, a later one reuses the stored scale and clips), then every
    position up to it attended."""
    from repro.core import attention as attn
    cfg, p, pool, tables, pos, x = _decode_layer_case(kv_dtype)
    B, KV, hd = 2, cfg.n_kv_heads, cfg.head_dim
    kw = dict(plan=UNSHARDED, cfg=cfg, policy=FP32)
    with ops.kernel_mode(mode):
        y, rows = attn.attn_decode_paged(p, x, pos, pool, 1, tables, **kw)
    stacked = jax.tree.map(lambda r: jnp.stack([r, r]), rows)
    written = attn.write_decode_rows(pool, stacked, pos, tables,
                                     plan=UNSHARDED)

    # the rows as the parent's quantize-on-write stores them
    k_new, v_new = attn._decode_kv_new(p, x, pos, **kw)
    blk, off = np.asarray([5, 2]), np.asarray([0, 1])
    for key, new in (("k", k_new), ("v", v_new)):
        new = np.asarray(new, np.float32)
        row = np.asarray(written[key][1, blk, off], np.float32)
        if kv_dtype == "int8":
            s = np.asarray(written[key + "s"][1, blk])           # [B, KV]
            amax0 = np.maximum(np.abs(new[0]).max(-1), 1e-30) / 127.0
            np.testing.assert_allclose(s[0], amax0, rtol=1e-6)  # re-amaxed
            np.testing.assert_array_equal(s[1], pool[key + "s"][1, 2])
            want = np.clip(np.round(new / s[:, :, None]), -127, 127)
            np.testing.assert_array_equal(row.reshape(B, KV, hd), want)
        else:
            np.testing.assert_array_equal(
                row, np.asarray(jnp.asarray(new.reshape(B, -1), jnp.bfloat16),
                                np.float32))
    # untouched elsewhere: only the two rows changed in each layer
    changed = np.asarray(written["k"] != pool["k"]).any(axis=-1)
    assert set(zip(*np.nonzero(changed))) <= {(l, b, o) for l in (0, 1)
                                              for b, o in zip(blk, off)}

    q = attn._decode_q(p, x, pos, **kw)
    with ops.kernel_mode(mode):
        o, m, l = ops.paged_decode_partials(
            q, written["k"], written["v"], 1, tables, pos + 1,
            k_scale=written.get("ks"), v_scale=written.get("vs"))
    merged = attn.merge_partials(o, m, l, ()).reshape(B, -1)
    want = attn._decode_out_proj(p, merged, plan=UNSHARDED, policy=FP32)
    # the bf16 kernel rounds its softmax weights to bf16 for the P.V dot
    # (2^-9 relative); the folded term keeps its weight in fp32
    tol = 1e-3 if (mode, kv_dtype) == ("interpret", "bfloat16") else 2e-5
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=tol, atol=tol)


def _serve_modes(cfg, params, prompts, mode, **kw):
    """Serve `prompts` (uid 1 sampled, the rest greedy) in blocks of 4 under
    kernel mode `mode` -> {uid: tokens}."""
    with ops.kernel_mode(mode):
        engine = InferenceEngine(cfg, params, batch_size=2, max_seq=32,
                                 policy=FP32, block_size=4,
                                 prefix_cache=False, **kw)
        for uid, pr in enumerate(prompts):
            engine.submit(Request(
                uid=uid, prompt=pr, max_new_tokens=10,
                sampling=(SamplingParams(temperature=0.8, top_k=8, seed=uid)
                          if uid == 1 else SamplingParams())))
        return {r.uid: r.output for r in engine.run()}


def _parts_at_near_tie(cfg, params, prompt, got, want, rtol=2e-3):
    """True when greedy streams `got` and `want` are equal, or part where
    the float32 forward over the common prefix ranks both tokens within
    `rtol` of the largest |logit| — a near-tie that two arithmetic orders
    (the kernel rounds its softmax weights to bf16) may break apart."""
    j = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b), None)
    if j is None:
        return len(got) == len(want)
    seq = np.concatenate([prompt, want[:j]]).astype(np.int32)
    lg = np.asarray(lm.prefill_logits(params, {"tokens": jnp.asarray(seq)[None]},
                                      plan=UNSHARDED, cfg=cfg,
                                      policy=FP32)).reshape(-1)
    return abs(lg[got[j]] - lg[want[j]]) <= rtol * np.abs(lg).max()


def test_stacked_decode_kernel_matches_reference_across_blocks():
    """Ten decode steps in blocks of 4 (every slot crosses two or three
    block boundaries): the reference path serves the greedy requests the
    dense-cache oracle serves (which never touches a pool), and the paged
    kernel in interpret mode serves the reference's tokens — the sampled
    request exactly, a greedy one up to a near-tie of the float32 forward
    (this trace holds one: 0.38145 against 0.38107)."""
    cfg, params = _phi4()
    rng = np.random.default_rng(67)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 11, 3)]
    want = _serve_modes(cfg, params, prompts, "ref")
    for uid in (0, 2):
        assert want[uid] == _direct_tokens(cfg, params, prompts[uid], 10)
    got = _serve_modes(cfg, params, prompts, "interpret")
    assert got[1] == want[1]
    for uid in (0, 2):
        assert _parts_at_near_tie(cfg, params, prompts[uid], got[uid],
                                  want[uid]), (uid, got[uid], want[uid])


def test_decode_step_dispatches_stacked_kernel_per_paged_segment(
        monkeypatch):
    """Traced as if on a TPU, the paged decode step dispatches the in-place
    form of the kernel once per paged segment (one scan body each), and
    nothing takes the reference."""
    from repro.configs.base import ShapeConfig
    from repro.launch import steps
    cfg = get_config("gemma3-27b").reduced()       # local and global segments
    step = steps.make_decode_step(
        cfg, ShapeConfig("d", "decode", 64, 2), None, max_seq=64,
        with_sampling=True, paged=(16, 8))
    n_paged = sum(step.aux["paged"].segments)
    assert 0 < n_paged < len(cfg.schedule)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.reset_dispatch_record()
    try:
        jax.eval_shape(step.fn, *step.in_structs)
        rec = ops.dispatch_record()
        assert rec["paged_decode_partials.stacked"] == {"pallas": n_paged,
                                                        "ref": 0}
        assert ops.hidden_fallbacks() == []
    finally:
        ops.reset_dispatch_record()


_SHARDED_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.precision import FP32
from repro.launch.mesh import make_test_mesh
from repro.models import lm
from repro.serving import InferenceEngine, Request, SamplingParams

for arch in ("chatglm3-6b", "phi4-mini-3.8b"):
    cfg = get_config(arch).reduced()
    params = lm.init_lm(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(71)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (9, 14, 6)]
    for kv in ("bfloat16", "int8"):
        outs = []
        for mesh in (None, make_test_mesh((1, 2))):
            eng = InferenceEngine(cfg, params, batch_size=2, max_seq=32,
                                  policy=FP32, block_size=4,
                                  kv_pool_blocks=16, kv_dtype=kv,
                                  prefix_cache=False, mesh=mesh)
            if mesh is not None:
                assert eng.runner.decode_step.plan.cache_shards == 2
            for uid, p in enumerate(prompts):
                eng.submit(Request(uid=uid, prompt=p, max_new_tokens=10,
                    sampling=(SamplingParams(temperature=0.8, top_k=8,
                                             seed=uid) if uid == 1
                              else SamplingParams())))
            outs.append({r.uid: r.output for r in eng.run()
                         if r.uid != 1})
            assert eng.allocator.num_free == eng.allocator.num_blocks
        assert outs[0] == outs[1], (arch, kv, outs)
        print("OK", arch, kv, flush=True)
"""


def test_block_sharded_pool_counts_current_token_once():
    """A pool block-sharded over 2 CPU devices (the cache axis of a (1, 2)
    mesh; blocks of 4 in a 16-block pool, so slots own blocks on both
    shards and a slot's current token lies on one of them): every shard
    attends its own blocks before the token, the owning shard alone folds
    the token in, and the merged result serves the greedy tokens one
    device serves — bf16 and int8 pools, head_tp and seq_sp attention.  A
    token counted on both shards, or on neither, changes the softmax and
    the tokens.  A sampled request shares every step; its draws are not
    compared, since they depend on how the vocabulary is sharded (they
    differ between the mesh and one device with the pool untouched)."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _SHARDED_PROG % src],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.count("OK") == 4, p.stdout
