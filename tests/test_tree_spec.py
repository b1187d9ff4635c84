"""Token-tree speculative decoding: topology, mask semantics, token
identity, rollback hygiene, degrade ladder, and the draft-checkpoint
round-trip satellite.

The tentpole property is the same one single-branch speculation carries
(tests/test_spec.py) widened to trees: with multi-branch proposals
verified in ONE tree-masked target pass, every committed sequence —
greedy AND sampled — must equal step-by-step decoding exactly, because
the accepted path is re-derived from the target's own deterministic
(seed, position)-keyed choices at every node.  Identity is asserted
across the rejection-heavy mis-seeded draft (maximal rollback + KV
compaction), a mid-acceptance interpolated draft (sibling branches
actually win), chunked admission, prefix-cache-warm starts, and
recompute preemption; b=1 must reduce to the single-branch engine
byte-for-byte.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.precision import FP32
from repro.kernels.ref import paged_chunk_partials_ref
from repro.models import lm
from repro.serving import (ChunkedPrefillPolicy, DeadlinePolicy, FCFSPolicy,
                           InferenceEngine, Request, SamplingParams,
                           SpecConfig)
from repro.serving.spec import (TokenTree, accept_tree_path, build_tree,
                                resolve_draft)

# the rejection-heavy regime: a mis-seeded draft whose proposals are
# near-random over the reduced vocab — almost every round rejects at the
# root, exercising tree rollback with zero committed nodes
REJECTY_TREE = SpecConfig(draft="auto", k=3, draft_seed=1234, branches=3)


# --------------------------------------------------------------------------
# pure host-side pieces: config, topology, path acceptance
# --------------------------------------------------------------------------

def test_tree_config_validation():
    assert SpecConfig().branches == 1
    with pytest.raises(ValueError, match="branch count"):
        SpecConfig(branches=0)
    SpecConfig(branches=4)   # any width >= 1 is valid


def test_build_tree_topology():
    """The caterpillar: a primary chain plus (b-1) sibling leaves per
    depth, flattened depth-major chain-first, every prefix
    ancestor-closed."""
    t = build_tree(10, [[1, 2, 3], [4, 5, 6]])
    assert isinstance(t, TokenTree) and t.n_nodes == 7
    assert list(t.tokens) == [10, 1, 2, 3, 4, 5, 6]
    assert list(t.depth) == [0, 1, 1, 1, 2, 2, 2]
    # depth-1 nodes hang off the root; depth-2 nodes hang off depth 1's
    # CHAIN node (node 1) — siblings are leaves, only the chain extends
    assert list(t.parent[1:]) == [0, 0, 0, 1, 1, 1]
    assert list(t.chain) == [True, True, False, False, True, False, False]
    # ancestor rows: self + the path to the root, nothing else
    anc = np.asarray(t.anc)
    assert anc[0].tolist() == [True] + [False] * 6
    assert anc[2].tolist() == [True, False, True, False, False, False, False]
    assert anc[5].tolist() == [True, True, False, False, False, True, False]
    # ancestor closure: every ancestor's ancestors are mine too
    for i in range(t.n_nodes):
        for j in np.flatnonzero(anc[i]):
            assert (anc[i] | anc[j]).tolist() == anc[i].tolist()


def test_build_tree_single_branch_degenerates_to_chain():
    """b=1 trees ARE the PR-5 chain chunk: depth[i] == i and the ancestor
    matrix is exactly lower-triangular (causal)."""
    t = build_tree(7, [[3], [9], [4]])
    assert list(t.tokens) == [7, 3, 9, 4]
    assert list(t.depth) == list(range(4))
    assert all(t.chain)
    assert np.array_equal(np.asarray(t.anc), np.tril(np.ones((4, 4), bool)))


def test_accept_tree_path():
    t = build_tree(10, [[1, 2, 3], [4, 5, 6]])
    tok, par, n = t.tokens, t.parent, t.n_nodes
    # target chooses the chain token then a sibling: descend 0 -> 1 -> 5
    choices = np.zeros(n, np.int64)
    choices[0], choices[1] = 1, 5
    assert accept_tree_path(tok, par, choices, n) == [1, 5]
    # target chooses a sibling at depth 1: siblings have no children, so
    # the path ends there even if deeper tokens would have matched
    choices[0] = 3
    assert accept_tree_path(tok, par, choices, n) == [3]
    # no child carries the target's choice: empty path (round commits
    # only the target's own bonus token)
    choices[0] = 99
    assert accept_tree_path(tok, par, choices, n) == []
    # full chain walk-through
    choices[0], choices[1], choices[4] = 1, 4, 77
    assert accept_tree_path(tok, par, choices, n) == [1, 4]


# --------------------------------------------------------------------------
# kernel oracle: tree-mask semantics
# --------------------------------------------------------------------------

def _chunk_inputs(rng, B, C, pos0, *, H=4, D=8, KV=2, BS=4, NB=8, MB=4):
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(NB, BS, KV * D)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(NB, BS, KV * D)), jnp.float32)
    tables = jnp.asarray(
        [[b * MB + i for i in range(MB)] for b in range(B)], jnp.int32)
    q_pos = jnp.asarray(np.asarray(pos0)[:, None] + np.arange(C)[None, :],
                        jnp.int32)
    lengths = jnp.asarray(np.asarray(pos0) + C, jnp.int32)
    return q, k_pool, v_pool, tables, q_pos, lengths


def test_tree_mask_chain_degeneracy_bitwise():
    """A lower-triangular tree_mask must reproduce the plain causal chunk
    mask BIT-exactly — the masked score set is identical, so every fp op
    downstream sees the same operands (the b=1 == PR-5 guarantee at the
    kernel layer)."""
    rng = np.random.default_rng(11)
    B, C = 2, 4
    args = _chunk_inputs(rng, B, C, [5, 2])
    tri = jnp.broadcast_to(jnp.tril(jnp.ones((C, C), bool)), (B, C, C))
    o0, m0, l0 = paged_chunk_partials_ref(*args)
    o1, m1, l1 = paged_chunk_partials_ref(*args, tree_mask=tri)
    assert np.array_equal(np.asarray(o0), np.asarray(o1))
    assert np.array_equal(np.asarray(m0), np.asarray(m1))
    assert np.array_equal(np.asarray(l0), np.asarray(l1))


def test_tree_mask_blinds_siblings():
    """Mask semantics: a node attends its ancestors and the committed
    prefix, NEVER a sibling — perturbing one sibling's KV row must leave
    every non-descendant node's output bit-identical."""
    rng = np.random.default_rng(12)
    B, C = 1, 4
    t = build_tree(10, [[1, 2, 3]])          # root + three depth-1 leaves
    anc = jnp.asarray(np.asarray(t.anc)[None], bool)
    q, k_pool, v_pool, tables, q_pos, lengths = _chunk_inputs(
        rng, B, C, [5])
    out = paged_chunk_partials_ref(q, k_pool, v_pool, tables, q_pos,
                                   lengths, tree_mask=anc)
    # clobber the KV rows of node 1 (position pos0+1 = 6 -> block 1 off 2)
    k2 = k_pool.at[1, 2].add(100.0)
    v2 = v_pool.at[1, 2].add(100.0)
    out2 = paged_chunk_partials_ref(q, k2, v2, tables, q_pos, lengths,
                                    tree_mask=anc)
    for a, b in zip(out, out2):
        a, b = np.asarray(a), np.asarray(b)
        # node 1 sees its own perturbed row; the root (its parent) and
        # its siblings 2, 3 must not
        assert not np.array_equal(a[:, 1], b[:, 1])
        for node in (0, 2, 3):
            assert np.array_equal(a[:, node], b[:, node]), node


# --------------------------------------------------------------------------
# end-to-end identity
# --------------------------------------------------------------------------

_PARAMS_CACHE = {}


def _reduced(arch):
    if arch not in _PARAMS_CACHE:
        cfg = get_config(arch).reduced()
        _PARAMS_CACHE[arch] = (cfg, lm.init_lm(jax.random.key(0), cfg,
                                               jnp.float32))
    return _PARAMS_CACHE[arch]


def _trace(cfg, lens, *, max_new=7, sampled=()):
    rng = np.random.default_rng(29)
    reqs = []
    for uid, n in enumerate(lens):
        reqs.append(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
            max_new_tokens=max_new,
            sampling=SamplingParams(temperature=0.8, top_k=8, seed=uid)
            if uid in sampled else SamplingParams()))
    return reqs


def _run(cfg, params, reqs, **kw):
    engine = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                             policy=FP32, **kw)
    for r in reqs:
        engine.submit(r)
    done = {t.uid: t.output for t in engine.run()}
    return engine, done


def _midrange_draft(cfg, alpha=0.1):
    """Interpolate the truncated-target draft (seed 0 reproduces the
    target's own init -> ~100% acceptance on reduced configs) toward a
    decorrelated init: top-1 is wrong often enough to reject while the
    top-b set still carries the target's choice — the regime where
    sibling branches win (benchmarks/serving_bench.py uses the same
    construction for the tree gate)."""
    dcfg = resolve_draft(SpecConfig(draft="auto"), cfg)
    p0 = lm.init_lm(jax.random.key(0), dcfg, jnp.float32)
    p1 = lm.init_lm(jax.random.key(1234), dcfg, jnp.float32)
    return jax.tree.map(lambda a, b: (1 - alpha) * a + alpha * b, p0, p1)


@pytest.mark.parametrize("arch", ["gpt-j", "gpt3-xl", "phi4-mini-3.8b"])
def test_greedy_token_identity_tree(arch):
    """Greedy decode with tree speculation on is token-identical to
    speculation off, under the rejection-heavy draft (every round walks
    commit -> compact -> rollback)."""
    cfg, params = _reduced(arch)
    lens = (5, 12, 9)
    base = _run(cfg, params, _trace(cfg, lens))[1]
    eng, got = _run(cfg, params, _trace(cfg, lens), spec=REJECTY_TREE)
    st = eng.stats()
    assert got == base, f"{arch} diverged under tree speculation"
    assert eng.runner.tree_branches == 3
    assert st.spec_rounds > 0 and st.spec_tree_nodes > 0
    # node counts accumulate per slot-round (trees shrink near the
    # max_new horizon, but most rounds verify a root + k*b-node tree)
    assert st.spec_tree_nodes > st.spec_slot_steps
    # pool fully drained — tree verify writes + compaction + rollback
    # leak no blocks
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_sampled_lossless_parity_tree():
    """Sampled requests (fixed seeds) are exactly reproduced through the
    tree: acceptance re-derives the target's deterministic
    (seed, position)-keyed draws at every node, so a sibling only wins
    when it carries the token the target would have sampled anyway."""
    cfg, params = _reduced("gpt-j")
    lens = (6, 14, 10, 8)
    reqs = lambda: _trace(cfg, lens, sampled=(0, 1, 3))
    base = _run(cfg, params, reqs())[1]
    for b in (2, 3):
        _, got = _run(cfg, params, reqs(),
                      spec=SpecConfig(draft="auto", k=3, draft_seed=1234,
                                      branches=b))
        assert got == base, f"b={b} diverged"


def test_single_branch_engine_is_the_chain_engine():
    """branches=1 must take the single-branch code path wholesale: the
    chain steps are built, the tree steps are not, no tree telemetry
    accrues, and outputs equal the explicit PR-5 config's."""
    cfg, params = _reduced("gpt-j")
    lens = (5, 9)
    chain_spec = SpecConfig(draft="auto", k=3, draft_seed=1234)
    eng1, got1 = _run(cfg, params, _trace(cfg, lens), spec=chain_spec)
    eng2, got2 = _run(cfg, params, _trace(cfg, lens),
                      spec=SpecConfig(draft="auto", k=3, draft_seed=1234,
                                      branches=1))
    assert eng2.runner.tree_branches == 1
    assert eng2.runner.tree_verify_step is None
    assert eng2.runner.draft_topk_step is None
    assert eng2.runner.draft_decode_step is not None
    assert got1 == got2
    assert eng2.stats().spec_tree_nodes == 0


def test_tree_branches_actually_win_on_midrange_draft():
    """With a mid-acceptance draft the tree's sibling branches must
    rescue rounds the chain loses: sibling acceptances occur, identity
    holds, and accepted tokens per slot-round don't regress vs the
    chain at equal k."""
    cfg, params = _reduced("gpt-j")
    dparams = _midrange_draft(cfg)
    lens = (6, 9, 7, 11)
    base = _run(cfg, params, _trace(cfg, lens, max_new=12))[1]
    res = {}
    for b in (1, 3):
        eng, got = _run(cfg, params, _trace(cfg, lens, max_new=12),
                        spec=SpecConfig(draft="auto", k=3, branches=b),
                        draft_params=dparams)
        st = eng.stats()
        assert got == base, f"b={b} diverged"
        res[b] = (st.spec_accepted_tokens / max(1, st.spec_slot_steps),
                  st.spec_branch_hits)
        assert eng.allocator.num_free == eng.allocator.num_blocks
    assert res[3][1] > 0, "no sibling branch ever accepted"
    assert res[3][0] >= res[1][0], (
        f"tree accepted/round {res[3][0]:.3f} regressed vs chain "
        f"{res[1][0]:.3f}")


def test_tree_rollback_leak_free_and_bounded():
    """A rejection-heavy draft rolls whole trees back every round, and
    accepted paths compact KV rows within the slot's own blocks: the
    pool's peak stays within capacity and fully drains (no block leaked
    or double-freed by verify-write + compact + trailing-free cycles)."""
    cfg, params = _reduced("gpt-j")
    eng, _ = _run(cfg, params, _trace(cfg, (9, 17), max_new=12),
                  spec=REJECTY_TREE, block_size=4)
    st = eng.stats()
    assert st.spec_proposed_tokens > st.spec_accepted_tokens
    assert st.peak_blocks_used <= eng.allocator.num_blocks
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_random_tree_traces_drain_pool():
    """Property test: random traces (lengths, sampling mix, widths)
    through tree engines always drain the pool exactly and keep token
    identity — the block-accounting invariant under arbitrary
    accept/compact/rollback interleavings."""
    hyp = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed in this env")
    from hypothesis import given, settings, strategies as st_

    cfg, params = _reduced("gpt-j")

    @settings(max_examples=5, deadline=None)
    @given(st_.lists(st_.integers(4, 20), min_size=1, max_size=3),
           st_.integers(2, 3), st_.booleans())
    def run(lens, branches, sample_first):
        sampled = (0,) if sample_first else ()
        base = _run(cfg, params,
                    _trace(cfg, tuple(lens), sampled=sampled))[1]
        eng, got = _run(cfg, params,
                        _trace(cfg, tuple(lens), sampled=sampled),
                        spec=SpecConfig(draft="auto", k=2, draft_seed=1234,
                                        branches=branches),
                        block_size=4)
        assert got == base
        assert eng.allocator.num_free == eng.allocator.num_blocks

    run()


def test_tree_with_chunked_prefill_mix():
    """Tree speculation + ChunkedPrefillPolicy: long prompts chunk into
    their paged blocks while seated slots run tree rounds; outputs match
    plain FCFS with speculation off."""
    cfg, params = _reduced("phi4-mini-3.8b")
    lens = (5, 40, 12, 33)
    base = _run(cfg, params, _trace(cfg, lens, sampled=(1,)),
                scheduler=FCFSPolicy())[1]
    eng, got = _run(cfg, params, _trace(cfg, lens, sampled=(1,)),
                    scheduler=ChunkedPrefillPolicy(16), spec=REJECTY_TREE)
    st = eng.stats()
    assert st.prefill_chunks >= 5 and st.spec_rounds > 0
    assert got == base
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_tree_with_prefix_cache_warm_start():
    """Prefix-cache-warm admissions + tree rounds: wave 2 reuses wave 1's
    cached prompt blocks (COW — tree verify writes must never land in a
    shared block) and still commits identical tokens."""
    cfg, params = _reduced("gpt-j")
    rng = np.random.default_rng(31)
    shared = rng.integers(0, cfg.vocab, 24, dtype=np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab, 2 + u % 3,
                                            dtype=np.int32)])
               for u in range(3)]

    def wave():   # fresh Request objects over the same prompt arrays
        return [Request(uid=u, prompt=p, max_new_tokens=6,
                        sampling=SamplingParams())
                for u, p in enumerate(prompts)]

    base = _run(cfg, params, wave())[1]
    eng = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                          policy=FP32, spec=REJECTY_TREE,
                          prefix_cache=True)
    for r in wave():
        eng.submit(r)
    eng.run()
    for r in wave():
        eng.submit(r)
    got = {t.uid: t.output for t in eng.run()}
    st = eng.stats()
    assert st.prefix_hits > 0, "wave 2 never hit the prefix cache"
    assert got == base


def test_tree_preemption_then_resume_parity():
    """Recompute preemption under a starved pool with trees on: the
    budget-capped lookahead falls back to chain-width reservations
    instead of deadlocking, evicted requests re-prefill and continue
    token-exactly, and the pool drains."""
    cfg, params = _reduced("phi4-mini-3.8b")
    lens = (5, 11, 7, 16)
    reqs = lambda: _trace(cfg, lens, max_new=9, sampled=(1, 3))
    base = _run(cfg, params, reqs())[1]
    eng, got = _run(cfg, params, reqs(), spec=REJECTY_TREE,
                    block_size=8, kv_pool_blocks=5)
    st = eng.stats()
    assert st.preemptions > 0
    assert got == base
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_int8_kv_pool_forces_single_branch():
    """int8 paged KV pins rows to per-block scales, so tree compaction
    (raw row moves) is unsound — the runner must drop to the chain path
    rather than corrupt scales."""
    cfg, params = _reduced("gpt-j")
    eng = InferenceEngine(cfg, params, batch_size=2, max_seq=64,
                          policy=FP32, kv_dtype="int8",
                          spec=SpecConfig(draft="auto", k=2, branches=3))
    assert eng.runner.tree_branches == 1
    assert eng.runner.tree_verify_step is None


# --------------------------------------------------------------------------
# degrade ladder (DeadlinePolicy rung 1: chain-only; rung 2: spec off)
# --------------------------------------------------------------------------

def test_degrade_ladder_two_rungs():
    p = DeadlinePolicy(degrade_depth=1.0)
    # thresh = degrade_depth * n_slots = 2 for two slots
    assert p.degrade_level(0, 2) == 0
    assert p.degrade_level(2, 2) == 0      # at the threshold: full service
    assert p.degrade_level(3, 2) == 1      # rung 1: trees -> chains
    assert p.degrade_level(4, 2) == 1
    assert p.degrade_level(5, 2) == 2      # rung 2: spec off (sticky)
    # the chunk halving rides rung 1 and does not double at rung 2
    p2 = DeadlinePolicy(chunk_tokens=32, degrade_depth=1.0)
    assert p2.effective_chunk_tokens(0) == 32
    assert p2.effective_chunk_tokens(1) == 16
    assert p2.effective_chunk_tokens(2) == 16


def test_degrade_ladder_is_lossless_end_to_end():
    """A backlog deep enough to ride both rungs: requests get admitted
    chain-only and spec-off along the way, yet every committed sequence
    equals the un-degraded baseline (the ladder trades speed, never
    tokens)."""
    cfg, params = _reduced("gpt-j")
    lens = (5, 8, 6, 9, 7, 10)
    base = _run(cfg, params, _trace(cfg, lens, max_new=6))[1]
    eng, got = _run(cfg, params, _trace(cfg, lens, max_new=6),
                    spec=REJECTY_TREE,
                    scheduler=DeadlinePolicy(degrade_depth=0.25))
    st = eng.stats()
    assert got == base
    assert st.requests_degraded > 0, "backlog never tripped the ladder"
    assert eng.allocator.num_free == eng.allocator.num_blocks
    # the round-scoped rung-1 flag is reset once the backlog drains
    assert eng.runner._tree_chain_only is False


# --------------------------------------------------------------------------
# draft-checkpoint round trip (satellite: checkpoint/ -> serving)
# --------------------------------------------------------------------------

def test_draft_checkpoint_round_trip_token_identity(tmp_path):
    """save -> load -> serve: a draft restored from a Checkpointer
    directory drives byte-identical speculation to the same params passed
    in memory (and both stay lossless vs no speculation)."""
    from repro.checkpoint.checkpointer import Checkpointer

    cfg, params = _reduced("gpt-j")
    dparams = _midrange_draft(cfg)
    Checkpointer(str(tmp_path)).save(dparams, 0)
    lens = (6, 10, 8)
    spec = lambda: SpecConfig(draft="auto", k=3, branches=3)
    base = _run(cfg, params, _trace(cfg, lens, sampled=(2,)))[1]
    eng_mem, got_mem = _run(cfg, params, _trace(cfg, lens, sampled=(2,)),
                            spec=spec(), draft_params=dparams)
    eng_ckpt, got_ckpt = _run(cfg, params, _trace(cfg, lens, sampled=(2,)),
                              spec=spec(), draft_checkpoint=str(tmp_path))
    assert got_ckpt == got_mem == base
    # same draft -> same proposals -> same acceptance trajectory
    assert (eng_ckpt.stats().spec_accepted_tokens
            == eng_mem.stats().spec_accepted_tokens)


def test_draft_checkpoint_validation(tmp_path):
    cfg, params = _reduced("gpt-j")
    with pytest.raises(ValueError, match="SpecConfig"):
        InferenceEngine(cfg, params, batch_size=2, max_seq=64, policy=FP32,
                        draft_checkpoint=str(tmp_path))
    with pytest.raises(ValueError, match="not both"):
        InferenceEngine(cfg, params, batch_size=2, max_seq=64, policy=FP32,
                        spec=SpecConfig(draft="auto"),
                        draft_params=_midrange_draft(cfg),
                        draft_checkpoint=str(tmp_path))


# --------------------------------------------------------------------------
# telemetry surface
# --------------------------------------------------------------------------

def test_tree_stats_surface():
    """spec_tree_nodes / accepted-path-depth percentiles / branch
    utilization populate, serialize, and stay internally consistent."""
    cfg, params = _reduced("gpt-j")
    eng, _ = _run(cfg, params, _trace(cfg, (5, 9), max_new=8),
                  spec=SpecConfig(draft="auto", k=3, branches=3),
                  draft_params=_midrange_draft(cfg))
    st = eng.stats()
    assert st.spec_tree_nodes > 0
    assert 0.0 <= st.spec_branch_utilization <= 1.0
    # accepted-path depth is the number of accepted tree nodes: 0..k
    assert 0.0 <= st.spec_path_depth_p50 <= st.spec_path_depth_p95 <= 3.0
    d = st.to_dict()
    for key in ("spec_tree_nodes", "spec_branch_hits",
                "spec_branch_utilization", "spec_path_depth_p50",
                "spec_path_depth_p95"):
        assert key in d
    assert "tree" in st.summary()
