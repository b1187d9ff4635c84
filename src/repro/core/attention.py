"""Multi-head attention in manual-SPMD form (paper T2 + T3 + T4).

All functions run *inside* the step's `shard_map` (launch/steps.py) and
consult the static `Plan` for axis names.  With an empty plan (no mesh) every
collective degrades to identity, so the same code runs unsharded in tests.

Sharding schemes (train / prefill):
  head_tp   residual seq-sharded -> all-gather x over `model` (Megatron-SP),
            Q heads sharded over `model`, K/V computed column-sharded and
            re-gathered (cheap under GQA), flash attention per head shard,
            out-projection contracted on local heads -> reduce-scatter back
            to sequence-sharded.  The concatenated head tensor never exists
            (paper T3); the reduce-scatter *is* the paper's log-tree
            cluster-to-cluster reduction (a literal binary-tree schedule is
            selectable via core.collectives.set_reduce_method("tree")).
  seq_sp    for n_heads % tp != 0 (phi4 24H, hymba 25H, whisper 8H):
            Q stays sequence-sharded with full weights, K/V all-gathered over
            the sequence axis, flash attention with a query-position offset.

Decode (AR): the KV cache is *sequence-sharded* over `plan.cache_axes`; every
device attends its cache chunk producing online-softmax partials (m, l, o)
which are merged with the cross-device distributed-softmax rule (paper T4).
Weights stay tensor-parallel; only O(B·H·hd) activations cross the wire.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import collectives as col
from repro.core.nn import act_dtype, fused_pdot, gather_w, pdot
from repro.core.precision import Policy
from repro.core.rope import apply_rope
from repro.kernels import ops
from repro.kernels.epilogue import Epilogue
from repro.sharding.plan import Plan

NEG_INF = -1e30
CACHE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def attention_param_dims(cfg) -> dict:
    return {"wq": ("fsdp", "tp"), "wk": ("fsdp", "tp"),
            "wv": ("fsdp", "tp"), "wo": ("tp", "fsdp")}


def attention_param_shapes(cfg) -> dict:
    E, H, hd, KV = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_kv_heads
    return {"wq": (E, H * hd), "wk": (E, KV * hd),
            "wv": (E, KV * hd), "wo": (H * hd, E)}


def init_attention(key, cfg, dtype):
    shapes = attention_param_shapes(cfg)
    ks = jax.random.split(key, len(shapes))
    return {n: (jax.random.normal(k, s) * 0.02).astype(dtype)
            for (n, s), k in zip(sorted(shapes.items()), ks)}


# --------------------------------------------------------------------------
# static head layout
# --------------------------------------------------------------------------

class KVLayout(NamedTuple):
    n_kv_loc: int       # kv heads each device holds for attention
    aligned: bool       # True: the column shard of wk IS the local kv heads


def kv_layout(cfg, tp: int) -> KVLayout:
    H, KV, G = cfg.n_heads, cfg.n_kv_heads, cfg.q_per_kv
    if tp == 1:
        return KVLayout(KV, True)
    assert H % tp == 0, (H, tp)
    if KV % tp == 0:
        return KVLayout(KV // tp, True)
    h_loc = H // tp
    n_loc = max(1, h_loc // G)
    for i in range(tp):           # no q-head group may straddle kv shards
        lo, hi = (i * h_loc) // G, (i * h_loc + h_loc - 1) // G
        assert hi - lo + 1 <= n_loc, (
            f"kv heads straddle shards: tp={tp} H={H} KV={KV}")
    return KVLayout(n_loc, False)


def _first_kv(cfg, tp, tp_axes):
    """Traced index of this device's first kv head (unaligned layout)."""
    h_loc = cfg.n_heads // tp
    return (col.axis_index(tp_axes) * h_loc) // cfg.q_per_kv


def _attention_fn(plan: Plan):
    """Flash kernel (optimized, T2) or naive full-materialization reference
    (the paper's baseline implementation — benchmarks/ablation)."""
    if plan.naive_attention:
        from repro.kernels.ref import attention_ref
        return attention_ref
    return ops.flash_attention


# --------------------------------------------------------------------------
# distributed softmax merge (T4) — manual-SPMD variant
# --------------------------------------------------------------------------

def merge_partials(o, m, l, axes):
    """Merge per-shard online-softmax partials across `axes`.
    o: [..., D] unnormalized; m, l: [...] running max / sum-exp (fp32)."""
    if not axes:
        return o / jnp.maximum(l, 1e-30)[..., None]
    m_all = col.pmax(jax.lax.stop_gradient(m), axes)   # stabilizer only
    corr = jnp.exp(m - m_all)
    l_all = col.psum(l * corr, axes)
    o_all = col.psum(o * corr[..., None], axes)
    return o_all / jnp.maximum(l_all, 1e-30)[..., None]


def decode_partials(q, k_loc, v_loc, valid, *, sm_scale):
    """One-token attention over a local cache chunk -> (o, m, l) partials.
    q: [B, H, D]; k/v_loc: [B, Sl, KV, D]; valid: [B, Sl] bool.  GEMMs in
    operand dtype (fp32 accumulation), statistics fp32 (paper T6)."""
    B, H, D = q.shape
    KV = k_loc.shape[2]
    G = H // KV
    qr = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_loc.astype(q.dtype),
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype),
                   v_loc.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)


# --------------------------------------------------------------------------
# KV-cache construction (prefill)
# --------------------------------------------------------------------------

def ring_from_full(k_full, window: int):
    """Arrange the last `window` positions of [B, S, KV, hd] into ring-buffer
    order (slot = pos % window).  S < window pads at the tail (masked by pos
    validity at decode)."""
    B, S = k_full.shape[:2]
    if S >= window:
        tail = k_full[:, S - window:]
        return jnp.roll(tail, shift=S % window, axis=1)
    pad = window - S
    return jnp.pad(k_full, ((0, 0), (0, pad), (0, 0), (0, 0)))


def cache_slice(k_full, plan: Plan):
    """Slice this device's cache-sequence chunk from a fully-gathered
    [B, W, KV, hd] tensor."""
    W = k_full.shape[1]
    shards = plan.cache_shards
    chunk = W // shards
    start = col.axis_index(plan.cache_axes) * chunk
    return jax.lax.dynamic_slice_in_dim(k_full, start, chunk, axis=1)


def build_cache(k_full, v_full, plan: Plan, *, window: int, cache_len: int):
    """-> {"k","v"} local shards [B, cache_len/shards, KV, hd]
    (plan.kv_cache_dtype).  window > 0 => ring cache of `window` slots."""
    S = k_full.shape[1]
    if window > 0:
        k_full = ring_from_full(k_full, window)
        v_full = ring_from_full(v_full, window)
    elif S < cache_len:
        pad = cache_len - S
        k_full = jnp.pad(k_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_full = jnp.pad(v_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cd = jnp.dtype(plan.kv_cache_dtype)
    return {"k": cache_slice(k_full.astype(cd), plan),
            "v": cache_slice(v_full.astype(cd), plan)}


# --------------------------------------------------------------------------
# full-sequence attention (train / prefill / encoder)
# --------------------------------------------------------------------------

def attn_full(p, x, *, plan: Plan, cfg, policy: Policy, causal: bool,
              window: int, with_cache: bool = False, cache_len: int = 0,
              memory=None, memory_len: int = 0, norm=None, residual=None):
    """x: [B, S_loc, E] sequence-sharded.  `memory`: cross-attention source
    [B, Sm_loc, E] (whisper decoder).  Returns (y [B, S_loc, E], cache|None).

    Fused pipeline operands (plan.fuse_epilogues):
      `norm`      kernels.epilogue.Prologue — x arrives UN-normalized and
                  the pre-norm fuses into the Q/K/V projection GEMMs (the
                  cross-attention memory is never normalized, matching the
                  unfused chain).
      `residual`  [B, S_loc, E] residual stream — folded into the output
                  projection's epilogue when no tp-partial reduction is
                  pending, added after the collective otherwise.  When
                  given, the first return value is the UPDATED residual
                  stream (residual + attn out), not the raw sub-layer out.
    """
    scheme = plan.attention_sharding
    if memory is not None or scheme == "seq_sp" or plan.tp == 1:
        return _attn_seq_sp(p, x, plan=plan, cfg=cfg, policy=policy,
                            causal=causal, window=window,
                            with_cache=with_cache, cache_len=cache_len,
                            memory=memory, memory_len=memory_len,
                            norm=norm, residual=residual)
    return _attn_head_tp(p, x, plan=plan, cfg=cfg, policy=policy,
                         causal=causal, window=window,
                         with_cache=with_cache, cache_len=cache_len,
                         norm=norm, residual=residual)


def _attn_head_tp(p, x, *, plan, cfg, policy, causal, window,
                  with_cache, cache_len, norm=None, residual=None):
    tp, tp_ax = plan.tp, plan.tp_axes
    B, S_loc, E = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h_loc = H // tp
    ad = act_dtype(policy)
    lay = kv_layout(cfg, tp)

    gather = col.all_gather_fp8 if plan.comm_fp8 else col.all_gather
    x_full = gather(x, plan.seq_axes, axis=1)                  # [B, S, E]
    S = x_full.shape[1]
    positions = jnp.arange(S)

    wq = gather_w(p["wq"], plan)                               # [E, h_loc*hd]
    q = fused_pdot(x_full, wq, policy,
                   prologue=norm).reshape(B, S, h_loc, hd)
    q = apply_rope(q, positions, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)

    kp = fused_pdot(x_full, gather_w(p["wk"], plan), policy,
                    prologue=norm)                             # [B,S,KVhd/tp]
    vp = fused_pdot(x_full, gather_w(p["wv"], plan), policy, prologue=norm)
    need_full_kv = with_cache or not lay.aligned
    if need_full_kv and tp > 1:
        k_full = col.all_gather(kp, tp_ax, axis=-1).reshape(B, S, KV, hd)
        v_full = col.all_gather(vp, tp_ax, axis=-1).reshape(B, S, KV, hd)
    else:
        k_full = kp.reshape(B, S, -1, hd)
        v_full = vp.reshape(B, S, -1, hd)
    k_full = apply_rope(k_full, positions, theta=cfg.rope_theta,
                        fraction=cfg.rope_fraction)

    if lay.aligned and not (need_full_kv and tp > 1):
        k_loc, v_loc = k_full, v_full
    elif lay.aligned:
        i = col.axis_index(tp_ax)
        k_loc = jax.lax.dynamic_slice_in_dim(
            k_full, i * lay.n_kv_loc, lay.n_kv_loc, axis=2)
        v_loc = jax.lax.dynamic_slice_in_dim(
            v_full, i * lay.n_kv_loc, lay.n_kv_loc, axis=2)
    else:
        first = _first_kv(cfg, tp, tp_ax)
        k_loc = jax.lax.dynamic_slice_in_dim(k_full, first, lay.n_kv_loc, axis=2)
        v_loc = jax.lax.dynamic_slice_in_dim(v_full, first, lay.n_kv_loc, axis=2)

    out = _attention_fn(plan)(q.astype(ad), k_loc.astype(ad),
                              v_loc.astype(ad), causal=causal, window=window)
    o = out.reshape(B, S, h_loc * hd)

    wo = gather_w(p["wo"], plan, fsdp_dim=1)                   # [h_loc*hd, E]
    # head_tp only runs with tp > 1 (attn_full routes tp == 1 to seq_sp),
    # so a tp-partial reduction is always pending: the residual add lands
    # after the reduce-scatter, never in the GEMM epilogue
    part = pdot(o, wo, policy)                                 # partial over tp
    y = col.psum_scatter(part, tp_ax, scatter_dimension=1)     # T3
    if residual is not None:
        y = residual + y

    cache = None
    if with_cache:
        cache = build_cache(k_full, v_full, plan, window=window,
                            cache_len=cache_len)
    return y, cache


def _attn_seq_sp(p, x, *, plan, cfg, policy, causal, window, with_cache,
                 cache_len, memory=None, memory_len=0, norm=None,
                 residual=None):
    sp_ax = plan.seq_axes
    B, S_loc, E = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ad = act_dtype(policy)

    off = col.axis_index(sp_ax) * S_loc
    q_pos = jnp.arange(S_loc) + off

    wq = gather_w(p["wq"], plan, tp_dim=1)                     # full [E, H*hd]
    q = fused_pdot(x, wq, policy, prologue=norm).reshape(B, S_loc, H, hd)
    q = apply_rope(q, q_pos, theta=cfg.rope_theta, fraction=cfg.rope_fraction)

    src = x if memory is None else memory
    src_norm = norm if memory is None else None   # memory is never normed
    Sm_loc = src.shape[1]
    k_loc = fused_pdot(src, gather_w(p["wk"], plan, tp_dim=1), policy,
                       prologue=src_norm)
    v_loc = fused_pdot(src, gather_w(p["wv"], plan, tp_dim=1), policy,
                       prologue=src_norm)
    k_loc = k_loc.reshape(B, Sm_loc, KV, hd)
    v_loc = v_loc.reshape(B, Sm_loc, KV, hd)
    if memory is None:
        k_loc = apply_rope(k_loc, q_pos, theta=cfg.rope_theta,
                           fraction=cfg.rope_fraction)
    gather = col.all_gather_fp8 if plan.comm_fp8 else col.all_gather
    k_full = gather(k_loc, sp_ax, axis=1)                      # [B, Sm, KV, hd]
    v_full = gather(v_loc, sp_ax, axis=1)

    out = _attention_fn(plan)(q.astype(ad), k_full.astype(ad),
                              v_full.astype(ad), causal=causal,
                              window=window, q_offset=off)
    o = out.reshape(B, S_loc, H * hd)

    wo = gather_w(p["wo"], plan, fsdp_dim=1, tp_dim=0)         # full [H*hd, E]
    if residual is not None:    # no collective pending: fuse the residual
        y = fused_pdot(o, wo, policy,
                       epilogue=Epilogue(residual=residual, out_dtype=ad))
    else:
        y = pdot(o, wo, policy)                                # stays sharded

    cache = None
    if with_cache:
        cache = build_cache(k_full, v_full, plan, window=window,
                            cache_len=cache_len)
    return y, cache


# --------------------------------------------------------------------------
# AR decode (T4: sequence-sharded cache + distributed softmax)
# --------------------------------------------------------------------------

def _decode_q(p, x, pos, *, plan: Plan, cfg, policy: Policy, norm=None):
    """Projected + rotated query for one decode step: [B, H, hd].
    `norm`: fused pre-norm prologue (x arrives un-normalized)."""
    B = x.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim
    qp = fused_pdot(x, gather_w(p["wq"], plan), policy,
                    prologue=norm)                             # [B, Hhd/tp]
    q = col.all_gather(qp, plan.tp_axes, axis=-1).reshape(B, H, hd)
    return apply_rope(q[:, None], pos[:, None], theta=cfg.rope_theta,
                      fraction=cfg.rope_fraction)[:, 0]


def _decode_kv_new(p, x, pos, *, plan: Plan, cfg, policy: Policy, norm=None):
    """This step's K/V rows ([B, KV, hd] each; K rotated)."""
    B = x.shape[0]
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    kp = fused_pdot(x, gather_w(p["wk"], plan), policy, prologue=norm)
    vp = fused_pdot(x, gather_w(p["wv"], plan), policy, prologue=norm)
    k_new = col.all_gather(kp, plan.tp_axes, axis=-1).reshape(B, KV, hd)
    v_new = col.all_gather(vp, plan.tp_axes, axis=-1).reshape(B, KV, hd)
    k_new = apply_rope(k_new[:, None], pos[:, None], theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)[:, 0]
    return k_new, v_new


def _decode_out_proj(p, merged, *, plan: Plan, policy: Policy,
                     residual=None):
    """Contract the merged [B, H*hd] head tensor with wo (tp-partial +
    psum) -> [B, E] at activation dtype.  `residual` folds into the GEMM
    epilogue when no tp reduction is pending (added after the psum
    otherwise); when given, the result is the updated residual stream."""
    tp_ax = plan.tp_axes
    ad = act_dtype(policy)
    rows_loc = merged.shape[1] // plan.tp
    i = col.axis_index(tp_ax)
    o_loc = jax.lax.dynamic_slice_in_dim(
        merged.astype(ad), i * rows_loc, rows_loc, axis=1)
    wo = gather_w(p["wo"], plan, fsdp_dim=1)                   # [Hhd/tp, E]
    if residual is not None and not tp_ax:
        return fused_pdot(o_loc, wo, policy,
                          epilogue=Epilogue(residual=residual, out_dtype=ad),
                          out_dtype=jnp.float32)
    part = pdot(o_loc, wo, policy, out_dtype=jnp.float32)
    y = col.psum(part, tp_ax).astype(ad)
    return y if residual is None else residual + y


def attn_decode(p, x, pos, cache, *, plan: Plan, cfg, policy: Policy,
                window: int, cross: bool = False, memory_len: int = 0,
                norm=None, residual=None):
    """One decode step.  x: [B, E] (replicated over tp); pos: [B] int32 —
    position index of the token being written; cache: {"k","v"} local shards
    [B, W_loc, KV, hd].  Returns (y [B, E], updated cache).

    `norm` / `residual`: fused prologue/epilogue — see `attn_full` (with
    `residual` the first return value is the updated stream)."""
    c_ax = plan.cache_axes
    B, E = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    ad = act_dtype(policy)
    sm_scale = float(1.0 / (hd ** 0.5))

    W_loc = cache["k"].shape[1]
    W = W_loc * plan.cache_shards                  # global cache slots
    ring = window > 0 and W == window

    q = _decode_q(p, x, pos, plan=plan, cfg=cfg, policy=policy, norm=norm)

    if not cross:
        k_new, v_new = _decode_kv_new(p, x, pos, plan=plan, cfg=cfg,
                                      policy=policy, norm=norm)
        slot = pos % W if ring else pos
        start = col.axis_index(c_ax) * W_loc
        loc = slot - start
        # negative indices WRAP in .at[] before mode="drop" applies — route
        # non-owned slots to an out-of-bounds positive index instead
        loc = jnp.where((loc >= 0) & (loc < W_loc), loc, W_loc)
        rows = jnp.arange(B)
        cache = {
            "k": cache["k"].at[rows, loc].set(
                k_new.astype(cache["k"].dtype), mode="drop"),
            "v": cache["v"].at[rows, loc].set(
                v_new.astype(cache["v"].dtype), mode="drop"),
        }
    else:
        start = col.axis_index(c_ax) * W_loc

    # validity of local slots
    gidx = jnp.arange(W_loc)[None, :] + start                  # [1, W_loc]
    if cross:
        valid = jnp.broadcast_to(gidx < memory_len, (B, W_loc))
    elif ring:
        # slot s holds abs position pos - ((pos - s) mod W); valid if >= 0
        valid = (pos[:, None] + 1 >= W) | (gidx <= pos[:, None])
    else:
        valid = gidx <= pos[:, None]
        if window > 0:
            valid &= gidx > (pos[:, None] - window)

    o, m, l = decode_partials(q.astype(ad), cache["k"], cache["v"], valid,
                              sm_scale=sm_scale)
    merged = merge_partials(o, m, l, c_ax).reshape(B, H * hd)  # T4 merge
    return _decode_out_proj(p, merged, plan=plan, policy=policy,
                            residual=residual), cache


SCALE_EPS = 1e-30      # guards zero-amax blocks and unwritten scale slots


def _quantized_kv(cache) -> bool:
    """True when the paged pools store int8 K/V with per-block-per-head
    scales ({"ks","vs"} [.., NB_loc, KV] fp32 leaves alongside
    {"k","v"})."""
    return "ks" in cache


def _quantize_row(x_new, s_old, fresh):
    """Quantize-on-write for a single-token paged append.  x_new: [B, KV, hd]
    fp-valued rows; s_old [B, KV] the stored scales of their blocks;
    fresh [B] marks rows landing at offset 0.  Blocks fill front-to-back, so
    a token at offset 0 is the block's first (re)use: it (re)sets the block
    scale from its own amax.  Later offsets reuse the stored scale and
    clip — entries already in the block never move, which is what makes
    speculative rollback (a fill-count rewind) and COW sharing safe.
    -> (q int8 [B, KV, hd], s_new [B, KV] the scale a fresh block takes,
    s_read [B, KV] the scale the block holds once the row is written)."""
    xf = x_new.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)                       # [B, KV]
    s_new = jnp.maximum(amax, SCALE_EPS) / 127.0
    fresh = fresh[:, None]
    s = jnp.where(fresh, s_new, jnp.maximum(s_old, SCALE_EPS))
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s_new, jnp.where(fresh, s_new, s_old)


def _scatter_quantized(pool, scales, x_new, loc, off, fresh):
    """Quantize-on-write for a multi-token chunk scatter into one layer's
    pool [NB_loc, BS, KV*hd].  x_new: [B, C, KV, hd]; loc/off [B, C]
    (non-owned / pad tokens routed to loc == NB_loc => dropped); `fresh`
    [B, C] marks tokens whose block's offset 0 lies inside this write.
    Fresh blocks take their scale from a scatter-max over the chunk's own
    token amaxes (exactly the per-block amax of what lands in them); stale
    blocks keep their stored scale and this chunk's tokens clip against it
    — same invariant as `_quantize_row`, vectorized."""
    NB_loc = pool.shape[0]
    xf = x_new.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)                       # [B, C, KV]
    zloc = jnp.where(fresh & (off == 0), loc, NB_loc)
    scales = scales.at[zloc].set(0.0, mode="drop")             # reset fresh
    floc = jnp.where(fresh, loc, NB_loc)
    scales = scales.at[floc].max(jnp.maximum(amax, SCALE_EPS) / 127.0,
                                 mode="drop")
    s = jnp.maximum(scales[jnp.clip(loc, 0, NB_loc - 1)], SCALE_EPS)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    q = q.reshape(*q.shape[:2], -1)                            # [B, C, KV*hd]
    return pool.at[loc, off].set(q, mode="drop"), scales


def attn_chunk_paged(p, x, pos0, chunk_len, cache, block_tables, *,
                     plan: Plan, cfg, policy: Policy, norm=None,
                     residual=None, rope_pos=None, tree_mask=None):
    """One chunked-prefill piece against a block-paged KV cache.

    x: [B, C, E] — C consecutive prompt tokens per row, starting at absolute
    position `pos0` [B]; `chunk_len` [B] is the true token count this chunk
    carries (<= C; the tail is padding whose KV is never scattered and whose
    outputs the caller discards); cache: {"k","v"} one layer's pool shards
    [NB_loc, BS, KV*hd]; block_tables: [B, MB] global pool indices.

    The chunk's KV rows are scattered into their blocks FIRST, then the
    chunk queries attend the pool (prefix + this chunk) under a per-query
    causal mask — so one code path covers both the first chunk (empty
    prefix) and every later one.  Per-shard partials merge with the same T4
    rule as decode; projections reuse the decode helpers on the flattened
    [B*C] token batch.  Returns (y [B, C, E], updated cache).

    Tree-speculative verify reuses this path with two overrides: the chunk
    then carries a token *tree* whose node i is scattered at pos0+i as
    usual, but `rope_pos` [B, C] rotates q/k at each node's *logical* depth
    (pos0 + depth, shared by sibling branches) so the winning path's KV is
    correctly rotated for its final position, and `tree_mask` [B, C, C]
    replaces the intra-chunk causal mask with the ancestor matrix."""
    c_ax = plan.cache_axes
    B, C, E = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ad = act_dtype(policy)

    NB_loc, BS = cache["k"].shape[0], cache["k"].shape[1]
    start = col.axis_index(c_ax) * NB_loc
    pos = pos0[:, None] + jnp.arange(C)[None, :]               # [B, C]

    # projections: decode math on B*C tokens, reshaped back to chunks
    flat = x.reshape(B * C, E)
    pflat = (pos if rope_pos is None else rope_pos).reshape(B * C)
    q = _decode_q(p, flat, pflat, plan=plan, cfg=cfg,
                  policy=policy, norm=norm).reshape(B, C, H, hd)
    k_new, v_new = _decode_kv_new(p, flat, pflat, plan=plan, cfg=cfg,
                                  policy=policy, norm=norm)
    k_new = k_new.reshape(B, C, KV, hd)
    v_new = v_new.reshape(B, C, KV, hd)

    # scatter the chunk KV into its blocks (pad tail / non-owned dropped)
    real = jnp.arange(C)[None, :] < chunk_len[:, None]         # [B, C]
    MB = block_tables.shape[1]
    entry = jnp.clip(pos // BS, 0, MB - 1)
    gb = jnp.take_along_axis(block_tables, entry, axis=1)      # [B, C]
    loc = gb - start
    owned = real & (gb >= 0) & (loc >= 0) & (loc < NB_loc)
    loc = jnp.where(owned, loc, NB_loc)      # out of range => mode="drop"
    off = pos % BS
    if _quantized_kv(cache):
        fresh = (pos - off) >= pos0[:, None]   # block's offset 0 is ours
        kp, ks = _scatter_quantized(cache["k"], cache["ks"], k_new,
                                    loc, off, fresh)
        vp, vs = _scatter_quantized(cache["v"], cache["vs"], v_new,
                                    loc, off, fresh)
        cache = {"k": kp, "v": vp, "ks": ks, "vs": vs}
    else:
        cache = {
            "k": cache["k"].at[loc, off].set(
                k_new.reshape(B, C, KV * hd).astype(cache["k"].dtype),
                mode="drop"),
            "v": cache["v"].at[loc, off].set(
                v_new.reshape(B, C, KV * hd).astype(cache["v"].dtype),
                mode="drop"),
        }

    # local table view (entries this shard owns, local ids)
    length = pos0 + chunk_len                  # valid tokens incl. the chunk
    loc_tab = block_tables - start
    present = (block_tables >= 0) & (loc_tab >= 0) & (loc_tab < NB_loc)
    loc_tab = jnp.where(present, loc_tab, -1)

    o, m, l = ops.paged_chunk_partials(q.astype(ad), cache["k"], cache["v"],
                                       loc_tab, pos, length,
                                       k_scale=cache.get("ks"),
                                       v_scale=cache.get("vs"),
                                       tree_mask=tree_mask)
    merged = merge_partials(o, m, l, c_ax).reshape(B * C, H * hd)
    y = _decode_out_proj(p, merged, plan=plan, policy=policy,
                         residual=residual.reshape(B * C, E)
                         if residual is not None else None)
    return y.reshape(B, C, E), cache


def _decode_slot(pos, block_tables, NB_loc: int, BS: int, plan: Plan):
    """Where this step's token goes in this shard's pool: -> (loc [B] local
    block id, NB_loc where the block is absent or another shard's — an
    out-of-range index that `mode="drop"` skips; negative ids would wrap
    in .at[] —, off [B] in-block offset, owned [B] bool)."""
    start = col.axis_index(plan.cache_axes) * NB_loc   # first owned block
    gb = jnp.take_along_axis(block_tables, (pos // BS)[:, None],
                             axis=1)[:, 0]                       # [B]
    loc = gb - start
    owned = (gb >= 0) & (loc >= 0) & (loc < NB_loc)
    return jnp.where(owned, loc, NB_loc), pos % BS, owned


def _fold_current(o, m, l, q, k_cur, v_cur, owned, k_scale=None,
                  v_scale=None):
    """Fold the current token into a slot's online-softmax partials as one
    more term (m = q.k * scale, l = 1, o = v) with the T4 rescale rule
    `merge_partials` applies.  q: [B, H, D]; k/v_cur: [B, KV, D], the
    values as the pool stores them (int8 with `k_scale`/`v_scale` [B, KV],
    applied as the kernel applies a block's scale); owned [B]: the shard
    that holds the token's block counts it, every other shard adds
    nothing.  Elementwise fp32 math, so the term rounds like the kernel's
    fp32 dot over the same stored values."""
    B, H, D = q.shape
    KV = k_cur.shape[1]
    qf = q.astype(jnp.float32).reshape(B, KV, H // KV, D)
    s = jnp.sum(qf * k_cur.astype(jnp.float32)[:, :, None, :], axis=-1)
    s = s * float(1.0 / (D ** 0.5))
    if k_scale is not None:
        s = s * k_scale[:, :, None]
    v = v_cur.astype(jnp.float32)
    if v_scale is not None:
        v = v * v_scale[..., None]
    v = jnp.broadcast_to(v[:, :, None, :], (B, KV, H // KV, D))
    s = jnp.where(owned[:, None, None], s, NEG_INF).reshape(B, H)
    m_all = jnp.maximum(m, s)
    a = jnp.exp(m - m_all)
    b = jnp.exp(s - m_all) * owned[:, None]
    o = o * a[..., None] + b[..., None] * v.reshape(B, H, D)
    return o, m_all, l * a + b


def attn_decode_paged(p, x, pos, pool, layer, block_tables, *, plan: Plan,
                      cfg, policy: Policy, norm=None, residual=None):
    """One decode step of one layer against the block-paged KV cache
    (full-context layers only — window/ring layers keep the dense per-slot
    ring, `attn_decode`).

    x: [B, E]; pos: [B] — position index of the token being written;
    pool: {"k","v"[,"ks","vs"]} every layer's pool shards, stacked
    ([count, NB_loc, BS, KV*hd]; int8 scales [count, NB_loc, KV]),
    block-sharded over `plan.cache_axes` and read only; layer: int32
    scalar, this layer's index in the stack; block_tables: [B, MB] int32
    *global* pool indices in sequence order (< 0 = unallocated).
    Returns (y [B, E], rows): rows holds this token's K/V as the pool will
    store them ({"k","v"} [B, KV*hd] in the pool dtype; int8 pools add
    {"ks","vs"} [B, KV], the scales a block first written here takes),
    which `write_decode_rows` puts into the pool after the layer scan.

    Attention dispatches to the paged split-KV partials kernel
    (kernels/ops.paged_decode_partials), which reads the stacked pool in
    place over the positions before `pos` in the blocks this shard owns;
    the current token joins as one more online-softmax term on the shard
    that owns its block, and the per-shard partials merge across cache
    shards with the same T4 rule as the dense path — the pool is never
    gathered, sliced or copied."""
    c_ax = plan.cache_axes
    B, E = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ad = act_dtype(policy)

    NB_loc, BS = pool["k"].shape[1], pool["k"].shape[2]
    start = col.axis_index(c_ax) * NB_loc          # first owned global block

    q = _decode_q(p, x, pos, plan=plan, cfg=cfg, policy=policy,
                  norm=norm).astype(ad)
    k_new, v_new = _decode_kv_new(p, x, pos, plan=plan, cfg=cfg,
                                  policy=policy, norm=norm)

    loc, off, owned = _decode_slot(pos, block_tables, NB_loc, BS, plan)
    scales = {}
    if _quantized_kv(pool):
        rows, read = {}, {}
        for key, x_new in (("k", k_new), ("v", v_new)):
            s_old = pool[key + "s"][layer, jnp.clip(loc, 0, NB_loc - 1)]
            rows[key], rows[key + "s"], read[key] = _quantize_row(
                x_new, s_old, off == 0)
        k_cur, v_cur = rows["k"], rows["v"]
        scales = {"k_scale": read["k"], "v_scale": read["v"]}
    else:
        dt = pool["k"].dtype
        k_cur, v_cur = k_new.astype(dt), v_new.astype(dt)
        rows = {"k": k_cur, "v": v_cur}
    rows["k"] = rows["k"].reshape(B, KV * hd)
    rows["v"] = rows["v"].reshape(B, KV * hd)

    # local view of the table: entries this shard owns, local ids
    loc_tab = block_tables - start
    present = (block_tables >= 0) & (loc_tab >= 0) & (loc_tab < NB_loc)
    loc_tab = jnp.where(present, loc_tab, -1)

    o, m, l = ops.paged_decode_partials(q, pool["k"], pool["v"], layer,
                                        loc_tab, pos,
                                        k_scale=pool.get("ks"),
                                        v_scale=pool.get("vs"))
    o, m, l = _fold_current(o, m, l, q, k_cur, v_cur, owned, **scales)
    merged = merge_partials(o, m, l, c_ax).reshape(B, H * hd)  # T4 merge
    return _decode_out_proj(p, merged, plan=plan, policy=policy,
                            residual=residual), rows


def write_decode_rows(pool, rows, pos, block_tables, *, plan: Plan):
    """Write one decode step's new tokens into the pools, every layer at
    once: rows {"k","v"} [count, B, KV*hd] (int8 pools add {"ks","vs"}
    [count, B, KV]), stacked by the decode layer scan from
    `attn_decode_paged`.  One scatter per leaf lands row [l, b] in layer
    l's block table[b, pos // BS] at offset pos % BS, in place in the
    donated pool; absent / non-owned blocks are dropped.  A row at offset 0
    is its block's first: its scale replaces the block's (the append rule
    of `_quantize_row`).  -> the updated pool leaves."""
    count, NB_loc, BS = pool["k"].shape[:3]
    loc, off, _ = _decode_slot(pos, block_tables, NB_loc, BS, plan)
    lay = jnp.arange(count)[:, None]
    out = {key: pool[key].at[lay, loc[None], off[None]].set(
        rows[key], mode="drop") for key in ("k", "v")}
    if _quantized_kv(pool):
        sloc = jnp.where(off == 0, loc, NB_loc)[None]
        for key in ("ks", "vs"):
            out[key] = pool[key].at[lay, sloc].set(rows[key], mode="drop")
    return out
