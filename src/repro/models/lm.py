"""Decoder-only / encoder-decoder language model over the segment schedule.

The model is a list of *segments* (configs.base): each segment is one
`lax.scan` over `count` stacked layers of one kind, keeping the HLO compact
for deep models (deepseek-67b: one 95-trip while loop).  All forwards run
inside the step's `shard_map` (launch/steps.py) and follow the manual-SPMD
contract: x is [B_loc, S_loc, E] (batch over `plan.batch_axes`, sequence
over `plan.seq_axes`).

Modes
-----
train     full sequence, remat per layer, distributed-CE loss (NAR math)
prefill   full sequence + KV-cache construction, next token (NAR); supports
          right-padded length buckets (`prompt_len`) and in-jit sampling
decode    one token per call against the sequence-sharded cache (AR, T4)

Modality frontends are stubs per the assignment: VLM patch embeddings and
audio frames arrive as precomputed [*, E] inputs (models/frontends.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import attention as attn
from repro.core import blocks
from repro.core import collectives as col
from repro.core.embedding import (ce_loss, embed_sequence, embed_token,
                                  embedding_param_dims,
                                  embedding_param_shapes, greedy_token,
                                  init_embedding, logits_local, sample_token,
                                  sample_topn)
from repro.core.nn import act_dtype
from repro.core.rope import sinusoidal_positions
from repro.kernels import ops
from repro.sharding.plan import Plan

AUX_WEIGHT = 0.01      # MoE load-balance loss weight


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _stack_dims(dims):
    return jax.tree.map(lambda d: (None,) + tuple(d), dims,
                        is_leaf=lambda x: isinstance(x, tuple))


def lm_param_dims(cfg) -> dict:
    out = {
        "embedding": embedding_param_dims(cfg),
        "final_norm": blocks._norm_dims(cfg),
        "segments": tuple(_stack_dims(blocks.block_param_dims(kind, cfg))
                          for kind, _ in cfg.schedule),
    }
    if cfg.enc_schedule:
        out["enc_segments"] = tuple(
            _stack_dims(blocks.block_param_dims(kind, cfg))
            for kind, _ in cfg.enc_schedule)
        out["enc_final_norm"] = blocks._norm_dims(cfg)
    return out


def init_lm(key, cfg, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 4)

    def init_segment(k, kind, count):
        ks = jax.random.split(k, count)
        return jax.vmap(lambda kk: blocks.init_block(kk, kind, cfg, dtype))(ks)

    segs = []
    for i, (kind, count) in enumerate(cfg.schedule):
        segs.append(init_segment(jax.random.fold_in(keys[0], i), kind, count))
    out = {
        "embedding": init_embedding(keys[1], cfg, dtype),
        "final_norm": blocks._init_norm(cfg, dtype),
        "segments": tuple(segs),
    }
    if cfg.enc_schedule:
        enc = []
        for i, (kind, count) in enumerate(cfg.enc_schedule):
            enc.append(init_segment(jax.random.fold_in(keys[2], i), kind,
                                    count))
        out["enc_segments"] = tuple(enc)
        out["enc_final_norm"] = blocks._init_norm(cfg, dtype)
    return out


# --------------------------------------------------------------------------
# sequence assembly
# --------------------------------------------------------------------------

def total_seq(cfg, s_text: int) -> int:
    return s_text + (cfg.n_patches or 0)


def _embed_sequence(params, batch, *, plan: Plan, cfg, policy,
                    with_labels: bool):
    """Local residual stream [B_loc, S_loc, E]; labels/valid cover the FULL
    sequence (vocab-parallel CE contract, core/embedding.py)."""
    tokens = batch["tokens"]                       # [B_loc, S_text]
    B, S_text = tokens.shape
    n_p = cfg.n_patches if (cfg.n_patches and "patches" in batch) else 0
    S_tot = S_text + n_p
    ids_full = tokens
    if n_p:                                        # patch positions: dummy id
        ids_full = jnp.concatenate(
            [jnp.zeros((B, n_p), tokens.dtype), tokens], axis=1)
    x = embed_sequence(params["embedding"]["embed"], ids_full, plan=plan,
                       policy=policy)              # [B, S_loc, E]

    S_loc = S_tot // plan.sp
    off = col.axis_index(plan.seq_axes) * S_loc
    gpos = jnp.arange(S_loc) + off                 # [S_loc] global positions
    if n_p:                                        # overwrite patch prefix
        prow = jnp.take(batch["patches"], jnp.clip(gpos, 0, n_p - 1), axis=1)
        x = jnp.where((gpos < n_p)[None, :, None], prow.astype(x.dtype), x)
    if cfg.rope_theta == 0:                        # whisper: sinusoidal abs
        pos_tab = sinusoidal_positions(S_tot, cfg.d_model)
        x = x + jnp.take(pos_tab, gpos, axis=0)[None].astype(x.dtype)

    if not with_labels:
        return x, None, None
    labels = batch["labels"]
    valid = jnp.ones((B, S_text), bool)
    if "valid" in batch:
        valid &= batch["valid"]
    if n_p:
        labels = jnp.concatenate(
            [jnp.zeros((B, n_p), labels.dtype), labels], axis=1)
        valid = jnp.concatenate([jnp.zeros((B, n_p), bool), valid], axis=1)
    return x, labels, valid


def _run_encoder(params, batch, *, plan: Plan, cfg, policy):
    """Whisper encoder over stub frame embeddings -> [B, S_enc_loc, E]."""
    frames = batch["frames"]                       # [B, S_enc_pad, E]
    S_enc = frames.shape[1]
    S_loc = S_enc // plan.sp
    off = col.axis_index(plan.seq_axes) * S_loc
    x = jax.lax.dynamic_slice_in_dim(frames, off, S_loc, axis=1)
    pos_tab = sinusoidal_positions(S_enc, cfg.d_model)
    x = (x + jnp.take(pos_tab, jnp.arange(S_loc) + off, axis=0)[None]
         ).astype(act_dtype(policy))
    for (kind, _), p_seg in zip(cfg.enc_schedule, params["enc_segments"]):
        def body(carry, p_layer):
            y, _, _ = blocks.block_full(kind, p_layer, carry, plan=plan,
                                        cfg=cfg, policy=policy)
            return y, None
        x, _ = jax.lax.scan(body, x, p_seg)
    return ops.norm(x, params["enc_final_norm"], cfg.norm)


# --------------------------------------------------------------------------
# segment runners
# --------------------------------------------------------------------------

def _run_segments_train(params, x, *, plan, cfg, policy, memory, memory_len):
    aux = jnp.zeros((), jnp.float32)
    for (kind, _), p_seg in zip(cfg.schedule, params["segments"]):
        def body(carry, p_layer, _kind=kind):
            h, a = carry
            h2, _, da = blocks.block_full(_kind, p_layer, h, plan=plan,
                                          cfg=cfg, policy=policy,
                                          memory=memory,
                                          memory_len=memory_len)
            return (h2, a + da), None
        (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, aux), p_seg)
    return x, aux


def _run_segments_prefill(params, x, *, plan, cfg, policy, max_seq,
                          memory, memory_len, compact_kv=False,
                          with_cache=True):
    """`with_cache=False` runs the same full-sequence stack cache-free (the
    encoder-only serving pass) — one segment pipeline, not two."""
    caches = []
    for (kind, _), p_seg in zip(cfg.schedule, params["segments"]):
        def body(h, p_layer, _kind=kind):
            h2, cache, _ = blocks.block_full(_kind, p_layer, h, plan=plan,
                                             cfg=cfg, policy=policy,
                                             with_cache=with_cache,
                                             max_seq=max_seq,
                                             memory=memory,
                                             memory_len=memory_len,
                                             compact_kv=compact_kv)
            return h2, cache
        x, seg_cache = jax.lax.scan(body, x, p_seg)
        caches.append(seg_cache)
    return x, tuple(caches)


POOL_KEYS = ("k", "v", "ks", "vs")


def _run_segments_decode(params, x, pos, caches, *, plan, cfg, policy,
                         memory_len, block_tables=None, paged_segments=None):
    """Paged segments keep their KV pools out of the layer scan: the
    stacked pools are a read-only operand of every layer (the kernel reads
    them in place by layer index), each layer emits its token's K/V rows,
    and one scatter per pool leaf writes them after the scan — the decode
    step moves no pool bytes but the blocks it reads and the rows it
    writes.  Dense leaves (SSM state, rings, cross-attention memory) ride
    the scan as before."""
    new_caches = []
    paged_segments = paged_segments or (False,) * len(cfg.schedule)
    for (kind, count), p_seg, c_seg, pgd in zip(
            cfg.schedule, params["segments"], caches, paged_segments):
        pool = ({k: v for k, v in c_seg.items() if k in POOL_KEYS}
                if pgd else None)
        dense = {k: v for k, v in c_seg.items() if k not in (pool or ())}

        def body(h, inp, _kind=kind, _pool=pool):
            p_layer, layer, c_layer = inp
            return blocks.block_decode(_kind, p_layer, h, pos, c_layer,
                                       plan=plan, cfg=cfg, policy=policy,
                                       memory_len=memory_len,
                                       block_tables=block_tables,
                                       pool=_pool, layer=layer)
        x, c_new = jax.lax.scan(body, x, (p_seg, jnp.arange(count), dense))
        if pgd:
            c_new.update(attn.write_decode_rows(
                pool, c_new.pop("kv_rows"), pos, block_tables, plan=plan))
        new_caches.append(c_new)
    return x, tuple(new_caches)


# --------------------------------------------------------------------------
# entry points (called inside shard_map)
# --------------------------------------------------------------------------

def forward_train(params, batch, *, plan: Plan, cfg, policy):
    """-> (loss_for_grad, metrics).

    `loss_for_grad` is THIS DEVICE's contribution to the global mean loss
    (manual-SPMD contract: differentiating a psum'd scalar inside shard_map
    would scale every gradient by the device count, since each device would
    return the same global sum).  The psum'd global loss lives in
    `metrics["loss"]`.  With no mesh the two coincide."""
    x, labels, valid = _embed_sequence(params, batch, plan=plan, cfg=cfg,
                                       policy=policy, with_labels=True)
    memory = memory_len = None
    if cfg.enc_schedule:
        memory = _run_encoder(params, batch, plan=plan, cfg=cfg,
                              policy=policy)
        memory_len = cfg.enc_seq_padded
    x, aux = _run_segments_train(params, x, plan=plan, cfg=cfg, policy=policy,
                                 memory=memory, memory_len=memory_len)
    x = ops.norm(x, params["final_norm"], cfg.norm)
    loss_sum, cnt = ce_loss(x, params["embedding"]["unemb"], labels, valid,
                            plan=plan, cfg=cfg, policy=policy)
    # CE is computed redundantly on every tp peer (x gathered over seq): each
    # copy is scaled by 1/tp so that, summed over devices, the total counts
    # every token exactly once — the manual-SPMD "loss = sum of per-device
    # contributions" contract that makes the collective transposes exact.
    sp = max(plan.sp, 1)
    tok_axes = plan.batch_axes + plan.seq_axes
    n = jnp.maximum(col.psum(cnt / sp, tok_axes), 1.0)
    contrib = (loss_sum / sp) / n               # this device's share
    ce = col.psum(contrib, tok_axes)            # global mean (metrics)
    loss_for_grad = contrib
    metrics = {"ce": ce, "tokens": n}
    if cfg.n_experts:
        n_moe = sum(c for k, c in cfg.schedule if k in blocks.MOE_KINDS)
        aux_share = aux / sp / max(plan.dp, 1) / max(n_moe, 1)
        loss_for_grad = loss_for_grad + AUX_WEIGHT * aux_share
        metrics["aux"] = col.psum(aux_share, tok_axes)
    metrics["loss"] = ce + (AUX_WEIGHT * metrics["aux"]
                            if cfg.n_experts else 0.0)
    return loss_for_grad, metrics


def _head_norm(params, plan: Plan, cfg):
    """Final-norm prologue for the logits head when the fused pipeline is
    on (None = apply ops.norm separately, the unfused chain)."""
    if not blocks.block_fused(plan):
        return None
    return ops.norm_prologue(params["final_norm"], cfg.norm)


def _last_position(x, plan: Plan):
    """x: [B, S_loc, E] sequence-sharded -> [B, E] residual of the final
    global position (fixed-length convenience over `_residual_at`)."""
    B, S_loc = x.shape[0], x.shape[1]
    S_tot = S_loc * max(plan.sp, 1)
    return _residual_at(x, jnp.full((B,), S_tot - 1, jnp.int32), plan)


def _residual_at(x, idx, plan: Plan):
    """x: [B, S_loc, E] sequence-sharded; idx: [B] global positions ->
    [B, E] residual at each row's position (psum'd from the owner shard)."""
    S_loc = x.shape[1]
    off = col.axis_index(plan.seq_axes) * S_loc
    loc = idx.astype(jnp.int32) - off
    rows = jnp.take_along_axis(
        x, jnp.clip(loc, 0, S_loc - 1)[:, None, None], axis=1)[:, 0]
    if not plan.seq_axes:
        return rows
    mine = ((loc >= 0) & (loc < S_loc))[:, None].astype(jnp.float32)
    return col.psum(rows.astype(jnp.float32) * mine,
                    plan.seq_axes).astype(x.dtype)


def forward_prefill(params, batch, *, plan: Plan, cfg, policy, max_seq: int,
                    prompt_len=None, lane=None, compact_kv: bool = False):
    """NAR prompt pass.  -> (next_token [B], caches, pos [B]).

    `prompt_len` ([B] int32, optional): true per-row text length when
    `batch["tokens"]` is right-padded to a length bucket — the next token is
    read at each row's true last position and `pos` starts at its true
    length (pad cache entries beyond it are never attended: decode masks
    positions > pos, and causality masks them during the prefill itself).
    `lane` (optional): per-row sampling state (core.embedding.sample_token,
    sans "step"); greedy decoding when None.
    `compact_kv`: emit full-context KV caches at prompt length instead of
    padded to `max_seq` (paged admission scatters them into pool blocks)."""
    x_last, head_norm, caches, pos = _prefill_last(
        params, batch, plan=plan, cfg=cfg, policy=policy, max_seq=max_seq,
        prompt_len=prompt_len, compact_kv=compact_kv)
    if lane is None:
        tok = greedy_token(x_last, params["embedding"]["unemb"], plan=plan,
                           cfg=cfg, policy=policy, norm=head_norm)
    else:
        tok = sample_token(x_last, params["embedding"]["unemb"],
                           dict(lane, step=pos), plan=plan, cfg=cfg,
                           policy=policy, norm=head_norm)
    return tok, caches, pos


def prefill_logits(params, batch, *, plan: Plan, cfg, policy):
    """fp32 logits [B, Vp/tp] at each row's last prompt position (padded
    vocab columns masked): the scores `forward_prefill`'s greedy pick
    takes the argmax of, for checks against a reference path."""
    x_last, head_norm, _, _ = _prefill_last(
        params, batch, plan=plan, cfg=cfg, policy=policy,
        max_seq=total_seq(cfg, batch["tokens"].shape[1]), prompt_len=None,
        compact_kv=True)
    z, _ = logits_local(x_last, params["embedding"]["unemb"], plan=plan,
                        cfg=cfg, policy=policy, norm=head_norm)
    return z


def _prefill_last(params, batch, *, plan: Plan, cfg, policy, max_seq: int,
                  prompt_len, compact_kv: bool):
    """The prompt pass up to the head: -> (raw residual at each row's last
    prompt position [B, E], the final norm when it fuses into the head
    GEMM (else already applied), caches, pos [B])."""
    x, _, _ = _embed_sequence(params, batch, plan=plan, cfg=cfg,
                              policy=policy, with_labels=False)
    memory = None
    memory_len = 0
    if cfg.enc_schedule:
        memory = _run_encoder(params, batch, plan=plan, cfg=cfg,
                              policy=policy)
        memory_len = cfg.enc_seq_padded
    x, caches = _run_segments_prefill(params, x, plan=plan, cfg=cfg,
                                      policy=policy, max_seq=max_seq,
                                      memory=memory, memory_len=memory_len,
                                      compact_kv=compact_kv)
    head_norm = _head_norm(params, plan, cfg)
    if head_norm is None:
        x = ops.norm(x, params["final_norm"], cfg.norm)
    B = batch["tokens"].shape[0]
    if prompt_len is None:
        pos = jnp.full((B,), total_seq(cfg, batch["tokens"].shape[1]),
                       jnp.int32)
    else:
        pos = (cfg.n_patches or 0) + prompt_len.astype(jnp.int32)
    # fused head: select the raw residual row first (norm is row-wise, so
    # select-then-norm == norm-then-select) and fold the final norm into
    # the logits GEMM — the full-sequence normalized copy never exists
    return _residual_at(x, pos - 1, plan), head_norm, caches, pos


def forward_encode(params, batch, *, plan: Plan, cfg, policy,
                   prompt_len=None, pooling: str = "last"):
    """Encoder-only NAR pass: one full-sequence forward, no KV cache, no
    sampling — the paper's encoder topology served as a first-class task.
    -> pooled [B, E] float32.

    `prompt_len` ([B] int32, optional): true per-row text length when the
    batch is right-padded to a length bucket.  Padding is output-exact only
    for causal schedules (bidirectional kinds attend pad positions); the
    runner pads only when every kind is causal and encodes at exact length
    otherwise.
    `pooling`: "last" — residual at the final true position (what a prefill
    would sample from); "mean" — masked mean over the true positions."""
    x, _, _ = _embed_sequence(params, batch, plan=plan, cfg=cfg,
                              policy=policy, with_labels=False)
    memory = None
    memory_len = 0
    if cfg.enc_schedule:
        memory = _run_encoder(params, batch, plan=plan, cfg=cfg,
                              policy=policy)
        memory_len = cfg.enc_seq_padded
    x, _ = _run_segments_prefill(params, x, plan=plan, cfg=cfg,
                                 policy=policy, max_seq=0, memory=memory,
                                 memory_len=memory_len, with_cache=False)
    fused_head = blocks.block_fused(plan) and pooling == "last"
    if not fused_head:
        # mean pooling needs every position normalized — norm of the mean
        # is not the mean of the norms, so the full-seq norm stays
        x = ops.norm(x, params["final_norm"], cfg.norm)

    B, S_loc = x.shape[0], x.shape[1]
    n_p = cfg.n_patches or 0
    if prompt_len is None:
        pos = jnp.full((B,), S_loc * max(plan.sp, 1), jnp.int32)
    else:
        pos = n_p + prompt_len.astype(jnp.int32)
    if pooling == "last":
        if fused_head:      # select the raw row, then norm just that row
            row = _residual_at(x, pos - 1, plan)
            return ops.norm(row, params["final_norm"],
                            cfg.norm).astype(jnp.float32)
        return _residual_at(x, pos - 1, plan).astype(jnp.float32)
    # masked mean over true text positions (patch prefix excluded)
    off = col.axis_index(plan.seq_axes) * S_loc
    gpos = jnp.arange(S_loc)[None, :] + off                    # [1, S_loc]
    keep = (gpos >= n_p) & (gpos < pos[:, None])               # [B, S_loc]
    s = jnp.sum(x.astype(jnp.float32) * keep[..., None], axis=1)
    s = col.psum(s, plan.seq_axes)
    n = jnp.maximum((pos - n_p).astype(jnp.float32), 1.0)
    return s / n[:, None]


def _run_chunk_stack(params, tokens, pos0, chunk_len, caches, block_tables,
                     *, plan: Plan, cfg, policy, paged_segments,
                     rope_pos=None, tree_mask=None):
    """The shared chunk body: embed C consecutive tokens per row, run every
    segment's `block_chunk` (KV scattered into the paged blocks), apply the
    final norm unless the fused head will fold it.  `forward_chunk`
    (chunked prefill: sample the last position), `forward_verify`
    (speculative decoding: sample every position), and
    `forward_verify_tree` (tree speculation: `rope_pos`/`tree_mask`
    overrides) all sit on THIS stack — the verify paths' losslessness
    rests on them sharing one body.
    -> (x [B, C, E], caches, head_norm-or-None)."""
    B, C = tokens.shape
    x = embed_token(params["embedding"]["embed"], tokens.reshape(B * C),
                    plan=plan, policy=policy).reshape(B, C, -1)
    paged_segments = paged_segments or (True,) * len(cfg.schedule)
    new_caches = []
    for (kind, _), p_seg, c_seg, pgd in zip(cfg.schedule, params["segments"],
                                            caches, paged_segments):
        assert pgd, f"chunk forward requires paged segments: {kind}"
        def body(h, inp, _kind=kind):
            p_layer, c_layer = inp
            h2, c2 = blocks.block_chunk(_kind, p_layer, h, pos0, chunk_len,
                                        c_layer, block_tables, plan=plan,
                                        cfg=cfg, policy=policy,
                                        rope_pos=rope_pos,
                                        tree_mask=tree_mask)
            return h2, c2
        x, c_new = jax.lax.scan(body, x, (p_seg, c_seg))
        new_caches.append(c_new)
    head_norm = _head_norm(params, plan, cfg)
    if head_norm is None:
        x = ops.norm(x, params["final_norm"], cfg.norm)
    return x, tuple(new_caches), head_norm


def forward_chunk(params, tokens, pos0, chunk_len, caches, block_tables, *,
                  plan: Plan, cfg, policy, lane=None, paged_segments=None):
    """One chunked-prefill piece: encode C consecutive prompt tokens into
    the paged KV cache.  tokens: [B, C]; pos0: [B] absolute start position;
    chunk_len: [B] real tokens this chunk (<= C; tail is padding).
    -> (next_token [B], caches, pos [B]).

    Every call also samples a token at each row's last real chunk position
    — the caller uses it only when the chunk completes the prompt, where it
    equals what `forward_prefill` samples (same residual, same (seed, step)
    draw).  Requires every segment paged (ModelRunner.supports_chunked);
    `lane` as in forward_prefill (sans prompt_len); greedy when None."""
    B, C = tokens.shape
    x, new_caches, head_norm = _run_chunk_stack(
        params, tokens, pos0, chunk_len, caches, block_tables, plan=plan,
        cfg=cfg, policy=policy, paged_segments=paged_segments)

    pos = pos0 + chunk_len.astype(jnp.int32)
    last = jnp.clip(chunk_len - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    if lane is None:
        tok = greedy_token(x_last, params["embedding"]["unemb"], plan=plan,
                           cfg=cfg, policy=policy, norm=head_norm)
    else:
        tok = sample_token(x_last, params["embedding"]["unemb"],
                           dict(lane, step=pos), plan=plan, cfg=cfg,
                           policy=policy, norm=head_norm)
    return tok, new_caches, pos


def forward_verify(params, tokens, pos0, chunk_len, caches, block_tables, *,
                   plan: Plan, cfg, policy, lane=None, paged_segments=None):
    """Multi-token verification pass for speculative decoding: one target
    forward over C = k+1 consecutive tokens (the pending token + k draft
    proposals) straight into the paged KV cache, returning the target's
    OWN next-token choice at every position.  tokens: [B, C]; pos0: [B]
    absolute start position (== the slot's decode pos); chunk_len: [B]
    real tokens this row carries (<= C; tail is padding).
    -> (choices [B, C], caches, pos [B]).

    choices[b, j] is the token the target would emit after the prefix
    ending at absolute position pos0[b] + j — i.e. exactly what a
    non-speculative decode step at that state would produce: greedy rows
    take the argmax, sampled rows the (seed, step)-keyed Gumbel-max draw
    with step = pos0 + j + 1, matching forward_decode's step = pos + 1.
    The host accepts the longest prefix where the draft's proposal equals
    the target's choice (serving/spec.py), so committed outputs are
    token-identical to step-by-step decoding.  KV for every chunk position
    is scattered into the slot's blocks; rejected positions sit beyond the
    committed `pos` and are masked / overwritten — rollback is a
    fill-count rewind, not a cache edit.  Requires every segment paged
    (same gate as forward_chunk, whose stack this shares)."""
    B, C = tokens.shape
    x, new_caches, head_norm = _run_chunk_stack(
        params, tokens, pos0, chunk_len, caches, block_tables, plan=plan,
        cfg=cfg, policy=policy, paged_segments=paged_segments)

    # every position samples: flatten [B, C, E] -> [B*C, E] and draw with
    # step = pos0 + j + 1 per position (the decode-step contract: the token
    # occupying position p is drawn with step p)
    E = x.shape[-1]
    x_flat = x.reshape(B * C, E)
    steps = (pos0[:, None] + 1 + jnp.arange(C)[None, :]).astype(jnp.int32)
    if lane is None:
        tok = greedy_token(x_flat, params["embedding"]["unemb"], plan=plan,
                           cfg=cfg, policy=policy, norm=head_norm)
    else:
        lane_flat = {k: jnp.repeat(v, C) for k, v in lane.items()}
        tok = sample_token(x_flat, params["embedding"]["unemb"],
                           dict(lane_flat, step=steps.reshape(B * C)),
                           plan=plan, cfg=cfg, policy=policy, norm=head_norm)
    return (tok.reshape(B, C), new_caches,
            pos0 + chunk_len.astype(jnp.int32))


def forward_verify_tree(params, tokens, pos0, chunk_len, depth, anc, caches,
                        block_tables, *, plan: Plan, cfg, policy, lane=None,
                        paged_segments=None):
    """Tree-speculative verification: one target forward over C flattened
    token-tree nodes per row (node 0 = the pending token, then the draft's
    branches in an ancestor-closed flatten order), returning the target's
    own next-token choice at every node.  tokens: [B, C] node tokens;
    pos0: [B] the slot's decode pos; chunk_len: [B] real nodes (<= C);
    depth: [B, C] int32 each node's tree depth (node 0 -> 0); anc: [B, C, C]
    bool ancestor-or-self matrix (anc[b, i, j] <=> node j is on node i's
    root path).  -> (choices [B, C], caches, pos [B]).

    Node i's KV scatters at position pos0 + i (unique per node — the same
    scatter mechanics as forward_verify), while rope and the sampling step
    use the node's *logical* position pos0 + depth[i]: sibling branches at
    one depth share a rotation and a (seed, step) draw key, exactly the
    state a step-by-step decode would have after committing that node's
    root path — so choices[b, i] is bit-identical to what non-speculative
    decode (or forward_verify on the same chain) would emit there, greedy
    or sampled, and the winning path's KV bytes are already rotated for
    their final positions (commit is a pure row move, serving/kv_cache.py).
    The ancestor mask keeps each node blind to its sibling branches:
    attention sees the committed prefix (< pos0) plus its own root path
    only.  With a single-branch chain (depth == node index, anc lower
    triangular) every override reduces to forward_verify's causal math."""
    B, C = tokens.shape
    rope_pos = pos0[:, None] + depth                           # [B, C]
    x, new_caches, head_norm = _run_chunk_stack(
        params, tokens, pos0, chunk_len, caches, block_tables, plan=plan,
        cfg=cfg, policy=policy, paged_segments=paged_segments,
        rope_pos=rope_pos, tree_mask=anc)

    E = x.shape[-1]
    x_flat = x.reshape(B * C, E)
    steps = (rope_pos + 1).astype(jnp.int32)     # token after node i's path
    if lane is None:
        tok = greedy_token(x_flat, params["embedding"]["unemb"], plan=plan,
                           cfg=cfg, policy=policy, norm=head_norm)
    else:
        lane_flat = {k: jnp.repeat(v, C) for k, v in lane.items()}
        tok = sample_token(x_flat, params["embedding"]["unemb"],
                           dict(lane_flat, step=steps.reshape(B * C)),
                           plan=plan, cfg=cfg, policy=policy, norm=head_norm)
    return (tok.reshape(B, C), new_caches,
            pos0 + chunk_len.astype(jnp.int32))


def forward_decode(params, token, pos, caches, *, plan: Plan, cfg, policy,
                   lane=None, block_tables=None, paged_segments=None):
    """One AR step.  token/pos: [B] -> (next_token [B], caches).

    `lane` (optional): per-row sampling state (core.embedding.sample_token,
    sans "step"); greedy decoding when None.
    `block_tables` / `paged_segments` (optional): block-paged KV cache —
    [B, MB] int32 pool indices per slot plus a static per-segment tuple
    marking which segments' k/v leaves are pools (launch/steps builds both;
    `pos` doubles as the per-slot valid length)."""
    x = embed_token(params["embedding"]["embed"], token, plan=plan,
                    policy=policy)                              # [B, E]
    if cfg.rope_theta == 0:
        pos_tab = sinusoidal_positions(cfg.max_seq, cfg.d_model)
        x = x + jnp.take(pos_tab, jnp.clip(pos, 0, cfg.max_seq - 1),
                         axis=0).astype(x.dtype)
    memory_len = cfg.enc_seq_padded if cfg.enc_schedule else 0
    x, caches = _run_segments_decode(params, x, pos, caches, plan=plan,
                                     cfg=cfg, policy=policy,
                                     memory_len=memory_len,
                                     block_tables=block_tables,
                                     paged_segments=paged_segments)
    head_norm = _head_norm(params, plan, cfg)
    if head_norm is None:
        x = ops.norm(x, params["final_norm"], cfg.norm)
    if lane is None:
        tok = greedy_token(x, params["embedding"]["unemb"], plan=plan,
                           cfg=cfg, policy=policy, norm=head_norm)
    else:
        tok = sample_token(x, params["embedding"]["unemb"],
                           dict(lane, step=pos + 1), plan=plan, cfg=cfg,
                           policy=policy, norm=head_norm)
    return tok, caches


def forward_decode_topk(params, token, pos, caches, *, n, plan: Plan, cfg,
                        policy, lane, block_tables=None, paged_segments=None):
    """One AR step that also surfaces the sampler's runners-up: the tree
    proposer's draft step.  token/pos: [B] -> (next_token [B],
    alts [B, n], caches) where alts[:, 0] == next_token (the chain token —
    the exact `forward_decode` choice) and alts[:, 1:] are the next-best
    distinct ids of the SAME deterministic score `sample_token` ranks
    (greedy rows: raw logits; sampled rows: the (seed, step)-keyed
    Gumbel-perturbed top-k-masked scores).  The draft's cache advances one
    position regardless — only the chain is ever fed back."""
    x = embed_token(params["embedding"]["embed"], token, plan=plan,
                    policy=policy)                              # [B, E]
    if cfg.rope_theta == 0:
        pos_tab = sinusoidal_positions(cfg.max_seq, cfg.d_model)
        x = x + jnp.take(pos_tab, jnp.clip(pos, 0, cfg.max_seq - 1),
                         axis=0).astype(x.dtype)
    memory_len = cfg.enc_seq_padded if cfg.enc_schedule else 0
    x, caches = _run_segments_decode(params, x, pos, caches, plan=plan,
                                     cfg=cfg, policy=policy,
                                     memory_len=memory_len,
                                     block_tables=block_tables,
                                     paged_segments=paged_segments)
    head_norm = _head_norm(params, plan, cfg)
    if head_norm is None:
        x = ops.norm(x, params["final_norm"], cfg.norm)
    tok, alts = sample_topn(x, params["embedding"]["unemb"],
                            dict(lane, step=pos + 1), n, plan=plan, cfg=cfg,
                            policy=policy, norm=head_norm)
    return tok, alts, caches
