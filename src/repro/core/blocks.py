"""Transformer blocks: one assembly per layer kind (configs.base docstring).

Every block is pre-norm residual.  `block_full` handles train / prefill /
encoder passes (sequence-sharded x); `block_decode` handles one AR step
(x: [B, E]).  Both are `lax.scan`-compatible: stacked layer params in,
stacked caches out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import attention as attn
from repro.core import mlp as mlp_mod
from repro.core import ssm as ssm_mod
from repro.kernels import ops
from repro.sharding.plan import Plan

ATTN_KINDS = ("attn", "local", "moe", "moe_local", "hybrid_attn",
              "hybrid_local", "enc", "dec", "vit")
SSM_KINDS = ("ssm", "hybrid_attn", "hybrid_local")
MOE_KINDS = ("moe", "moe_local")
MLP_KINDS = ("attn", "local", "hybrid_attn", "hybrid_local", "enc", "dec",
             "vit")
LOCAL_KINDS = ("local", "moe_local", "hybrid_local")
BIDIR_KINDS = ("enc", "vit")


def block_fused(plan: Plan) -> bool:
    """True when this block should emit the fused prologue/epilogue
    pipeline.  fp8 residual gathers (comm_fp8) pre-norm BEFORE quantizing
    the wire bytes — folding the norm behind the gather would move the
    quantization point — so fusion falls back to the discrete chain there."""
    return plan.fuse_epilogues and not plan.comm_fp8


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _norm_shapes(cfg):
    E = cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": (E,)}
    return {"scale": (E,), "bias": (E,)}


def _norm_dims(cfg):
    return {k: (None,) for k in _norm_shapes(cfg)}


def _init_norm(cfg, dtype):
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def block_param_shapes(kind: str, cfg) -> dict:
    out = {"ln1": _norm_shapes(cfg)}
    if kind in ATTN_KINDS:
        out["attn"] = attn.attention_param_shapes(cfg)
    if kind in SSM_KINDS or kind == "ssm":
        out["ssm"] = ssm_mod.ssm_param_shapes(cfg)
    if kind == "dec":
        out["lnx"] = _norm_shapes(cfg)
        out["xattn"] = attn.attention_param_shapes(cfg)
    if kind in MOE_KINDS:
        out["ln2"] = _norm_shapes(cfg)
        out["moe"] = mlp_mod.moe_param_shapes(cfg)
    elif kind in MLP_KINDS:
        out["ln2"] = _norm_shapes(cfg)
        out["mlp"] = mlp_mod.mlp_param_shapes(cfg)
    return out


def block_param_dims(kind: str, cfg) -> dict:
    out = {"ln1": _norm_dims(cfg)}
    if kind in ATTN_KINDS:
        out["attn"] = attn.attention_param_dims(cfg)
    if kind in SSM_KINDS or kind == "ssm":
        out["ssm"] = ssm_mod.ssm_param_dims(cfg)
    if kind == "dec":
        out["lnx"] = _norm_dims(cfg)
        out["xattn"] = attn.attention_param_dims(cfg)
    if kind in MOE_KINDS:
        out["ln2"] = _norm_dims(cfg)
        out["moe"] = mlp_mod.moe_param_dims(cfg)
    elif kind in MLP_KINDS:
        out["ln2"] = _norm_dims(cfg)
        out["mlp"] = mlp_mod.mlp_param_dims(cfg)
    return out


def init_block(key, kind: str, cfg, dtype) -> dict:
    ks = jax.random.split(key, 4)
    out = {"ln1": _init_norm(cfg, dtype)}
    if kind in ATTN_KINDS:
        out["attn"] = attn.init_attention(ks[0], cfg, dtype)
    if kind in SSM_KINDS or kind == "ssm":
        out["ssm"] = ssm_mod.init_ssm(ks[1], cfg, dtype)
    if kind == "dec":
        out["lnx"] = _init_norm(cfg, dtype)
        out["xattn"] = attn.init_attention(ks[2], cfg, dtype)
    if kind in MOE_KINDS:
        out["ln2"] = _init_norm(cfg, dtype)
        out["moe"] = mlp_mod.init_moe(ks[3], cfg, dtype)
    elif kind in MLP_KINDS:
        out["ln2"] = _init_norm(cfg, dtype)
        out["mlp"] = mlp_mod.init_mlp(ks[3], cfg, dtype)
    return out


# --------------------------------------------------------------------------
# static per-kind attention attributes
# --------------------------------------------------------------------------

def kind_window(kind: str, cfg) -> int:
    return cfg.sliding_window if kind in LOCAL_KINDS else 0


def kind_causal(kind: str, cfg) -> bool:
    if kind in BIDIR_KINDS:
        return False
    return cfg.causal


def kind_cache_len(kind: str, cfg, max_seq: int) -> int:
    """Global KV-cache slots for this kind (ring caches: the window)."""
    w = kind_window(kind, cfg)
    return min(w, max_seq) if w > 0 and w < max_seq else max_seq


def kind_paged(kind: str, cfg, max_seq: int) -> bool:
    """True when this kind's self-attention KV cache is block-paged under a
    paged layout: full-context attention layers only — window/ring caches
    are already bounded and stay dense per-slot (as do SSM state and
    cross-attention memory)."""
    return (kind in ATTN_KINDS
            and kind_cache_len(kind, cfg, max_seq) == max_seq)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def block_full(kind: str, p, x, *, plan: Plan, cfg, policy,
               with_cache: bool = False, max_seq: int = 0, memory=None,
               memory_len: int = 0, compact_kv: bool = False):
    """x: [B, S_loc, E] -> (x', cache | None, aux).

    `compact_kv`: emit full-context KV caches at the prompt's own length
    instead of padding to `max_seq` (paged prefill: the engine scatters the
    compact cache into pool blocks, so the B x max_seq dense buffer never
    materializes).  Ring/window caches keep their window-sized layout."""
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    causal = kind_causal(kind, cfg)
    window = kind_window(kind, cfg)
    cache_len = kind_cache_len(kind, cfg, max_seq) if with_cache else 0
    if compact_kv and kind_paged(kind, cfg, max_seq):
        # compact cache at the sequence's own length, rounded up to the
        # cache-shard count (cache_slice cuts S // shards rows per device —
        # an indivisible S would silently drop the tail positions)
        S_tot = x.shape[1] * max(plan.sp, 1)
        shards = max(plan.cache_shards, 1)
        cache_len = -(-S_tot // shards) * shards

    fused = block_fused(plan)
    if kind == "ssm":
        h = ops.norm(x, p["ln1"], cfg.norm)
        y, sc = ssm_mod.ssm_full(p["ssm"], h, plan=plan, cfg=cfg,
                                 policy=policy, with_cache=with_cache)
        if with_cache:
            cache.update(sc)
        return x + y, (cache if with_cache else None), aux

    hybrid = kind in ("hybrid_attn", "hybrid_local")
    moe_like = kind in MOE_KINDS
    h = y = None
    if fused and not hybrid and not moe_like:
        # pre-norm folds into the Q/K/V projections, the residual add into
        # the out-projection epilogue: x' comes back as the updated stream
        x, kv = attn.attn_full(p["attn"], x, plan=plan, cfg=cfg,
                               policy=policy, causal=causal, window=window,
                               with_cache=with_cache, cache_len=cache_len,
                               norm=ops.norm_prologue(p["ln1"], cfg.norm),
                               residual=x)
    elif fused and moe_like:
        # keep the sub-layer output: its residual add fuses with ln2 below
        y, kv = attn.attn_full(p["attn"], x, plan=plan, cfg=cfg,
                               policy=policy, causal=causal, window=window,
                               with_cache=with_cache, cache_len=cache_len,
                               norm=ops.norm_prologue(p["ln1"], cfg.norm))
    else:
        h = ops.norm(x, p["ln1"], cfg.norm)
        y, kv = attn.attn_full(p["attn"], h, plan=plan, cfg=cfg,
                               policy=policy, causal=causal, window=window,
                               with_cache=with_cache, cache_len=cache_len)
    if with_cache:
        cache.update(kv)
    if hybrid:
        s, sc = ssm_mod.ssm_full(p["ssm"], h, plan=plan, cfg=cfg,
                                 policy=policy, with_cache=with_cache)
        y = (y + s) * 0.5
        if with_cache:
            cache.update(sc)
    if y is not None and not fused:
        x = x + y
        y = None
    # fused + (hybrid | moe): y still pending — it folds into the fused
    # residual_norm at the ln2 boundary below

    if kind == "dec":
        cl = memory.shape[1] * plan.sp if memory is not None else 0
        if fused:
            x, xkv = attn.attn_full(p["xattn"], x, plan=plan, cfg=cfg,
                                    policy=policy, causal=False, window=0,
                                    with_cache=with_cache, cache_len=cl,
                                    memory=memory, memory_len=memory_len,
                                    norm=ops.norm_prologue(p["lnx"],
                                                           cfg.norm),
                                    residual=x)
        else:
            hx = ops.norm(x, p["lnx"], cfg.norm)
            yx, xkv = attn.attn_full(p["xattn"], hx, plan=plan, cfg=cfg,
                                     policy=policy, causal=False, window=0,
                                     with_cache=with_cache, cache_len=cl,
                                     memory=memory, memory_len=memory_len)
            x = x + yx
        if with_cache:
            cache["ck"], cache["cv"] = xkv["k"], xkv["v"]

    if moe_like:
        if fused:       # add + norm in one pass (GEMMs can't absorb MoE's
            h2, x = ops.residual_norm(x, y, p["ln2"], cfg.norm)  # dispatch)
        else:
            h2 = ops.norm(x, p["ln2"], cfg.norm)
        y2, aux = mlp_mod.moe_full(p["moe"], h2, plan=plan, cfg=cfg,
                                   policy=policy)
        x = x + y2
    elif kind in MLP_KINDS:
        if fused and hybrid:
            h2, x = ops.residual_norm(x, y, p["ln2"], cfg.norm)
            x = mlp_mod.mlp_full(p["mlp"], h2, plan=plan, cfg=cfg,
                                 policy=policy, residual=x)
        elif fused:
            x = mlp_mod.mlp_full(p["mlp"], x, plan=plan, cfg=cfg,
                                 policy=policy,
                                 norm=ops.norm_prologue(p["ln2"], cfg.norm),
                                 residual=x)
        else:
            h2 = ops.norm(x, p["ln2"], cfg.norm)
            x = x + mlp_mod.mlp_full(p["mlp"], h2, plan=plan, cfg=cfg,
                                     policy=policy)
    return x, (cache if with_cache else None), aux


def block_chunk(kind: str, p, x, pos0, chunk_len, cache, block_tables, *,
                plan: Plan, cfg, policy, rope_pos=None, tree_mask=None):
    """One chunked-prefill piece through a block whose KV cache is paged.

    x: [B, C, E] — C consecutive prompt tokens starting at absolute position
    `pos0` [B] (`chunk_len` [B] of them real).  Only full-context attention
    kinds support chunking (their KV lives in the block pool, which carries
    the chunk state between engine steps); SSM / sliding-window / cross-attn
    kinds have recurrent or ring state a partial prefill would corrupt — the
    runner gates on `ModelRunner.supports_chunked` and falls back to
    whole-prompt prefill.  MLP / MoE run the decode path on the flattened
    [B*C, E] token batch (identical per-token math); only attention needs
    the chunk structure.  Returns (x', updated cache).

    `rope_pos` / `tree_mask` flow to `attn_chunk_paged` for tree-speculative
    verify (logical-depth rotation + ancestor attention mask); both None on
    the plain chunked-prefill path."""
    assert kind in ATTN_KINDS and kind not in SSM_KINDS and kind != "dec", (
        f"chunked prefill unsupported for kind {kind!r}")
    B, C, E = x.shape
    new_cache = dict(cache)
    fused = block_fused(plan)
    moe_like = kind in MOE_KINDS

    kv_in = {k: cache[k] for k in ("k", "v", "ks", "vs") if k in cache}
    tree_kw = dict(rope_pos=rope_pos, tree_mask=tree_mask)
    y = None
    if fused and not moe_like:
        x, kv = attn.attn_chunk_paged(p["attn"], x, pos0, chunk_len, kv_in,
                                      block_tables, plan=plan, cfg=cfg,
                                      policy=policy,
                                      norm=ops.norm_prologue(p["ln1"],
                                                             cfg.norm),
                                      residual=x, **tree_kw)
    elif fused:
        y, kv = attn.attn_chunk_paged(p["attn"], x, pos0, chunk_len, kv_in,
                                      block_tables, plan=plan, cfg=cfg,
                                      policy=policy,
                                      norm=ops.norm_prologue(p["ln1"],
                                                             cfg.norm),
                                      **tree_kw)
    else:
        h = ops.norm(x, p["ln1"], cfg.norm)
        y, kv = attn.attn_chunk_paged(p["attn"], h, pos0, chunk_len, kv_in,
                                      block_tables, plan=plan, cfg=cfg,
                                      policy=policy, **tree_kw)
        x = x + y
        y = None
    new_cache.update(kv)

    if moe_like:
        if fused:
            h2, x = ops.residual_norm(x, y, p["ln2"], cfg.norm)
        else:
            h2 = ops.norm(x, p["ln2"], cfg.norm)
        y2, _ = mlp_mod.moe_decode(p["moe"], h2.reshape(B * C, E), plan=plan,
                                   cfg=cfg, policy=policy)
        x = x + y2.reshape(B, C, E)
    elif fused:
        xf = mlp_mod.mlp_decode(p["mlp"], x.reshape(B * C, E), plan=plan,
                                cfg=cfg, policy=policy,
                                norm=ops.norm_prologue(p["ln2"], cfg.norm),
                                residual=x.reshape(B * C, E))
        x = xf.reshape(B, C, E)
    else:
        h2 = ops.norm(x, p["ln2"], cfg.norm).reshape(B * C, E)
        y2 = mlp_mod.mlp_decode(p["mlp"], h2, plan=plan, cfg=cfg,
                                policy=policy)
        x = x + y2.reshape(B, C, E)
    return x, new_cache


def block_decode(kind: str, p, x, pos, cache, *, plan: Plan, cfg, policy,
                 memory_len: int = 0, block_tables=None, pool=None,
                 layer=None):
    """x: [B, E]; pos: [B]; cache: this layer's cache dict.
    Returns (x', updated cache).

    `pool` / `layer`: this kind's self-attention KV lives in the block
    pools — every layer's, stacked ([count, NB, BS, KV*hd] leaves, read
    only) — addressed through `block_tables` [B, MB]; `layer` is this
    layer's index in the stack (core/attention.attn_decode_paged).  The
    updated cache then carries the token's pool rows under "kv_rows" for
    the caller to write after the layer scan.  SSM state, ring caches and
    cross-attention memory are per-slot dense either way."""
    window = kind_window(kind, cfg)
    new_cache = dict(cache)
    fused = block_fused(plan)

    if kind == "ssm":
        h = ops.norm(x, p["ln1"], cfg.norm)
        y, sc = ssm_mod.ssm_decode(p["ssm"], h,
                                   {k: cache[k] for k in ("h", "cx", "cbc")},
                                   plan=plan, cfg=cfg, policy=policy)
        new_cache.update(sc)
        return x + y, new_cache

    hybrid = kind in ("hybrid_attn", "hybrid_local")
    moe_like = kind in MOE_KINDS
    attn_fused = fused and not hybrid
    nspec = (ops.norm_prologue(p["ln1"], cfg.norm) if attn_fused else None)
    res = x if attn_fused and not moe_like else None
    h = None
    if attn_fused:
        q_in = x
    else:
        h = ops.norm(x, p["ln1"], cfg.norm)
        q_in = h
    if pool is not None:
        y, new_cache["kv_rows"] = attn.attn_decode_paged(
            p["attn"], q_in, pos, pool, layer, block_tables, plan=plan,
            cfg=cfg, policy=policy, norm=nspec, residual=res)
    else:
        y, kv = attn.attn_decode(p["attn"], q_in, pos,
                                 {k: cache[k] for k in ("k", "v")},
                                 plan=plan, cfg=cfg, policy=policy,
                                 window=window, norm=nspec, residual=res)
        new_cache.update(kv)
    if res is not None:         # y IS the updated stream
        x, y = y, None
    if hybrid:
        s, sc = ssm_mod.ssm_decode(p["ssm"], h,
                                   {k: cache[k] for k in ("h", "cx", "cbc")},
                                   plan=plan, cfg=cfg, policy=policy)
        y = (y + s) * 0.5
        new_cache.update(sc)
    if y is not None and not fused:
        x = x + y
        y = None
    # fused + (hybrid | moe): y pending for the residual_norm below

    if kind == "dec":
        if fused:
            x, _ = attn.attn_decode(p["xattn"], x, pos,
                                    {"k": cache["ck"], "v": cache["cv"]},
                                    plan=plan, cfg=cfg, policy=policy,
                                    window=0, cross=True,
                                    memory_len=memory_len,
                                    norm=ops.norm_prologue(p["lnx"],
                                                           cfg.norm),
                                    residual=x)
        else:
            hx = ops.norm(x, p["lnx"], cfg.norm)
            yx, _ = attn.attn_decode(p["xattn"], hx, pos,
                                     {"k": cache["ck"], "v": cache["cv"]},
                                     plan=plan, cfg=cfg, policy=policy,
                                     window=0, cross=True,
                                     memory_len=memory_len)
            x = x + yx

    if moe_like:
        if fused:
            h2, x = ops.residual_norm(x, y, p["ln2"], cfg.norm)
        else:
            h2 = ops.norm(x, p["ln2"], cfg.norm)
        y2, _ = mlp_mod.moe_decode(p["moe"], h2, plan=plan, cfg=cfg,
                                   policy=policy)
        x = x + y2
    elif kind in MLP_KINDS:
        if fused and hybrid:
            h2, x = ops.residual_norm(x, y, p["ln2"], cfg.norm)
            x = mlp_mod.mlp_decode(p["mlp"], h2, plan=plan, cfg=cfg,
                                   policy=policy, residual=x)
        elif fused:
            x = mlp_mod.mlp_decode(p["mlp"], x, plan=plan, cfg=cfg,
                                   policy=policy,
                                   norm=ops.norm_prologue(p["ln2"],
                                                          cfg.norm),
                                   residual=x)
        else:
            h2 = ops.norm(x, p["ln2"], cfg.norm)
            x = x + mlp_mod.mlp_decode(p["mlp"], h2, plan=plan, cfg=cfg,
                                       policy=policy)
    return x, new_cache
