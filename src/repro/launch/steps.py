"""Step builders: jit(shard_map(...)) train / prefill / decode steps.

This is the distribution boundary.  Each builder:
  1. derives the `Plan` for (arch, shape, mesh, mode),
  2. resolves every parameter / cache / batch leaf's logical dims to a
     PartitionSpec,
  3. wraps the manual-SPMD model forward in one `shard_map`,
  4. returns a `StepBundle` with the jitted fn + fully-sharded
     ShapeDtypeStructs — exactly what the dry-run `.lower().compile()`s and
     what train.py / serve.py execute.

Gradient synchronization (train): gradients are taken *inside* the
shard_map, so collective transposes handle the fsdp/tp reductions and the
remaining replication axes (the pure-DP `pod` axis, tp-replicated scalars)
are reduced explicitly by `grad_sync` — the hook where int8 error-feedback
compression applies to the cross-pod hop (optim/compression.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import blocks
from repro.core import collectives as col
from repro.core.attention import CACHE_DTYPE
from repro.core.nn import act_dtype
from repro.core.precision import BF16, FP8_SERVE, Policy, get_policy
from repro.models import frontends, lm
from repro.models.quantize import quantize_param_dims, quantize_params
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         cosine_schedule)
from repro.optim.compression import ef_compressed_psum
from repro.sharding.plan import Plan, make_plan

IS_DIMS = lambda x: isinstance(x, tuple) and all(
    isinstance(d, (str, type(None))) for d in x)

# Sharding-invariant RNG: on jax versions where threefry_partitionable still
# defaults False, RNG lowered under sharded outputs (init_state's
# out_shardings) produces different bits than the same program run eagerly /
# unsharded — the multi-device equivalence contract (tests/_distributed_prog)
# needs identical params either way.  Partitionable threefry guarantees it.
# NOTE this is a PROCESS-GLOBAL flag flipped at import: any program that
# imports this module (directly or via repro.serving) gets partitionable
# threefry bits everywhere, which differ from the legacy algorithm's.  It
# lives here rather than per-entrypoint because every step builder, test
# subprocess and serving engine funnels through this module, and a path
# that missed the flag would silently break cross-sharding determinism.
jax.config.update("jax_threefry_partitionable", True)


# --------------------------------------------------------------------------
# spec resolution
# --------------------------------------------------------------------------

def resolve_pspecs(dims_tree, plan: Plan):
    return jax.tree.map(lambda d: plan.pspec(*d), dims_tree, is_leaf=IS_DIMS)


def to_shardings(spec_tree, mesh: Optional[Mesh]):
    if mesh is None:
        return jax.tree.map(lambda s: None, spec_tree,
                            is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def with_shardings(struct_tree, spec_tree, mesh: Optional[Mesh]):
    """Attach NamedShardings to ShapeDtypeStructs (dry-run inputs)."""
    if mesh is None:
        return struct_tree
    return jax.tree.map(
        lambda st, sp: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                            sharding=NamedSharding(mesh, sp)),
        struct_tree, spec_tree)


def _sharded_axes(dims, plan: Plan):
    spec = plan.pspec(*dims)
    out = []
    for entry in spec:
        if entry is None:
            continue
        out.extend(entry if isinstance(entry, tuple) else (entry,))
    return tuple(out)


def shard_axes_list(dims_tree, plan: Plan):
    """Flat list (aligned with jax.tree.leaves of the params) of mesh-axis
    tuples each leaf is SHARDED over."""
    return [_sharded_axes(d, plan)
            for d in jax.tree.leaves(dims_tree, is_leaf=IS_DIMS)]


def replication_axes_list(dims_tree, plan: Plan):
    """Flat list of mesh-axis tuples each leaf is REPLICATED over."""
    if plan.mesh is None:
        return [() for _ in jax.tree.leaves(dims_tree, is_leaf=IS_DIMS)]
    all_axes = tuple(plan.mesh.axis_names)
    return [tuple(a for a in all_axes if a not in sh)
            for sh in shard_axes_list(dims_tree, plan)]


# --------------------------------------------------------------------------
# policies
# --------------------------------------------------------------------------

def default_policy(cfg: ModelConfig, mode: str) -> Policy:
    if mode == "train":
        return BF16
    if cfg.name == "mixtral-8x22b":
        return FP8_SERVE          # fp8 storage: fits the 16-chip TP column
    return BF16


# --------------------------------------------------------------------------
# cache layout
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class PagedLayout:
    """Static description of a block-paged KV cache layout.

    The pools are [count, num_blocks, block_size, KV*hd] per full-attention
    segment (block dim sharded over `plan.cache_axes`), a token row holding
    every head side by side: the layout the paged decode kernel tiles, so
    it reads the stacked pools in place.  One [B, max_blocks]
    block table addresses every layer — block b of a sequence lives in pool
    slot table[b] of EVERY paged segment.  `segments` marks which schedule
    entries are paged (window/ring, SSM and cross-attention caches stay
    dense per-slot)."""
    num_blocks: int
    block_size: int
    max_blocks: int                     # table width: ceil(max_seq / bs)
    segments: tuple                     # per-segment bool: k/v are pools

    @property
    def any_paged(self) -> bool:
        return any(self.segments)


def serve_dp(cfg: ModelConfig, shape: ShapeConfig,
             mesh: Optional[Mesh]) -> int:
    """Batch-shard count the serve plan for this shape uses — the single
    probe for "can this batch be paged?" (a paged pool is shared across
    slots, so the engine needs dp == 1; make_decode_step asserts the same
    plan-derived value)."""
    return make_plan(cfg, shape, mesh, mode="serve").dp


def make_paged_layout(cfg: ModelConfig, plan: Plan, max_seq: int,
                      num_blocks: int, block_size: int) -> PagedLayout:
    """Round the pool up to the cache-shard count and mark paged segments."""
    shards = max(plan.cache_shards, 1)
    nb = -(-num_blocks // shards) * shards
    return PagedLayout(
        num_blocks=nb, block_size=block_size,
        max_blocks=-(-max_seq // block_size),
        segments=tuple(blocks.kind_paged(kind, cfg, max_seq)
                       for kind, _ in cfg.schedule))


def cache_layout(cfg: ModelConfig, plan: Plan, global_batch: int,
                 max_seq: int, policy: Policy,
                 paged: Optional[PagedLayout] = None):
    """(struct tree, logical-dims tree) mirroring the prefill cache pytree.
    With `paged`, full-attention k/v leaves become block pools.

    kv_cache_dtype="int8" applies to the PAGED pools only (per-block-
    per-head fp32 scale leaves "ks"/"vs" ride alongside "k"/"v"; the
    scatter/append paths quantize on write and the paged kernels dequantize
    in-register).  Dense ring / cross-attention caches have no block
    granularity to hang scales off and stay bf16 — lossless."""
    B = global_batch
    kv_dtype = jnp.dtype(plan.kv_cache_dtype)
    int8_kv = kv_dtype == jnp.dtype(jnp.int8)
    dense_dtype = jnp.dtype(CACHE_DTYPE) if int8_kv else kv_dtype
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    Hp, Pd, N = cfg.padded_ssm_heads(), cfg.ssm_head_dim, cfg.ssm_state
    cw, dip = cfg.conv_width, cfg.padded_d_inner()
    ad = act_dtype(policy)
    structs, dims = [], []
    for kind, count in cfg.schedule:
        d, dm = {}, {}
        if kind in blocks.ATTN_KINDS:
            W = blocks.kind_cache_len(kind, cfg, max_seq)
            kv_dims = (None, "batch", "cache", None, None)
            if paged is not None and blocks.kind_paged(kind, cfg, max_seq):
                shape = (count, paged.num_blocks, paged.block_size, KV * hd)
                kv_dims = (None, "cache", None, None)
                d["k"] = jax.ShapeDtypeStruct(shape, kv_dtype)
                d["v"] = jax.ShapeDtypeStruct(shape, kv_dtype)
                dm["k"] = dm["v"] = kv_dims
                if int8_kv:
                    sshape = (count, paged.num_blocks, KV)
                    d["ks"] = jax.ShapeDtypeStruct(sshape, jnp.float32)
                    d["vs"] = jax.ShapeDtypeStruct(sshape, jnp.float32)
                    dm["ks"] = dm["vs"] = (None, "cache", None)
            else:
                d["k"] = jax.ShapeDtypeStruct((count, B, W, KV, hd),
                                              dense_dtype)
                d["v"] = jax.ShapeDtypeStruct((count, B, W, KV, hd),
                                              dense_dtype)
                dm["k"] = dm["v"] = kv_dims
            if kind == "dec":
                We = cfg.enc_seq_padded
                d["ck"] = jax.ShapeDtypeStruct((count, B, We, KV, hd),
                                               dense_dtype)
                d["cv"] = jax.ShapeDtypeStruct((count, B, We, KV, hd),
                                               dense_dtype)
                # cross-attn memory is per-slot dense even under paging
                dm["ck"] = dm["cv"] = (None, "batch", "cache", None, None)
        if kind in blocks.SSM_KINDS or kind == "ssm":
            d["h"] = jax.ShapeDtypeStruct((count, B, Hp, Pd, N), jnp.float32)
            dm["h"] = (None, "batch", "tp", None, None)
            d["cx"] = jax.ShapeDtypeStruct((count, B, cw - 1, dip), ad)
            dm["cx"] = (None, "batch", None, "tp")
            d["cbc"] = jax.ShapeDtypeStruct((count, B, cw - 1, 2 * N), ad)
            dm["cbc"] = (None, "batch", None, None)
        structs.append(d)
        dims.append(dm)
    return tuple(structs), tuple(dims)


def batch_dims(cfg: ModelConfig, shape_kind: str):
    out = {"tokens": ("batch", None)}
    if shape_kind == "train":
        out["labels"] = ("batch", None)
    if cfg.n_patches:
        out["patches"] = ("batch", None, None)
    if cfg.enc_schedule:
        out["frames"] = ("batch", None, None)
    return out


def lane_dims(with_prompt_len: bool):
    """Logical dims of the per-request sampling lane ([B] leaves)."""
    out = {"temperature": ("batch",), "top_k": ("batch",),
           "seed": ("batch",)}
    if with_prompt_len:
        out["prompt_len"] = ("batch",)
    return out


def lane_struct(global_batch: int, with_prompt_len: bool):
    B = global_batch
    out = {"temperature": jax.ShapeDtypeStruct((B,), jnp.float32),
           "top_k": jax.ShapeDtypeStruct((B,), jnp.int32),
           "seed": jax.ShapeDtypeStruct((B,), jnp.int32)}
    if with_prompt_len:
        out["prompt_len"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    return out


# --------------------------------------------------------------------------
# bundles
# --------------------------------------------------------------------------

@dataclass
class StepBundle:
    fn: Any                       # jitted step function
    plan: Plan
    policy: Policy
    cfg: ModelConfig
    in_structs: tuple             # ShapeDtypeStructs with shardings (dry-run)
    in_specs: tuple = ()
    aux: dict = field(default_factory=dict)

    def lower(self):
        return self.fn.lower(*self.in_structs)


def _maybe_shard_map(fn, mesh, in_specs, out_specs):
    if mesh is None:
        return fn
    return col.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _param_struct(cfg, dtype, weight_dtype: str = "bfloat16"):
    init = functools.partial(lm.init_lm, cfg=cfg, dtype=dtype)
    fn = init if weight_dtype != "int8" else (
        lambda key: quantize_params(init(key)))
    return jax.eval_shape(fn, jax.random.key(0))


def _serve_param_layout(cfg, policy, weight_dtype: str):
    """(dims, struct) for the serving param tree — weight-only int8 swaps
    every dense GEMM leaf for its {"q", "scale"} pair (models/quantize)."""
    p_dims = lm.lm_param_dims(cfg)
    if weight_dtype == "int8":
        p_dims = quantize_param_dims(p_dims)
    return p_dims, _param_struct(cfg, policy.param_dtype, weight_dtype)


# --------------------------------------------------------------------------
# train step
# --------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, shape: ShapeConfig,
                    mesh: Optional[Mesh], *,
                    policy: Optional[Policy] = None,
                    lr_fn: Optional[Callable] = None,
                    max_grad_norm: float = 1.0,
                    grad_compression: Optional[str] = None,
                    reduce_method: str = "ring",
                    gelu_impl: str = "i_gelu",
                    naive_attention: bool = False,
                    ssm_seq_parallel: bool = False,
                    fuse_epilogues: bool = True) -> StepBundle:
    import dataclasses
    policy = policy or default_policy(cfg, "train")
    lr_fn = lr_fn or cosine_schedule(3e-4, 100, 10_000)
    plan = make_plan(cfg, shape, mesh, mode="train",
                     reduce_method=reduce_method)
    plan = dataclasses.replace(plan, gelu_impl=gelu_impl,
                               naive_attention=naive_attention,
                               ssm_seq_parallel=ssm_seq_parallel,
                               fuse_epilogues=fuse_epilogues)

    p_dims = lm.lm_param_dims(cfg)
    p_specs = resolve_pspecs(p_dims, plan)
    p_struct = _param_struct(cfg, jnp.float32)
    rep_axes = replication_axes_list(p_dims, plan)
    shard_axes = shard_axes_list(p_dims, plan)
    compress_pod = (grad_compression == "int8"
                    and plan.mesh is not None
                    and "pod" in plan.mesh.axis_names)

    state_specs = {"step": P(), "params": p_specs,
                   "opt": {"m": p_specs, "v": p_specs}}
    if compress_pod:
        state_specs["ef"] = p_specs
    b_dims = batch_dims(cfg, "train")
    b_specs = resolve_pspecs(b_dims, plan)
    b_struct = frontends.batch_struct(cfg, "train", shape.global_batch,
                                      shape.seq_len)
    metric_specs = {"loss": P(), "ce": P(), "grad_norm": P(), "lr": P(),
                    "tokens": P()}
    if cfg.n_experts:
        metric_specs["aux"] = P()

    def body(state, batch):
        col.set_reduce_method(plan.reduce_method)   # T3 schedule selection

        def loss_fn(params_f32):
            params_c = jax.tree.map(
                lambda x: x.astype(policy.param_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params_f32)
            loss, metrics = lm.forward_train(params_c, batch, plan=plan,
                                             cfg=cfg, policy=policy)
            return loss, metrics

        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"])

        # explicit sync over replication axes (pod DP hop optionally int8)
        flat_g, tdef = jax.tree.flatten(grads)
        flat_rep = rep_axes
        assert len(flat_g) == len(flat_rep), (len(flat_g), len(flat_rep))
        flat_ef = (jax.tree.leaves(state["ef"]) if compress_pod
                   else [None] * len(flat_g))
        new_g, new_ef = [], []
        for g, rep, ef in zip(flat_g, flat_rep, flat_ef):
            if compress_pod and "pod" in rep:
                g, ef = ef_compressed_psum(g, ef, "pod")
                rep = tuple(a for a in rep if a != "pod")
            new_ef.append(ef)
            new_g.append(col.psum(g.astype(jnp.float32), rep))
        grads = jax.tree.unflatten(tdef, new_g)
        grads, gnorm = clip_by_global_norm(grads, shard_axes, max_grad_norm)
        lr = lr_fn(state["step"])
        new_params, new_opt = adamw_update(state["params"], grads,
                                           state["opt"], step=state["step"],
                                           lr=lr)
        new_state = {"step": state["step"] + 1, "params": new_params,
                     "opt": new_opt}
        if compress_pod:
            new_state["ef"] = jax.tree.unflatten(tdef, new_ef)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        return new_state, metrics

    sm = _maybe_shard_map(body, mesh,
                          in_specs=(state_specs, b_specs),
                          out_specs=(state_specs, metric_specs))
    fn = jax.jit(sm, donate_argnums=(0,))

    opt_struct = jax.eval_shape(adamw_init, p_struct)
    state_struct = {"step": jax.ShapeDtypeStruct((), jnp.int32),
                    "params": p_struct, "opt": opt_struct}
    if compress_pod:
        state_struct["ef"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_struct)
    in_structs = (with_shardings(state_struct, state_specs, mesh),
                  with_shardings(b_struct, b_specs, mesh))

    def init_state(seed: int = 0):
        def build():
            params = lm.init_lm(jax.random.key(seed), cfg, jnp.float32)
            return {"step": jnp.zeros((), jnp.int32), "params": params,
                    "opt": adamw_init(params),
                    **({"ef": jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)}
                       if compress_pod else {})}
        if mesh is None:
            return build()
        shardings = to_shardings(state_specs, mesh)
        return jax.jit(build, out_shardings=shardings)()

    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs,
                      in_specs=(state_specs, b_specs),
                      aux={"init_state": init_state,
                           "state_specs": state_specs,
                           "batch_specs": b_specs,
                           "param_dims": p_dims})


# --------------------------------------------------------------------------
# prefill step (NAR)
# --------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                      mesh: Optional[Mesh], *,
                      policy: Optional[Policy] = None,
                      max_seq: Optional[int] = None,
                      reduce_method: str = "ring",
                      naive_attention: bool = False,
                      ssm_seq_parallel: bool = False,
                      kv_cache_dtype: str = "bfloat16",
                      weight_dtype: str = "bfloat16",
                      attention_sharding: str = "",
                      comm_fp8: bool = False,
                      mlp_weight_stationary: bool = False,
                      with_sampling: bool = False,
                      compact_kv: bool = False,
                      fuse_epilogues: bool = True) -> StepBundle:
    """`compact_kv`: emit full-context KV caches at the batch's own
    sequence length instead of padded to `max_seq` — paged admission
    scatters them into pool blocks, so the dense B x max_seq buffer never
    materializes (ring/window caches keep their window layout)."""
    import dataclasses
    policy = policy or default_policy(cfg, "serve")
    plan = make_plan(cfg, shape, mesh, mode="serve",
                     reduce_method=reduce_method)
    plan = dataclasses.replace(
        plan, naive_attention=naive_attention,
        ssm_seq_parallel=ssm_seq_parallel, kv_cache_dtype=kv_cache_dtype,
        weight_dtype=weight_dtype,
        attention_sharding=attention_sharding or plan.attention_sharding,
        comm_fp8=comm_fp8, mlp_weight_stationary=mlp_weight_stationary,
        fuse_epilogues=fuse_epilogues)
    max_seq = max_seq or shape.seq_len

    p_dims, p_struct = _serve_param_layout(cfg, policy, weight_dtype)
    p_specs = resolve_pspecs(p_dims, plan)
    b_dims = batch_dims(cfg, "prefill")
    b_specs = resolve_pspecs(b_dims, plan)
    b_struct = frontends.batch_struct(cfg, "prefill", shape.global_batch,
                                      shape.seq_len)
    c_struct, c_dims = cache_layout(cfg, plan, shape.global_batch, max_seq,
                                    policy)
    c_specs = resolve_pspecs(c_dims, plan)
    tok_spec = plan.pspec("batch")

    def run(params, batch, lane):
        col.set_reduce_method(plan.reduce_method)   # T3 schedule selection
        if lane is None:
            return lm.forward_prefill(params, batch, plan=plan, cfg=cfg,
                                      policy=policy, max_seq=max_seq,
                                      compact_kv=compact_kv)
        # per-request lane: sampling params + true prompt length (the batch
        # may be right-padded to a length bucket)
        lane = dict(lane)
        return lm.forward_prefill(params, batch, plan=plan, cfg=cfg,
                                  policy=policy, max_seq=max_seq,
                                  prompt_len=lane.pop("prompt_len"),
                                  lane=lane, compact_kv=compact_kv)

    body = run if with_sampling else (lambda params, batch:
                                      run(params, batch, None))
    in_specs = (p_specs, b_specs)
    in_structs = (with_shardings(p_struct, p_specs, mesh),
                  with_shardings(b_struct, b_specs, mesh))
    if with_sampling:
        l_specs = resolve_pspecs(lane_dims(True), plan)
        in_specs += (l_specs,)
        in_structs += (with_shardings(lane_struct(shape.global_batch, True),
                                      l_specs, mesh),)
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs,
                          out_specs=(tok_spec, c_specs, tok_spec))
    fn = jax.jit(sm)
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs, in_specs=in_specs,
                      aux={"param_specs": p_specs, "cache_struct": c_struct,
                           "cache_specs": c_specs, "max_seq": max_seq,
                           "param_dims": p_dims})


# --------------------------------------------------------------------------
# encode step (encoder-only NAR)
# --------------------------------------------------------------------------

def make_encode_step(cfg: ModelConfig, shape: ShapeConfig,
                     mesh: Optional[Mesh], *,
                     policy: Optional[Policy] = None,
                     pooling: str = "last",
                     reduce_method: str = "ring",
                     naive_attention: bool = False,
                     weight_dtype: str = "bfloat16",
                     fuse_epilogues: bool = True) -> StepBundle:
    """Encoder-only serving step: one full-sequence forward, no KV cache,
    returning a pooled [B, d_model] float32 embedding per row (the paper's
    encoder topology — ViT/BERT-style configs — served through the same
    engine as decoder LMs).  Reuses the prefill bundle machinery: same plan
    derivation, same batch specs, same lane-carried true lengths for
    right-padded length buckets; there is just no cache tree and no token.

    fn(params, batch, prompt_len [B] int32) -> pooled [B, E] float32."""
    import dataclasses
    policy = policy or default_policy(cfg, "serve")
    plan = make_plan(cfg, shape, mesh, mode="serve",
                     reduce_method=reduce_method)
    plan = dataclasses.replace(plan, naive_attention=naive_attention,
                               weight_dtype=weight_dtype,
                               fuse_epilogues=fuse_epilogues)

    p_dims, p_struct = _serve_param_layout(cfg, policy, weight_dtype)
    p_specs = resolve_pspecs(p_dims, plan)
    b_dims = batch_dims(cfg, "encode")
    b_specs = resolve_pspecs(b_dims, plan)
    b_struct = frontends.batch_struct(cfg, "encode", shape.global_batch,
                                      shape.seq_len)
    out_spec = plan.pspec("batch", None)

    def body(params, batch, prompt_len):
        col.set_reduce_method(plan.reduce_method)   # T3 schedule selection
        return lm.forward_encode(params, batch, plan=plan, cfg=cfg,
                                 policy=policy, prompt_len=prompt_len,
                                 pooling=pooling)

    len_spec = plan.pspec("batch")
    in_specs = (p_specs, b_specs, len_spec)
    in_structs = (with_shardings(p_struct, p_specs, mesh),
                  with_shardings(b_struct, b_specs, mesh),
                  with_shardings(jax.ShapeDtypeStruct(
                      (shape.global_batch,), jnp.int32), len_spec, mesh))
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs, out_specs=out_spec)
    fn = jax.jit(sm)
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs, in_specs=in_specs,
                      aux={"param_specs": p_specs, "pooling": pooling})


# --------------------------------------------------------------------------
# chunked-prefill step
# --------------------------------------------------------------------------

def chunk_support_reason(cfg: ModelConfig,
                         layout: Optional[PagedLayout]) -> Optional[str]:
    """Why this (cfg, layout) cannot run the chunk-shaped stack — None when
    it can.  The chunk stack underpins chunked prefill, speculative verify,
    AND the prefix cache's suffix-only prefill (all three resume encoding at
    an arbitrary `pos0` against the paged decode caches), so the runner,
    spec gating, and prefix-cache gating all consult this one predicate."""
    if layout is None or not layout.any_paged or not all(layout.segments):
        return ("every KV segment must be block-paged (dense, ring, and SSM "
                "caches cannot carry resumable chunk state)")
    if cfg.has_ssm:
        return "recurrent SSM state absorbs chunk boundaries"
    if cfg.enc_schedule:
        return "cross-attention memory is not paged"
    if cfg.n_patches:
        return "patch prefixes occupy unpaged cache positions"
    if cfg.rope_theta <= 0:
        return "the chunk stack requires rotary positions"
    return None


def _chunk_scaffold(cfg: ModelConfig, shape: ShapeConfig,
                    mesh: Optional[Mesh], *, layout: PagedLayout,
                    width: int, policy: Optional[Policy],
                    max_seq: Optional[int], reduce_method: str,
                    kv_cache_dtype: str, weight_dtype: str,
                    fuse_epilogues: bool, kind: str):
    """Shared plan/spec/struct scaffolding for the chunk-shaped steps —
    chunked prefill and speculative verify both run lm's paged chunk stack
    over `width` consecutive tokens per row against the decode cache
    layout, with the same operand schema:

      (params, tokens [n, width], pos0 [n], chunk_len [n], caches,
       tables [n, MB][, lane])

    Returns (plan, policy, max_seq, p_specs, row_spec, tok_spec, c_struct,
    c_specs, in_specs, in_structs) with the lane NOT yet appended (the two
    builders differ in whether sampling is optional)."""
    import dataclasses
    policy = policy or default_policy(cfg, "serve")
    plan = make_plan(cfg, shape, mesh, mode="serve",
                     reduce_method=reduce_method)
    plan = dataclasses.replace(plan, kv_cache_dtype=kv_cache_dtype,
                               weight_dtype=weight_dtype,
                               fuse_epilogues=fuse_epilogues)
    max_seq = max_seq or shape.seq_len
    assert plan.dp == 1, (
        f"{kind} requires an unsharded decode batch: dp={plan.dp}")
    assert all(layout.segments), (
        f"{kind} requires every segment's KV to be paged "
        f"(segments={layout.segments})")

    p_dims, p_struct = _serve_param_layout(cfg, policy, weight_dtype)
    p_specs = resolve_pspecs(p_dims, plan)
    c_struct, c_dims = cache_layout(cfg, plan, shape.global_batch, max_seq,
                                    policy, paged=layout)
    c_specs = resolve_pspecs(c_dims, plan)
    n = shape.global_batch
    row_spec = plan.pspec("batch")
    tok_spec = plan.pspec("batch", None)
    in_specs = (p_specs, tok_spec, row_spec, row_spec, c_specs, tok_spec)
    in_structs = (
        with_shardings(p_struct, p_specs, mesh),
        with_shardings(jax.ShapeDtypeStruct((n, width), jnp.int32),
                       tok_spec, mesh),
        with_shardings(jax.ShapeDtypeStruct((n,), jnp.int32), row_spec,
                       mesh),
        with_shardings(jax.ShapeDtypeStruct((n,), jnp.int32), row_spec,
                       mesh),
        with_shardings(c_struct, c_specs, mesh),
        with_shardings(jax.ShapeDtypeStruct((n, layout.max_blocks),
                                            jnp.int32), tok_spec, mesh))
    return (plan, policy, max_seq, p_specs, row_spec, tok_spec, c_struct,
            c_specs, in_specs, in_structs)


def make_chunk_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                            mesh: Optional[Mesh], *,
                            layout: PagedLayout,
                            chunk_tokens: int,
                            policy: Optional[Policy] = None,
                            max_seq: Optional[int] = None,
                            reduce_method: str = "ring",
                            kv_cache_dtype: str = "bfloat16",
                            weight_dtype: str = "bfloat16",
                            with_sampling: bool = False,
                            fuse_epilogues: bool = True) -> StepBundle:
    """One chunked-prefill piece over the *decode* cache layout: encodes up
    to `chunk_tokens` consecutive prompt tokens per row straight into the
    paged KV pools, so a long admission interleaves with decode steps
    instead of stalling them (chunk state is just the block tables + `pos`).

    `shape` must be the decode shape the engine's decode step was built
    with — the cache pytree (and its shardings) is shared between the two
    steps, and caches are donated here for the same in-place update.

    fn(params, tokens [n, C], pos0 [n], chunk_len [n], caches,
       tables [n, MB][, lane]) -> (token [n], caches, pos [n])

    The returned token is meaningful only for rows whose chunk completes
    the prompt (it then equals the unchunked prefill's sample; see
    lm.forward_chunk).

    `pos0` is an arbitrary per-row start offset: besides mid-prompt chunk
    resumption, the prefix cache's warm admissions reuse this step with
    pos0 = cached-prefix length to prefill only the uncached suffix against
    blocks already holding the shared prefix's KV."""
    (plan, policy, max_seq, p_specs, row_spec, tok_spec, c_struct, c_specs,
     in_specs, in_structs) = _chunk_scaffold(
        cfg, shape, mesh, layout=layout, width=chunk_tokens, policy=policy,
        max_seq=max_seq, reduce_method=reduce_method,
        kv_cache_dtype=kv_cache_dtype, weight_dtype=weight_dtype,
        fuse_epilogues=fuse_epilogues, kind="chunked prefill")

    def run(params, tokens, pos0, chunk_len, caches, tables, lane):
        col.set_reduce_method(plan.reduce_method)   # T3 schedule selection
        return lm.forward_chunk(params, tokens, pos0, chunk_len, caches,
                                tables, plan=plan, cfg=cfg, policy=policy,
                                lane=lane, paged_segments=layout.segments)

    body = (run if with_sampling
            else (lambda params, tokens, pos0, chunk_len, caches, tables:
                  run(params, tokens, pos0, chunk_len, caches, tables,
                      None)))
    if with_sampling:
        l_specs = resolve_pspecs(lane_dims(False), plan)
        in_specs += (l_specs,)
        in_structs += (with_shardings(lane_struct(shape.global_batch, False),
                                      l_specs, mesh),)
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs,
                          out_specs=(row_spec, c_specs, row_spec))
    fn = jax.jit(sm, donate_argnums=(4,))
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs, in_specs=in_specs,
                      aux={"param_specs": p_specs, "cache_struct": c_struct,
                           "cache_specs": c_specs, "max_seq": max_seq,
                           "paged": layout, "chunk_tokens": chunk_tokens})


# --------------------------------------------------------------------------
# speculative verify step (multi-token AR)
# --------------------------------------------------------------------------

def make_verify_step(cfg: ModelConfig, shape: ShapeConfig,
                     mesh: Optional[Mesh], *,
                     layout: PagedLayout,
                     num_tokens: int,
                     policy: Optional[Policy] = None,
                     max_seq: Optional[int] = None,
                     reduce_method: str = "ring",
                     kv_cache_dtype: str = "bfloat16",
                     weight_dtype: str = "bfloat16",
                     fuse_epilogues: bool = True) -> StepBundle:
    """Speculative-decoding verification: one target forward over
    `num_tokens` = k+1 consecutive tokens per decode slot (the pending
    token + k draft proposals), writing their KV straight into the slot's
    paged blocks and returning the target's own next-token choice at EVERY
    position (lm.forward_verify) — the chunked-prefill machinery pointed
    at decode-time positions, with a per-position sampling head instead of
    a final-position one.

    `shape` must be the decode shape the engine's decode step was built
    with: the cache pytree (and its shardings) is shared across
    decode / chunk / verify steps, and caches are donated here for the
    same in-place update.

    fn(params, tokens [B, C], pos0 [B], chunk_len [B], caches,
       tables [B, MB], lane) -> (choices [B, C], caches, pos [B])

    Rows whose chunk_len is 0 (empty / still-prefilling slots) write
    nothing (their table rows are -1, so scatters drop) and their choices
    are garbage the caller ignores."""
    (plan, policy, max_seq, p_specs, row_spec, tok_spec, c_struct, c_specs,
     in_specs, in_structs) = _chunk_scaffold(
        cfg, shape, mesh, layout=layout, width=num_tokens, policy=policy,
        max_seq=max_seq, reduce_method=reduce_method,
        kv_cache_dtype=kv_cache_dtype, weight_dtype=weight_dtype,
        fuse_epilogues=fuse_epilogues, kind="speculative verify")

    def body(params, tokens, pos0, chunk_len, caches, tables, lane):
        col.set_reduce_method(plan.reduce_method)   # T3 schedule selection
        return lm.forward_verify(params, tokens, pos0, chunk_len, caches,
                                 tables, plan=plan, cfg=cfg, policy=policy,
                                 lane=lane, paged_segments=layout.segments)

    l_specs = resolve_pspecs(lane_dims(False), plan)
    in_specs += (l_specs,)
    in_structs += (with_shardings(lane_struct(shape.global_batch, False),
                                  l_specs, mesh),)
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs,
                          out_specs=(tok_spec, c_specs, row_spec))
    fn = jax.jit(sm, donate_argnums=(4,))
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs, in_specs=in_specs,
                      aux={"param_specs": p_specs, "cache_struct": c_struct,
                           "cache_specs": c_specs, "max_seq": max_seq,
                           "paged": layout, "num_tokens": num_tokens})


def make_tree_verify_step(cfg: ModelConfig, shape: ShapeConfig,
                          mesh: Optional[Mesh], *,
                          layout: PagedLayout,
                          num_tokens: int,
                          policy: Optional[Policy] = None,
                          max_seq: Optional[int] = None,
                          reduce_method: str = "ring",
                          kv_cache_dtype: str = "bfloat16",
                          weight_dtype: str = "bfloat16",
                          fuse_epilogues: bool = True) -> StepBundle:
    """Tree-speculative verification: `make_verify_step` generalized from a
    token *chain* to a flattened token *tree* of `num_tokens` = 1 + k*b
    nodes per slot (lm.forward_verify_tree).  Two extra operands after
    chunk_len carry the per-slot tree shape — `depth` [B, C] int32 (each
    node's tree depth; rope + the sampling step key off pos0 + depth) and
    `anc` [B, C, C] bool (ancestor-or-self matrix; the intra-chunk
    attention mask) — while node KV still scatters at pos0 + node index.

    fn(params, tokens [B, C], pos0 [B], chunk_len [B], depth [B, C],
       anc [B, C, C], caches, tables [B, MB], lane)
      -> (choices [B, C], caches, pos [B])

    Rows are free to carry any ancestor-closed flatten-order prefix —
    full trees, shallower trees, or a plain chain (depth == node index,
    anc lower triangular, which reduces bit-exactly to make_verify_step's
    math) — so one compiled step serves per-slot tree truncation and the
    scheduler's shrink-to-chain degrade rung without recompiling."""
    (plan, policy, max_seq, p_specs, row_spec, tok_spec, c_struct, c_specs,
     in_specs, in_structs) = _chunk_scaffold(
        cfg, shape, mesh, layout=layout, width=num_tokens, policy=policy,
        max_seq=max_seq, reduce_method=reduce_method,
        kv_cache_dtype=kv_cache_dtype, weight_dtype=weight_dtype,
        fuse_epilogues=fuse_epilogues, kind="tree speculative verify")

    def body(params, tokens, pos0, chunk_len, depth, anc, caches, tables,
             lane):
        col.set_reduce_method(plan.reduce_method)   # T3 schedule selection
        return lm.forward_verify_tree(params, tokens, pos0, chunk_len,
                                      depth, anc, caches, tables, plan=plan,
                                      cfg=cfg, policy=policy, lane=lane,
                                      paged_segments=layout.segments)

    n = shape.global_batch
    anc_spec = plan.pspec("batch", None, None)
    l_specs = resolve_pspecs(lane_dims(False), plan)
    # splice depth + anc in after chunk_len (scaffold order: params, tokens,
    # pos0, chunk_len, caches, tables)
    in_specs = (in_specs[:4] + (tok_spec, anc_spec) + in_specs[4:]
                + (l_specs,))
    in_structs = (
        in_structs[:4]
        + (with_shardings(jax.ShapeDtypeStruct((n, num_tokens), jnp.int32),
                          tok_spec, mesh),
           with_shardings(jax.ShapeDtypeStruct(
               (n, num_tokens, num_tokens), jnp.bool_), anc_spec, mesh))
        + in_structs[4:]
        + (with_shardings(lane_struct(n, False), l_specs, mesh),))
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs,
                          out_specs=(tok_spec, c_specs, row_spec))
    fn = jax.jit(sm, donate_argnums=(6,))
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs, in_specs=in_specs,
                      aux={"param_specs": p_specs, "cache_struct": c_struct,
                           "cache_specs": c_specs, "max_seq": max_seq,
                           "paged": layout, "num_tokens": num_tokens})


# --------------------------------------------------------------------------
# decode step (AR)
# --------------------------------------------------------------------------

def make_decode_step(cfg: ModelConfig, shape: ShapeConfig,
                     mesh: Optional[Mesh], *,
                     policy: Optional[Policy] = None,
                     max_seq: Optional[int] = None,
                     reduce_method: str = "ring",
                     kv_cache_dtype: str = "bfloat16",
                     weight_dtype: str = "bfloat16",
                     with_sampling: bool = False,
                     paged: Optional[Tuple[int, int]] = None,
                     fuse_epilogues: bool = True) -> StepBundle:
    """`paged`: (num_blocks, block_size) — build the step against a
    block-paged KV cache: full-attention k/v leaves become global pools and
    the step takes a [B, max_blocks] block-table operand after the caches
    (`pos` carries the per-slot valid lengths).  The resolved `PagedLayout`
    (pool rounded up to the cache-shard count) lands in aux["paged"].
    Cache buffers are donated either way, so each step updates them in
    place instead of allocating a fresh B x max_seq (or pool-sized) copy."""
    import dataclasses
    policy = policy or default_policy(cfg, "serve")
    plan = make_plan(cfg, shape, mesh, mode="serve",
                     reduce_method=reduce_method)
    plan = dataclasses.replace(plan, kv_cache_dtype=kv_cache_dtype,
                               weight_dtype=weight_dtype,
                               fuse_epilogues=fuse_epilogues)
    max_seq = max_seq or shape.seq_len

    layout = None
    if paged is not None:
        layout = make_paged_layout(cfg, plan, max_seq, *paged)
        assert plan.dp == 1, (
            "paged KV cache requires an unsharded decode batch (the pool is "
            f"shared across slots): dp={plan.dp}")

    p_dims, p_struct = _serve_param_layout(cfg, policy, weight_dtype)
    p_specs = resolve_pspecs(p_dims, plan)
    c_struct, c_dims = cache_layout(cfg, plan, shape.global_batch, max_seq,
                                    policy, paged=layout)
    c_specs = resolve_pspecs(c_dims, plan)
    tok_spec = plan.pspec("batch")
    d_struct = frontends.decode_struct(shape.global_batch)

    def run(params, token, pos, caches, tables, lane):
        tok, caches = lm.forward_decode(
            params, token, pos, caches, plan=plan, cfg=cfg, policy=policy,
            lane=lane, block_tables=tables,
            paged_segments=layout.segments if layout else None)
        return tok, pos + 1, caches

    if layout is not None:
        body = (run if with_sampling
                else (lambda params, token, pos, caches, tables:
                      run(params, token, pos, caches, tables, None)))
    elif with_sampling:
        body = (lambda params, token, pos, caches, lane:
                run(params, token, pos, caches, None, lane))
    else:
        body = (lambda params, token, pos, caches:
                run(params, token, pos, caches, None, None))
    in_specs = (p_specs, tok_spec, tok_spec, c_specs)
    in_structs = (with_shardings(p_struct, p_specs, mesh),
                  with_shardings(d_struct["token"], tok_spec, mesh),
                  with_shardings(d_struct["pos"], tok_spec, mesh),
                  with_shardings(c_struct, c_specs, mesh))
    if layout is not None:
        t_spec = plan.pspec("batch", None)
        t_struct = jax.ShapeDtypeStruct(
            (shape.global_batch, layout.max_blocks), jnp.int32)
        in_specs += (t_spec,)
        in_structs += (with_shardings(t_struct, t_spec, mesh),)
    if with_sampling:
        l_specs = resolve_pspecs(lane_dims(False), plan)
        in_specs += (l_specs,)
        in_structs += (with_shardings(lane_struct(shape.global_batch, False),
                                      l_specs, mesh),)
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs,
                          out_specs=(tok_spec, tok_spec, c_specs))
    fn = jax.jit(sm, donate_argnums=(3,))
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs,
                      in_specs=in_specs,
                      aux={"param_specs": p_specs, "cache_struct": c_struct,
                           "cache_specs": c_specs, "max_seq": max_seq,
                           "param_dims": p_dims, "paged": layout})


def make_draft_topk_step(cfg: ModelConfig, shape: ShapeConfig,
                         mesh: Optional[Mesh], *,
                         branches: int,
                         policy: Optional[Policy] = None,
                         max_seq: Optional[int] = None,
                         reduce_method: str = "ring",
                         weight_dtype: str = "bfloat16",
                         fuse_epilogues: bool = True) -> StepBundle:
    """Draft decode step for tree speculation: `make_decode_step`'s dense
    sampling variant, except each call also returns the row's top
    `branches` token candidates (candidate 0 == the sampled/greedy token,
    so the draft chain itself is unchanged — siblings are a free byproduct
    of the same unembedding matmul).

    fn(params, token [B], pos [B], caches, lane)
      -> (tok [B], alts [B, branches], pos + 1, caches)

    Dense (non-paged) only, matching the draft cache the runner keeps."""
    import dataclasses
    policy = policy or default_policy(cfg, "serve")
    plan = make_plan(cfg, shape, mesh, mode="serve",
                     reduce_method=reduce_method)
    plan = dataclasses.replace(plan, weight_dtype=weight_dtype,
                               fuse_epilogues=fuse_epilogues)
    max_seq = max_seq or shape.seq_len

    p_dims, p_struct = _serve_param_layout(cfg, policy, weight_dtype)
    p_specs = resolve_pspecs(p_dims, plan)
    c_struct, c_dims = cache_layout(cfg, plan, shape.global_batch, max_seq,
                                    policy, paged=None)
    c_specs = resolve_pspecs(c_dims, plan)
    tok_spec = plan.pspec("batch")
    alt_spec = plan.pspec("batch", None)
    d_struct = frontends.decode_struct(shape.global_batch)

    def body(params, token, pos, caches, lane):
        tok, alts, caches = lm.forward_decode_topk(
            params, token, pos, caches, n=branches, plan=plan, cfg=cfg,
            policy=policy, lane=lane)
        return tok, alts, pos + 1, caches

    l_specs = resolve_pspecs(lane_dims(False), plan)
    in_specs = (p_specs, tok_spec, tok_spec, c_specs, l_specs)
    in_structs = (with_shardings(p_struct, p_specs, mesh),
                  with_shardings(d_struct["token"], tok_spec, mesh),
                  with_shardings(d_struct["pos"], tok_spec, mesh),
                  with_shardings(c_struct, c_specs, mesh),
                  with_shardings(lane_struct(shape.global_batch, False),
                                 l_specs, mesh))
    sm = _maybe_shard_map(body, mesh, in_specs=in_specs,
                          out_specs=(tok_spec, alt_spec, tok_spec, c_specs))
    fn = jax.jit(sm, donate_argnums=(3,))
    return StepBundle(fn=fn, plan=plan, policy=policy, cfg=cfg,
                      in_structs=in_structs,
                      in_specs=in_specs,
                      aux={"param_specs": p_specs, "cache_struct": c_struct,
                           "cache_specs": c_specs, "max_seq": max_seq,
                           "param_dims": p_dims, "paged": None,
                           "branches": branches})
