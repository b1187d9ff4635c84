"""ModelRunner: policy-free model execution for the serving engine.

Owns everything device-side — the compiled `StepBundle`s (decode, per-bucket
prefill, chunked prefill, per-bucket encode), the live caches, the
`BlockAllocator` and block tables, the sampling lanes, and the per-slot
token/pos state — and exposes exactly four execution verbs:

  prefill(group, stats)       one batched NAR pass admitting a group of
                              GenerateTasks into free decode slots
  chunk_step(task, stats)     advance one chunked-prefill piece for a task
                              parked in a slot (see begin_chunked)
  decode(stats)               one AR step over every *decoding* slot
                              (= decode_dispatch() + decode_commit(): the
                              overlapped engine loop splits them, running
                              host scheduling work — or the next dispatch —
                              between launch and token fetch)
  spec_decode(stats)          one speculative round (draft proposals ->
                              multi-token verify -> commit/rollback) over
                              every decoding slot, replacing decode() when
                              a SpecConfig is set (serving/spec.py)
  encode(group, stats)        one pooled full-sequence pass for a batch of
                              EncodeTasks (no slots, no cache)

No scheduling decisions happen here: which tasks to admit, in what order,
in what chunk budget, and who to preempt is the SchedulerPolicy's job
(serving/scheduler.py); the engine (serving/engine.py) wires queue ->
policy -> runner.  The runner's only "choice" is mechanical bookkeeping:
block alloc/free, table maintenance, lane scatter, retirement plumbing.

Chunked-prefill state: a task mid-chunk occupies a slot but its block-table
row is NOT installed in the decode tables until the final chunk lands — so
interleaved decode steps write nothing into its blocks (absent table rows
scatter-drop) and its garbage token/pos rows are ignored.  The chunk state
that persists between engine steps is exactly (block tables, prefilled
count): what PR 2's paged layout already carries.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import blocks
from repro.launch import steps as steps_mod
from repro.serving.kv_cache import (BlockAllocator, make_block_copy,
                                    make_prefill_scatter, make_row_copy,
                                    zero_caches)
from repro.models.quantize import quantize_params
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import (device_lane, set_lane, stack_lanes,
                                    stack_prefill_lanes, upload, zero_lane)
from repro.serving import trace
from repro.serving.spec import (DraftState, SpecConfig, accept_length,
                                accept_tree_path, build_tree, resolve_draft,
                                round_annotation, spec_support_reason,
                                trim_emitted)
from repro.serving.stats import EngineStats
from repro.serving.tasks import EncodeTask, GenerateTask, Task


@dataclass
class DecodeHandle:
    """One in-flight AR step: the device token future plus the host
    snapshot needed to commit it later.  `decode_dispatch` returns one;
    `decode_commit` consumes it.  The overlapped engine loop
    (serving/engine.py, overlap=True) holds at most one pending handle and
    runs host scheduling work — or even dispatches the NEXT step, chained
    on `tok_d` device-side — before fetching this one's tokens."""
    tok_d: object                               # [B] int32 device future
    t0: float                                   # dispatch wall-clock
    decoding: List[Tuple[int, GenerateTask]]    # slots this step decoded
    live_tokens: int                            # post-step pos over decoding
    blocks_used: int                            # allocator.num_used at dispatch
    t_disp: float = 0.0                         # dispatch-return wall-clock
    #                                             (the commit-side overlap
    #                                             lag is t_fetch - t_disp)


def _device_nbytes(x) -> int:
    """Bytes one device holds for array `x` (first addressable shard;
    replicated arrays charge their full size, as every device keeps a
    copy)."""
    shards = getattr(x, "addressable_shards", None)
    if shards:
        return shards[0].data.nbytes
    return x.nbytes


class ModelRunner:
    """Compiled steps + caches + pool for one serving engine instance."""

    def __init__(self, cfg: ModelConfig, params, *, batch_size: int = 4,
                 max_seq: int = 256, mesh=None, policy=None,
                 min_bucket: int = 8, paged: bool = True,
                 block_size: int = 16, kv_pool_blocks: Optional[int] = None,
                 fuse_epilogues: bool = True,
                 spec: Optional[SpecConfig] = None, draft_params=None,
                 prefix_cache: bool = False,
                 cache_blocks: Optional[int] = None,
                 weight_dtype: str = "bfloat16",
                 kv_dtype: Optional[str] = None,
                 tracer=None):
        assert min_bucket >= 1, f"min_bucket must be >= 1: {min_bucket}"
        assert weight_dtype in ("bfloat16", "int8"), weight_dtype
        assert kv_dtype in (None, "bfloat16", "int8"), kv_dtype
        self.cfg = cfg
        # opt-in ring tracer (serving/trace.py): the stage spans below
        # also push to it, and request-row hooks sit behind `if
        # self.tracer:`.  Pure observers: nothing they record feeds back
        # into scheduling or sampling.
        self.tracer = tracer
        # seconds spent blocked in "*.fetch" spans (host waits on the chip)
        self.fetch_s = 0.0
        # weight-only int8 (models/quantize): the dense GEMM weights are
        # quantized ONCE here, per output channel; every compiled step then
        # streams int8 tiles and dequantizes inside the fp32 epilogue.
        # `params` arrives as the usual full-precision tree.
        self.weight_dtype = weight_dtype
        if weight_dtype == "int8":
            params = quantize_params(params)
        self.params = params
        self.B = batch_size
        self.max_seq = max_seq
        self.min_bucket = min_bucket
        self.mesh = mesh
        self.policy = policy                 # precision policy (not sched)
        # fused prologue/epilogue GEMM pipeline (sharding/plan.py); the
        # unfused chain is kept for A/B parity (token-identical on the
        # reference dispatch path)
        self.fuse_epilogues = fuse_epilogues
        # pad-to-bucket is exact only for linear attention caches; recurrent
        # / ring-buffer archs (SSM hybrids, sliding window) prefill at exact
        # prompt length — their state would absorb pad positions
        self._pad_buckets = not (cfg.has_ssm or cfg.sliding_window > 0)
        # encode has no cache: padding is exact whenever every kind is
        # causal (pads sit after the true positions and are never pooled);
        # bidirectional kinds (enc/vit, or causal=False) attend their pads
        self._encode_pad = all(blocks.kind_causal(k, cfg)
                               for k, _ in cfg.schedule)
        # VLM patch prefix rides along in every prefill: it consumes cache
        # positions, shrinking the token budget a prompt may use
        self._n_prefix = cfg.n_patches or 0
        dshape = ShapeConfig("engine_decode", "decode", max_seq, batch_size)
        # the pool is shared across slots: a batch-sharded decode would give
        # each data shard a divergent pool copy -> fall back to dense rows
        if paged and steps_mod.serve_dp(cfg, dshape, mesh) > 1:
            paged = False
        self.paged = paged
        # int8 KV needs the block-paged pools (per-block scale granularity);
        # a dense fallback layout silently stays lossless bf16
        self.kv_dtype = ("int8" if kv_dtype == "int8" and paged
                         else "bfloat16")
        if paged:
            default_blocks = batch_size * (-(-max_seq // block_size))
            paged_arg: Optional[Tuple[int, int]] = (
                kv_pool_blocks or default_blocks, block_size)
        else:
            paged_arg = None
        self.decode_step = steps_mod.make_decode_step(
            cfg, dshape, mesh, policy=policy, max_seq=max_seq,
            with_sampling=True, paged=paged_arg,
            kv_cache_dtype=self.kv_dtype, weight_dtype=weight_dtype,
            fuse_epilogues=fuse_epilogues)
        self.layout = self.decode_step.aux["paged"]
        if mesh is not None:
            # lay the weights out once as every step's specs expect them
            # (a no-op for params built with these shardings); unplaced
            # params would be re-sent to the mesh on every step call
            params = self.params = jax.device_put(
                params, steps_mod.to_shardings(
                    self.decode_step.aux["param_specs"], mesh))
        self._prefill_steps: Dict[tuple, steps_mod.StepBundle] = {}
        self._encode_steps: Dict[tuple, steps_mod.StepBundle] = {}
        self._chunk_steps: Dict[int, steps_mod.StepBundle] = {}
        self.caches = zero_caches(self.decode_step.aux["cache_struct"],
                                  steps_mod.to_shardings(
                                      self.decode_step.aux["cache_specs"],
                                      mesh))
        if self.paged:
            self.allocator = BlockAllocator(self.layout.num_blocks,
                                            self.layout.block_size)
            self.block_tables = np.full(
                (batch_size, self.layout.max_blocks), -1, np.int32)
            self._scatter = make_prefill_scatter(self.layout.segments,
                                                 self.layout.block_size)
        else:
            self.allocator = None
            self.block_tables = None
            self._scatter = make_prefill_scatter(
                (False,) * len(cfg.schedule), 1)
        # chunked prefill needs every segment's KV in the pool (the tables
        # ARE the chunk state) and a token-only causal stack
        self.chunk_unsupported_reason = steps_mod.chunk_support_reason(
            cfg, self.layout if self.paged else None)
        self.supports_chunked = self.chunk_unsupported_reason is None
        # -- prefix cache (serving/prefix_cache.py): warm admissions reuse
        # cached prompt-prefix blocks and chunk-prefill only the suffix;
        # requires the chunk stack (suffix prefill IS a chunk at pos0 > 0)
        self.prefix_cache: Optional[PrefixCache] = None
        self.prefix_cache_reason: Optional[str] = None
        self.cow_copies = 0
        if prefix_cache:
            if self.supports_chunked:
                self.prefix_cache = PrefixCache(
                    self.allocator, self.layout.block_size,
                    max_blocks=cache_blocks)
                self._block_copy = make_block_copy(self.layout.segments)
            else:
                self.prefix_cache_reason = self.chunk_unsupported_reason
        self._slot_blocks: List[List[int]] = [[] for _ in range(batch_size)]
        self._tables_dev = None            # device copy, rebuilt when dirty
        self._admit_seq = 0
        # -- speculative decoding (serving/spec.py) --------------------
        # the draft LM is a second, much smaller model sharing the
        # target's vocabulary: its own dense per-slot cache, its own
        # decode/prefill bundles, proposals verified by ONE multi-token
        # target pass (make_verify_step) per round instead of one target
        # pass per token
        self.spec = spec
        if spec is not None:
            reason = spec_support_reason(cfg)
            if reason is None and not self.supports_chunked:
                reason = ("engine layout cannot carry multi-token verify "
                          "state (paged KV cache with every segment paged "
                          "and dp == 1 required)")
            if reason is not None:
                raise ValueError(f"speculative decoding unsupported for "
                                 f"{cfg.name}: {reason}")
            self.draft_cfg = resolve_draft(spec, cfg)
            # the draft LM stays bf16 — it is tiny (its weight traffic is
            # noise next to the target's) — unless it IS the target
            # ("self"), whose params are already quantized above
            self._draft_wdt = (weight_dtype if spec.draft == "self"
                               else "bfloat16")
            if spec.draft == "self":
                self.draft_params = params
            elif draft_params is not None:
                self.draft_params = draft_params
            else:
                from repro.models import lm as lm_mod
                pdtype = jax.tree.leaves(params)[0].dtype
                self.draft_params = lm_mod.init_lm(
                    jax.random.key(spec.draft_seed), self.draft_cfg, pdtype)
            # token-tree speculation (spec.branches > 1): the draft's
            # top-b candidates per depth become sibling leaves verified in
            # one tree-masked pass.  int8 KV pools force the single-chain
            # round: accepted-path compaction moves KV rows ACROSS blocks,
            # which per-block quantization scales cannot follow.
            self.tree_branches = (1 if self.kv_dtype == "int8"
                                  else spec.branches)
            self._tree_chain_only = False  # engine degrade rung 1 sets it
            self._round_depth: Optional[np.ndarray] = None
            self._round_width: Optional[np.ndarray] = None
            dshape_draft = ShapeConfig("draft_decode", "decode",
                                       max_seq, batch_size)
            if self.tree_branches > 1:
                self.draft_topk_step = steps_mod.make_draft_topk_step(
                    self.draft_cfg, dshape_draft, mesh,
                    branches=self.tree_branches, policy=policy,
                    max_seq=max_seq, weight_dtype=self._draft_wdt,
                    fuse_epilogues=fuse_epilogues)
                self.draft_decode_step = None
                self.tree_verify_step = steps_mod.make_tree_verify_step(
                    cfg, dshape, mesh, layout=self.layout,
                    num_tokens=1 + spec.k * self.tree_branches,
                    policy=policy, max_seq=max_seq,
                    kv_cache_dtype=self.kv_dtype, weight_dtype=weight_dtype,
                    fuse_epilogues=fuse_epilogues)
                self.verify_step = None
                self._row_copy = make_row_copy(self.layout.segments)
                dstep = self.draft_topk_step
            else:
                self.draft_decode_step = steps_mod.make_decode_step(
                    self.draft_cfg, dshape_draft,
                    mesh, policy=policy, max_seq=max_seq, with_sampling=True,
                    paged=None, weight_dtype=self._draft_wdt,
                    fuse_epilogues=fuse_epilogues)
                self.draft_topk_step = None
                self.verify_step = steps_mod.make_verify_step(
                    cfg, dshape, mesh, layout=self.layout,
                    num_tokens=spec.k + 1, policy=policy, max_seq=max_seq,
                    kv_cache_dtype=self.kv_dtype, weight_dtype=weight_dtype,
                    fuse_epilogues=fuse_epilogues)
                self.tree_verify_step = None
                dstep = self.draft_decode_step
            self.draft_caches = zero_caches(
                dstep.aux["cache_struct"],
                steps_mod.to_shardings(dstep.aux["cache_specs"], mesh))
            self._draft_prefill_steps: Dict[tuple,
                                            steps_mod.StepBundle] = {}
            self._draft_scatter = make_prefill_scatter(
                (False,) * len(self.draft_cfg.schedule), 1)
            self.draft_states: List[Optional[DraftState]] = (
                [None] * batch_size)
        else:
            self.draft_cfg = None
            self.tree_branches = 1
            self.draft_states = [None] * batch_size
        # token/pos live HOST-side: per-slot updates (prefill landing, chunk
        # completion) index by a python int, and a device `.at[b].set()`
        # would jit-compile once per distinct slot index — a 20-50ms spike
        # in the middle of serving.  [B] int32 transfers per step are noise.
        self.tokens = np.zeros((batch_size,), np.int32)
        self.pos = np.zeros((batch_size,), np.int32)
        # device-side copy of `tokens` chained from the last decode step's
        # output: a dispatch may feed it straight back into the next step
        # without a host round-trip.  Any HOST write to a token row
        # (prefill landing, chunk completion, spec commit) invalidates it.
        self._tok_dev = None
        self._t_last_commit: Optional[float] = None
        self.lane = zero_lane(batch_size)
        self.slots: List[Optional[GenerateTask]] = [None] * batch_size
        # slots holding a task whose prompt is still chunk-prefilling: their
        # table rows stay out of the decode tables and their token/pos rows
        # are garbage until the final chunk lands
        self.prefilling: List[bool] = [False] * batch_size
        self.steps_run = 0

    # -- resident-memory telemetry -------------------------------------
    def weight_bytes_per_device(self) -> int:
        """Per-device resident bytes of the target params (int8 `q` leaves
        count 1 byte/elem; their fp32 scales ride along)."""
        return sum(_device_nbytes(x) for x in jax.tree.leaves(self.params))

    def kv_pool_bytes(self) -> int:
        """Per-device resident bytes of the live decode caches — the paged
        pools plus their scale leaves and any dense (ring / cross-attn /
        SSM) state."""
        return sum(_device_nbytes(x) for x in jax.tree.leaves(self.caches))

    # -- capacity / bucket geometry ------------------------------------
    @property
    def prompt_cap(self) -> int:
        """Longest admissible prompt (one decode position + patch prefix
        reserved)."""
        return self.max_seq - 1 - self._n_prefix

    def bucket_for(self, prompt_len: int) -> int:
        """Prefill length bucket for a prompt: smallest rung of
        {m, 1.5m} x 2^k >= max(min_bucket, len), capped at the token budget
        (max_seq minus any patch prefix); exact length for archs whose
        caches cannot absorb padding."""
        if not self._pad_buckets:
            return prompt_len
        return self._bucket(prompt_len)

    def encode_bucket_for(self, prompt_len: int) -> int:
        """Length bucket for an EncodeTask batch (no cache: exactness is
        about attention masks, not cache state — see _encode_pad)."""
        if not self._encode_pad:
            return prompt_len
        return self._bucket(prompt_len)

    def _bucket(self, n: int) -> int:
        cap = self.max_seq - self._n_prefix
        base = self.min_bucket
        while True:
            for cand in (base, base + base // 2):
                if cand >= n or cand >= cap:
                    return min(cand, cap)
            base *= 2

    # -- step compilation caches ---------------------------------------
    def _prefill_for(self, bucket: int, group: int,
                     stats: EngineStats) -> steps_mod.StepBundle:
        step = self._prefill_steps.get((bucket, group))
        if step is None:
            pshape = ShapeConfig(f"engine_prefill_{bucket}x{group}",
                                 "prefill", bucket, group)
            # NOTE: no kv_cache_dtype here even when the pool is int8 — the
            # compact prefill caches stay bf16 and the admission scatter
            # (kv_cache._prefill_scatter) quantizes on entry to the pool
            step = steps_mod.make_prefill_step(
                self.cfg, pshape, self.mesh, policy=self.policy,
                max_seq=self.max_seq, with_sampling=True,
                compact_kv=self.paged, weight_dtype=self.weight_dtype,
                fuse_epilogues=self.fuse_epilogues)
            self._prefill_steps[(bucket, group)] = step
            stats.prefill_compiles += 1
        return step

    def _encode_for(self, bucket: int, group: int, pooling: str,
                    stats: EngineStats) -> steps_mod.StepBundle:
        step = self._encode_steps.get((bucket, group, pooling))
        if step is None:
            eshape = ShapeConfig(f"engine_encode_{bucket}x{group}",
                                 "prefill", bucket + self._n_prefix, group)
            step = steps_mod.make_encode_step(
                self.cfg, eshape, self.mesh, policy=self.policy,
                pooling=pooling, weight_dtype=self.weight_dtype,
                fuse_epilogues=self.fuse_epilogues)
            self._encode_steps[(bucket, group, pooling)] = step
            stats.encode_compiles += 1
        return step

    def _chunk_for(self, chunk_tokens: int) -> steps_mod.StepBundle:
        step = self._chunk_steps.get(chunk_tokens)
        if step is None:
            cshape = ShapeConfig(f"engine_chunk_{chunk_tokens}", "decode",
                                 self.max_seq, 1)
            step = steps_mod.make_chunk_prefill_step(
                self.cfg, cshape, self.mesh, layout=self.layout,
                chunk_tokens=chunk_tokens, policy=self.policy,
                max_seq=self.max_seq, with_sampling=True,
                kv_cache_dtype=self.kv_dtype,
                weight_dtype=self.weight_dtype,
                fuse_epilogues=self.fuse_epilogues)
            self._chunk_steps[chunk_tokens] = step
        return step

    # -- slot / pool bookkeeping ---------------------------------------
    def free_slots(self) -> List[int]:
        return [b for b in range(self.B) if self.slots[b] is None]

    def running(self) -> List[GenerateTask]:
        return [t for t in self.slots if t is not None]

    def has_running(self) -> bool:
        return any(s is not None for s in self.slots)

    def full_prompt(self, task: GenerateTask) -> np.ndarray:
        """The token sequence a (re-)prefill must encode: the prompt plus
        any tokens already generated before a preemption."""
        if not task.output:
            return np.asarray(task.prompt, np.int32)
        return np.concatenate([np.asarray(task.prompt, np.int32),
                               np.asarray(task.output, np.int32)])

    def full_len(self, task: GenerateTask) -> int:
        """len(full_prompt(task)) without materializing it."""
        return task.prompt_len + len(task.output)

    def blocks_needed(self, task: GenerateTask) -> int:
        return self.allocator.blocks_for(self._n_prefix + self.full_len(task))

    def alloc_for(self, task: GenerateTask) -> Optional[List[int]]:
        """All-or-nothing block allocation for (re-)admitting `task`."""
        if not self.paged:
            return []
        return self.allocator.alloc(self.blocks_needed(task))

    def release_slot(self, b: int):
        if self.paged and self._slot_blocks[b]:
            if self.prefix_cache is not None:
                self._index_slot(b)
            self.allocator.free(self._slot_blocks[b])
        self._slot_blocks[b] = []
        if self.paged:
            self.block_tables[b, :] = -1
            self._tables_dev = None
        self.slots[b] = None
        self.prefilling[b] = False
        self.draft_states[b] = None

    def evict(self, b: int) -> GenerateTask:
        """Pull the task out of slot `b`, releasing its blocks (recompute
        preemption: the engine re-queues it; a mid-chunk prefill restarts
        from scratch on re-admission — with the prefix cache on, the
        released blocks stay indexed, so the recompute is itself a warm
        admission as long as the pool doesn't reclaim them first)."""
        task = self.slots[b]
        if self.tracer:
            self.tracer.instant("preempt", time.perf_counter(), tid=task.uid,
                                recompute_tokens=self.full_len(task))
        self.release_slot(b)      # indexes [0, prefilled/pos) before reset
        task.prefilled = 0
        return task

    # -- prefix cache (serving/prefix_cache.py) ------------------------
    def cached_tokens_for(self, task: GenerateTask) -> int:
        """Peek at the cached-prefix length for `task` (no LRU touch, no
        hit-rate accounting) — the scheduler's cache-aware admission probe."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.lookup(
            self.full_prompt(task), limit=self.full_len(task) - 1,
            touch=False, record=False)[1]

    def admit_cached(self, task: GenerateTask, b: int) -> Optional[bool]:
        """Warm admission: look up the longest cached prefix of the (re-)
        prefill sequence, share those blocks into slot `b`'s table, and
        park the task prefilling with `prefilled = hit` so only the suffix
        gets encoded (chunk_step at pos0 = hit).

        The hit is capped at full_len - 1: the final position must be
        prefilled live to produce the sampling logits.  A hit ending
        mid-block copy-on-writes the shared tail before the suffix
        overwrites its trailing positions.

        Returns None on a cache miss (caller falls back to whole-prompt
        admission), False when the pool cannot supply the uncached blocks
        (caller stops admitting this step), True when seated."""
        pc = self.prefix_cache
        full = self.full_prompt(task)
        hit_blocks, hit = pc.lookup(full, limit=len(full) - 1)
        if hit <= 0:
            return None
        # pin the shared blocks before anything that could evict them
        self.allocator.retain(hit_blocks)
        bs = self.layout.block_size
        n_hit = len(hit_blocks)
        partial = hit % bs != 0
        need = self.blocks_needed(task) - n_hit + (1 if partial else 0)
        new = self.allocator.alloc(need)
        if new is None:
            self.allocator.free(hit_blocks)     # drop the pins
            return False
        table = list(hit_blocks)
        if partial:
            # COW: the suffix writes positions [hit, ...) of the tail block
            # other holders still depend on — duplicate it first and swap
            # the private copy into this slot's table
            src, dst = table[n_hit - 1], new[0]
            self.caches = self._block_copy(self.caches, src, dst)
            self.allocator.free([src])          # un-pin the shared original
            table[n_hit - 1] = dst
            new = new[1:]
            self.cow_copies += 1
        table.extend(new)
        # parked prefilling like begin_chunked: the decode-table row stays
        # -1 (interleaved decode writes drop) until the final suffix chunk
        # lands in chunk_step
        self._seat(task, b, table)
        self.prefilling[b] = True
        task.prefilled = hit
        task.cached_prefix = hit
        if self.tracer:
            self.tracer.instant("warm_hit", time.perf_counter(),
                                tid=task.uid, cached_prefix=hit, cow=partial)
        return True

    def _index_slot(self, b: int):
        """Index slot `b`'s committed tokens before its blocks are
        released: the full (re-)prefill sequence for a slot that reached
        decode (KV covers [0, pos)), or the prefix landed so far for a slot
        still chunk-prefilling.  Newly indexed blocks gain an allocator
        reference and survive the slot's release until LRU reclaim."""
        task = self.slots[b]
        if task is None:
            return
        n_kv = task.prefilled if self.prefilling[b] else int(self.pos[b])
        if n_kv <= 0:
            return
        nb = self.allocator.blocks_for(n_kv)
        self.prefix_cache.insert(self.full_prompt(task)[:n_kv],
                                 self._slot_blocks[b][:nb])

    def _index_prompt_blocks(self, task: GenerateTask, blk: List[int]):
        """Index the *full* blocks of a freshly prefilled prompt the moment
        its KV lands — later admissions in the same batch already hit.  The
        partial tail keeps changing as the slot decodes, so it only joins
        the index at release time (_index_slot).

        Called after landing appends its sampled token to `task.output`, so
        the landed KV extent is full_len - 1: that last token's KV only
        materializes on the next decode step, and indexing a block that
        straddles it would publish a position the pool hasn't written."""
        if self.prefix_cache is None:
            return
        bs = self.layout.block_size
        n_full = ((self.full_len(task) - 1) // bs) * bs
        if n_full > 0:
            self.prefix_cache.insert(self.full_prompt(task)[:n_full],
                                     blk[:n_full // bs])

    def _tables(self):
        # the host mirrors (tables, pos, tokens, lanes) are written in place
        # while a dispatched step may not have read them yet: `upload`
        if self._tables_dev is None:
            self._tables_dev = upload(self.block_tables)
        return self._tables_dev

    def _fetch(self, name: str, *arrays, **args):
        """Host copies of device `arrays` inside a `*.fetch` stage span:
        the host waits there for the chip.  -> (copies, span)."""
        with trace.span(name, self.tracer, **args) as sp:
            out = [np.asarray(a) for a in arrays]
        self.fetch_s += sp.seconds
        return out, sp

    def ensure_decode_blocks(
            self, select_victim: Callable[[Sequence[Task]], Task],
            stats: EngineStats,
            lookahead: Optional[np.ndarray] = None) -> List[GenerateTask]:
        """Before a decode step: every decoding slot must own the block its
        next token lands in (pos // block_size) — plus, under speculation,
        the blocks the verify chunk's `lookahead[b]` extra positions write
        into (spec_lookahead() caps each row so the need always fits the
        pool).  Allocation failure evicts `select_victim(running)` until it
        succeeds; returns the evicted tasks (the engine re-queues them)."""
        if not self.paged:
            return []
        evicted: List[GenerateTask] = []
        bs = self.layout.block_size
        pos = np.asarray(self.pos)
        for b in range(self.B):
            if self.slots[b] is None or self.prefilling[b]:
                continue
            la = int(lookahead[b]) if lookahead is not None else 0
            need = (int(pos[b]) + la) // bs + 1
            if need > self.allocator.num_blocks:
                # impossible to ever satisfy — fail before preempting (and
                # discarding) every other in-flight request's progress
                raise RuntimeError(
                    f"KV pool too small: request {self.slots[b].uid} needs "
                    f"{need} blocks, pool capacity is "
                    f"{self.allocator.num_blocks} (raise kv_pool_blocks, "
                    f"raise block_size, or cap max_new_tokens)")
            while (self.slots[b] is not None
                   and len(self._slot_blocks[b]) < need):
                got = self.allocator.alloc(1)
                if got is not None:
                    self.block_tables[b, len(self._slot_blocks[b])] = got[0]
                    self._slot_blocks[b].extend(got)
                    self._tables_dev = None
                    continue
                cand = self.running()
                if not cand:
                    raise RuntimeError(
                        "KV pool exhausted with no running request to "
                        "preempt")
                victim = select_victim(cand)
                vb = self.slots.index(victim)
                evicted.append(self.evict(vb))
                stats.preemptions += 1
            # write discipline under prefix sharing: the block the next
            # token lands in must be private to this slot.  Warm admission
            # already COWs the shared tail, so this guard is belt-and-
            # braces — but it makes decode safe against any future sharing
            # path by construction.
            while (self.prefix_cache is not None
                   and self.slots[b] is not None):
                e = int(pos[b]) // bs
                blk = self._slot_blocks[b][e]
                if self.allocator.refcount(blk) <= 1:
                    break
                got = self.allocator.alloc(1)
                if got is not None:
                    self.caches = self._block_copy(self.caches, blk, got[0])
                    self._slot_blocks[b][e] = got[0]
                    self.block_tables[b, e] = got[0]
                    self._tables_dev = None
                    self.allocator.free([blk])
                    self.cow_copies += 1
                    if self.tracer:
                        self.tracer.instant(
                            "cow_copy", time.perf_counter(),
                            tid=self.slots[b].uid, block=blk)
                    break
                cand = self.running()
                if not cand:
                    raise RuntimeError(
                        "KV pool exhausted with no running request to "
                        "preempt")
                victim = select_victim(cand)
                vb = self.slots.index(victim)
                evicted.append(self.evict(vb))
                stats.preemptions += 1
        return evicted

    # -- execution: batched whole-prompt prefill -----------------------
    def prefill(self, group: List[Tuple[GenerateTask, List[int]]],
                free_slots: List[int], stats: EngineStats,
                ) -> List[Tuple[GenerateTask, int]]:
        """One batched NAR pass for an admission group, scattering its KV
        into the assigned blocks (paged) / slot rows (dense).  Returns
        (task, output index) pairs for the freshly sampled first tokens."""
        tasks = [t for t, _ in group]
        fulls = [self.full_prompt(t) for t in tasks]
        bucket = self.bucket_for(len(fulls[0]))
        n = len(tasks)
        step = self._prefill_for(bucket, n, stats)
        slots = free_slots[:n]
        with trace.span(trace.PREFILL, self.tracer, bucket=bucket, group=n,
                        tokens=sum(len(f) for f in fulls),
                        uids=[t.uid for t in tasks]) as sp:
            padded = np.zeros((n, bucket), np.int32)
            for j, seq in enumerate(fulls):
                padded[j, :len(seq)] = seq
            batch = {"tokens": jnp.array(padded)}
            if self.cfg.n_patches:
                batch["patches"] = jnp.zeros(
                    (n, self.cfg.n_patches, self.cfg.d_model), jnp.bfloat16)
            if self.cfg.enc_schedule:
                batch["frames"] = jnp.zeros(
                    (n, self.cfg.enc_seq_padded, self.cfg.d_model),
                    jnp.bfloat16)
            tok, caches_g, pos_g = step.fn(
                self.params, batch,
                stack_prefill_lanes([t.sampling for t in tasks],
                                    [len(f) for f in fulls]))
            if self.paged:
                tables = np.full((n, self.layout.max_blocks), -1, np.int32)
                for j, (_, blk) in enumerate(group):
                    tables[j, :len(blk)] = blk
            else:
                tables = np.zeros((n, 1), np.int32)  # unused by the scatter
            self.caches = self._scatter(self.caches, caches_g,
                                        jnp.array(slots, jnp.int32),
                                        jnp.array(tables))
            (tok_np, pos_np), fetch = self._fetch(trace.PREFILL_FETCH, tok,
                                                  pos_g)
            self.tokens[slots] = tok_np
            self._tok_dev = None    # host token write: the chained device
            #                         copy no longer matches the mirror
            self.pos[slots] = pos_np
        t0, now = sp.t0, fetch.t1
        dt_ms = (now - t0) * 1e3

        fresh: List[Tuple[GenerateTask, int]] = []
        n_first = 0
        for j, (task, blk) in enumerate(group):
            b = slots[j]
            first_admit = not task.output
            task.bucket = bucket
            task.prefill_ms += dt_ms / n   # amortized share of the group
            task.prefilled = len(fulls[j])
            task.output.append(int(tok_np[j]))
            self._seat(task, b, blk)
            if self.paged:
                self.block_tables[b] = tables[j]
                self._tables_dev = None
            self._index_prompt_blocks(task, blk)
            fresh.append((task, len(task.output) - 1))
            stats.bucket_hits[bucket] = stats.bucket_hits.get(bucket, 0) + 1
            if first_admit:
                n_first += 1
                task.ttft_ms = (now - task._t_submit) * 1e3
                stats.nar_tokens += task.prompt_len
                stats.padded_nar_tokens += bucket
                stats.add_ttft_ms(task.ttft_ms)
                if self.tracer:
                    self.tracer.instant("first_token", now, tid=task.uid,
                                        ttft_ms=task.ttft_ms)
            else:
                stats.recompute_tokens += len(fulls[j])
        # preemption recomputes are overhead, not prompt-encoding goodput:
        # split the group's wall time so nar_tok_s stays comparable between
        # preempting and non-preempting runs
        stats.nar_time_s += (now - t0) * n_first / n
        stats.recompute_time_s += (now - t0) * (n - n_first) / n
        stats.prefill_batches += 1
        if self.spec is not None:
            self._draft_prefill(fulls, slots, stats)
        return fresh

    def _seat(self, task: GenerateTask, b: int, blk: List[int]):
        task._seq = self._admit_seq
        self._admit_seq += 1
        self.lane = set_lane(self.lane, b, task.sampling)
        self.slots[b] = task
        self.prefilling[b] = False
        self._slot_blocks[b] = list(blk)

    # -- execution: draft prefill (speculative decoding) ----------------
    def _draft_prefill(self, fulls: List[np.ndarray], slots: List[int],
                       stats: EngineStats):
        """Build the draft LM's dense cache rows for freshly (re-)admitted
        tasks: one batched draft prefill over the same padded token batch
        the target encoded, row-scattered into the draft cache at the
        assigned slots.  Pad positions beyond each row's true length hold
        junk KV the draft never attends (decode masks by pos).  The
        sampled token is discarded — the draft is only ever fed COMMITTED
        tokens, so its first proposal conditions on the target's first
        token, not its own guess."""
        n = len(fulls)
        bucket = self.bucket_for(max(len(f) for f in fulls))
        step = self._draft_prefill_steps.get((bucket, n))
        if step is None:
            pshape = ShapeConfig(f"draft_prefill_{bucket}x{n}", "prefill",
                                 bucket, n)
            step = steps_mod.make_prefill_step(
                self.draft_cfg, pshape, self.mesh, policy=self.policy,
                max_seq=self.max_seq, with_sampling=False, compact_kv=False,
                weight_dtype=self._draft_wdt,
                fuse_epilogues=self.fuse_epilogues)
            self._draft_prefill_steps[(bucket, n)] = step
        t0 = time.perf_counter()
        padded = np.zeros((n, bucket), np.int32)
        for j, seq in enumerate(fulls):
            padded[j, :len(seq)] = seq
        _, dcaches, _ = step.fn(self.draft_params,
                                {"tokens": jnp.array(padded)})
        self.draft_caches = self._draft_scatter(
            self.draft_caches, dcaches, jnp.array(slots, jnp.int32),
            jnp.zeros((n, 1), jnp.int32))
        with trace.span(trace.SPEC_DRAFT_FETCH, self.tracer) as sp:
            jax.block_until_ready(self.draft_caches)   # honest timing
        self.fetch_s += sp.seconds
        for j, b in enumerate(slots):
            self.draft_states[b] = DraftState(pos=len(fulls[j]))
        stats.spec_draft_time_s += time.perf_counter() - t0

    # -- execution: chunked prefill ------------------------------------
    def begin_chunked(self, task: GenerateTask, blk: List[int], b: int):
        """Park `task` in slot `b` with its full block allocation, in the
        prefilling state: its table row stays OUT of the decode tables (so
        interleaved decode steps drop every write to it) until the final
        chunk lands in `chunk_step`."""
        assert self.supports_chunked
        self._seat(task, b, blk)
        self.prefilling[b] = True
        task.prefilled = 0

    def chunk_step(self, task: GenerateTask, chunk_tokens: int,
                   stats: EngineStats) -> Optional[Tuple[GenerateTask, int]]:
        """Advance one <= `chunk_tokens`-sized prefill piece for `task`.
        Returns the (task, output index) first-token event when this chunk
        completes the prompt (the slot then joins decode), else None."""
        b = self.slots.index(task)
        assert self.prefilling[b], task.uid
        full = self.full_prompt(task)
        start = task.prefilled
        step = self._chunk_for(chunk_tokens)
        C = chunk_tokens
        take = min(C, len(full) - start)
        first_admit = not task.output
        with trace.span(trace.CHUNK, self.tracer, width=C, pos0=start,
                        take=take, uids=[task.uid],
                        recompute=not first_admit) as sp:
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :take] = full[start:start + take]
            row_table = np.full((1, self.layout.max_blocks), -1, np.int32)
            row_table[0, :len(self._slot_blocks[b])] = self._slot_blocks[b]
            lane = stack_lanes([task.sampling])
            tok, self.caches, pos_end = step.fn(
                self.params, jnp.array(chunk),
                jnp.array([start], jnp.int32),
                jnp.array([take], jnp.int32),
                self.caches, jnp.array(row_table), lane)
            (tok_np, pos_np), fetch = self._fetch(trace.CHUNK_FETCH, tok,
                                                  pos_end)
        tok_np, pos_np = int(tok_np[0]), int(pos_np[0])
        t0, now = sp.t0, fetch.t1
        task.prefilled = start + take
        task.prefill_ms += (now - t0) * 1e3
        if first_admit:
            stats.nar_tokens += take
            stats.padded_nar_tokens += C
            stats.nar_time_s += now - t0
        else:
            stats.recompute_tokens += take
            stats.recompute_time_s += now - t0
        stats.prefill_chunks += 1
        stats.chunked_prefill_tokens += take
        if task.prefilled < len(full):
            return None
        # final chunk: the sampled token is the prompt's first output and
        # the slot joins the decode batch
        task.bucket = -(-len(full) // chunk_tokens) * chunk_tokens
        task.output.append(tok_np)
        self.tokens[b] = tok_np
        self._tok_dev = None        # host token write invalidates the chain
        self.pos[b] = pos_np
        self.prefilling[b] = False
        if self.paged:
            self.block_tables[b] = row_table[0]
            self._tables_dev = None
        self._index_prompt_blocks(task, self._slot_blocks[b])
        if first_admit:
            task.ttft_ms = (now - task._t_submit) * 1e3
            stats.add_ttft_ms(task.ttft_ms)
            if self.tracer:
                self.tracer.instant("first_token", now, tid=task.uid,
                                    ttft_ms=task.ttft_ms)
        if self.spec is not None:
            # the draft (being small) prefills whole even when the target
            # chunked — one cheap pass once the final chunk lands
            self._draft_prefill([full], [b], stats)
        return (task, len(task.output) - 1)

    # -- execution: AR decode ------------------------------------------
    def decode_dispatch(self) -> DecodeHandle:
        """Launch one lockstep AR step over every decoding slot WITHOUT
        waiting for its tokens — JAX async dispatch returns device futures
        immediately.  The host token/pos mirrors advance eagerly: the
        compiled step returns `pos + 1` for every row (launch/steps.py), so
        `self.pos += 1` is exact, and the returned token future is kept as
        `_tok_dev` so a back-to-back dispatch chains on it device-side
        instead of re-uploading the host mirror."""
        decoding = [(b, self.slots[b]) for b in self.decoding_slots()]
        # a slot whose input sits at position p attends p + 1 positions
        live = sum(int(self.pos[b]) + 1 for b, _ in decoding)
        with trace.span(trace.DECODE_DISPATCH, self.tracer,
                        slots=len(decoding), kv_positions=live,
                        uids=[t.uid for _, t in decoding]) as sp:
            tok_in = (self._tok_dev if self._tok_dev is not None
                      else upload(self.tokens))
            pos_d = upload(self.pos)
            lane_d = device_lane(self.lane)
            if self.paged:
                tok_d, _, self.caches = self.decode_step.fn(
                    self.params, tok_in, pos_d, self.caches,
                    self._tables(), lane_d)
            else:
                tok_d, _, self.caches = self.decode_step.fn(
                    self.params, tok_in, pos_d, self.caches, lane_d)
            self._tok_dev = tok_d
            start_d2h = getattr(tok_d, "copy_to_host_async", None)
            if start_d2h is not None:
                start_d2h()     # non-blocking device_get: the commit's fetch
                #                 finds the bytes already on their way
            self.pos += 1
            self.steps_run += 1
        return DecodeHandle(tok_d, sp.t0, decoding, live,
                            self.allocator.num_used if self.paged else 0,
                            t_disp=sp.t1)

    def decode_commit(self, handle: DecodeHandle, stats: EngineStats,
                      ) -> List[Tuple[GenerateTask, int]]:
        """Fetch a dispatched step's tokens (blocking) and commit them to
        the host mirrors, task outputs and stats.  Under the overlapped
        loop the elapsed-time sample is floored at the previous commit so
        back-to-back pipelined steps don't double-count wall time."""
        # overlap_lag_ms: host wall between dispatch returning and this
        # fetch starting, scheduling work the device step hid
        (toks,), fetch = self._fetch(
            trace.DECODE_FETCH, handle.tok_d, overlap_lag_ms=max(
                0.0, (time.perf_counter() - handle.t_disp) * 1e3))
        now = fetch.t1
        floor = self._t_last_commit
        dt = now - (max(handle.t0, floor) if floor is not None
                    else handle.t0)
        self._t_last_commit = now
        fresh: List[Tuple[GenerateTask, int]] = []
        with trace.span(trace.DECODE_COMMIT, self.tracer):
            for b, task in handle.decoding:
                tok = int(toks[b])
                if self.slots[b] is task:
                    self.tokens[b] = tok    # mirror update, not a host
                    #                         write: _tok_dev stays valid
                task.output.append(tok)
                task.decode_ms += dt * 1e3
                fresh.append((task, len(task.output) - 1))
            stats.decode_steps += 1
            stats.ar_tokens += len(handle.decoding)
            stats.ar_time_s += dt
            stats.add_decode_step_ms(dt * 1e3)
            stats.occupied_slot_steps += len(handle.decoding)
            if self.paged:
                stats.block_slot_steps += handle.blocks_used
                stats.token_slot_steps += handle.live_tokens
        return fresh

    def decode(self, stats: EngineStats) -> List[Tuple[GenerateTask, int]]:
        """One lockstep AR step over every decoding slot (synchronous:
        dispatch + immediate commit).  Returns the (task, output index)
        token events."""
        return self.decode_commit(self.decode_dispatch(), stats)

    def next_token_block_ready(self, b: int) -> bool:
        """Whether decoding slot `b` can take one MORE decode step with no
        allocator/COW work: it already owns the block position `pos[b]`
        writes into, and (under prefix sharing) owns it exclusively.  The
        overlapped loop's dispatch-ahead fast path requires this — it runs
        before `ensure_decode_blocks` would."""
        if not self.paged:
            return True
        bs = self.layout.block_size
        need = int(self.pos[b]) // bs + 1
        if len(self._slot_blocks[b]) < need:
            return False
        if self.prefix_cache is not None:
            blk = self._slot_blocks[b][need - 1]
            if self.allocator.refcount(blk) > 1:
                return False
        return True

    def decoding_slots(self) -> List[int]:
        return [b for b in range(self.B)
                if self.slots[b] is not None and not self.prefilling[b]]

    # -- execution: speculative decode (propose -> verify -> commit) ----
    def spec_lookahead(self) -> np.ndarray:
        """Per-slot speculation depth for the next round: `spec.k` capped
        so the verify chunk's writes stay inside the sequence horizon
        (committing past max_seq - 1 would emit tokens a step-by-step
        decode never reaches), inside the pool's total block capacity (so
        ensure_decode_blocks can always satisfy the lookahead, by
        preemption if necessary), and inside the request's remaining
        max_new_tokens budget (a round commits at most room = budget
        tokens, so proposing past room - 1 would reserve blocks — and
        possibly preempt a neighbor for them — that trim_emitted then
        discards; capping cannot change outputs, each position's verify
        choice being independent of how many proposals follow it).
        Requests admitted degraded (DeadlinePolicy under pressure) get 0
        lookahead — their rounds propose nothing and commit exactly the
        pending token, i.e. plain decode at verify-step cost, still
        token-identical (speculation is lossless at every k).

        Token trees (tree_branches > 1) reserve NODE capacity, not chain
        depth: a depth-d, width-w tree scatters d*w node positions past
        pos.  Each row uses the deepest tree whose node count fits the
        same horizon / pool-capacity caps (at least depth 1), else falls
        back to the plain chain for the round; and when the trees'
        collective extra blocks exceed the pool's FREE blocks, every row
        shrinks to its chain — sibling scratch is pure lookahead and must
        never preempt a neighbor's committed state (the chain lookahead
        may, exactly as at width 1).  The per-row (depth, width) choice
        lands in _round_depth/_round_width for the round that follows."""
        la = np.zeros((self.B,), np.int64)
        chain_la = np.zeros((self.B,), np.int64)
        cap_tokens = self.allocator.num_blocks * self.layout.block_size
        w = self.tree_branches
        depths = np.zeros((self.B,), np.int64)
        widths = np.ones((self.B,), np.int64)
        for b in self.decoding_slots():
            p = int(self.pos[b])
            task = self.slots[b]
            if task.degraded:
                continue
            room = task.max_new_tokens - len(task.output)
            la_c = max(0, min(self.spec.k, self.max_seq - 1 - p,
                              cap_tokens - 1 - p, room - 1))
            chain_la[b] = la_c
            if w > 1 and not self._tree_chain_only:
                la_t = min(la_c, (self.max_seq - 1 - p) // w,
                           (cap_tokens - 1 - p) // w)
                if la_t >= 1:
                    depths[b], widths[b], la[b] = la_t, w, la_t * w
                    continue
            depths[b], la[b] = la_c, la_c
        if w > 1 and bool(np.any(widths > 1)):
            bs = self.layout.block_size
            need = sum(
                max(0, (int(self.pos[b]) + int(la[b])) // bs + 1
                    - len(self._slot_blocks[b]))
                for b in self.decoding_slots())
            if need > self.allocator.num_free:
                for b in self.decoding_slots():
                    if widths[b] > 1:
                        depths[b], widths[b] = chain_la[b], 1
                        la[b] = chain_la[b]
        self._round_depth = depths
        self._round_width = widths
        return la

    def _token_at(self, task: GenerateTask, p: int) -> int:
        """Committed token occupying absolute position `p` (prompt, then
        output history; patch prefixes are unsupported under spec)."""
        if p < task.prompt_len:
            return int(task.prompt[p])
        return int(task.output[p - task.prompt_len])

    def spec_decode(self, stats: EngineStats
                    ) -> List[Tuple[GenerateTask, int]]:
        """One speculative round over every decoding slot: k lockstep
        draft-decode proposal steps, ONE multi-token target verify pass
        (the whole round's target weight traffic), then host-side
        longest-prefix acceptance with rollback — pos rewinds to the
        committed length, blocks allocated solely for rejected tokens are
        freed, and the draft cache rewinds alongside.  Returns the
        committed (task, output index) token events: between 1 and k+1
        per slot, token-identical to `decode()` run step-by-step for
        greedy AND sampled requests (serving/spec.py).

        With `spec.branches > 1` the round goes through the token-tree
        variant instead (_spec_decode_tree); at branches == 1 this path
        runs unchanged."""
        if self.tree_branches > 1:
            return self._spec_decode_tree(stats)
        active = self.decoding_slots()
        if not active:
            return []
        k = self.spec.k
        C = k + 1
        la = self.spec_lookahead()
        pos0 = np.array(self.pos, np.int64)

        # -- propose.  The draft may lag the committed sequence by one
        # position after an all-accept round (the bonus token's
        # predecessor was never fed through it); `known` replays the gap
        # from committed history before the draft feeds its own guesses.
        starts = np.zeros((self.B,), np.int64)
        known: Dict[int, List[int]] = {}
        for b in active:
            ds = self.draft_states[b]
            starts[b] = ds.pos
            known[b] = [self._token_at(self.slots[b], p)
                        for p in range(ds.pos, int(pos0[b]) + 1)]
        n_steps = max(max(len(known[b]) - 1 + int(la[b]) for b in active), 1)
        with trace.span(trace.SPEC_DRAFT, self.tracer, steps=n_steps,
                        slots=len(active)) as sp:
            lane_d = device_lane(self.lane)
            feed = np.zeros((self.B,), np.int32)
            proposals: Dict[int, List[int]] = {b: [] for b in active}
            last_out = np.zeros((self.B,), np.int32)
            for s in range(n_steps):
                for b in active:
                    feed[b] = (known[b][s] if s < len(known[b])
                               else int(last_out[b]))
                out_d, _, self.draft_caches = self.draft_decode_step.fn(
                    self.draft_params, jnp.array(feed),
                    jnp.array(starts + s, jnp.int32), self.draft_caches,
                    lane_d)
                (last_out,), _ = self._fetch(trace.SPEC_DRAFT_FETCH, out_d)
                for b in active:
                    if (s >= len(known[b]) - 1
                            and len(proposals[b]) < int(la[b])):
                        proposals[b].append(int(last_out[b]))
        t_draft = sp.seconds
        stats.spec_draft_time_s += t_draft
        stats.add_draft_time_ms(t_draft * 1e3)

        with trace.span(trace.SPEC_VERIFY, self.tracer) as sp:
            # -- verify: target forwards [pending token, d_1..d_ke] into the
            # slot's paged blocks, returning its own choice at every position
            chunk = np.zeros((self.B, C), np.int32)
            chunk_len = np.zeros((self.B,), np.int32)
            for b in active:
                chunk[b, 0] = self.tokens[b]
                props = proposals[b]
                chunk[b, 1:1 + len(props)] = props
                chunk_len[b] = 1 + len(props)
            t1 = time.perf_counter()
            choices_d, self.caches, _ = self.verify_step.fn(
                self.params, jnp.array(chunk), jnp.array(pos0, jnp.int32),
                jnp.array(chunk_len), self.caches, self._tables(), lane_d)
            (choices,), fetch = self._fetch(trace.SPEC_VERIFY_FETCH, choices_d)
            dt = fetch.t1 - t1
            self.steps_run += 1

            # -- commit + rollback
            fresh: List[Tuple[GenerateTask, int]] = []
            occupied = live_tokens = emitted_total = 0
            round_proposed = round_accepted = 0
            for b in active:
                task = self.slots[b]
                occupied += 1
                ke = len(proposals[b])
                cand = [int(c) for c in choices[b, :ke + 1]]
                j = accept_length(proposals[b], cand)
                stats.spec_proposed_tokens += ke
                stats.spec_accepted_tokens += j
                round_proposed += ke
                round_accepted += j
                # commit c_0..c_j, clamped to step-by-step retirement
                # semantics (max_new / max_seq budget, cut at the first EOS)
                room = min(task.max_new_tokens - len(task.output),
                           self.max_seq - 1 - int(pos0[b]))
                emitted = trim_emitted(cand[:j + 1], room=room,
                                       eos_id=task.eos_id)
                for tok in emitted:
                    task.output.append(tok)
                    fresh.append((task, len(task.output) - 1))
                m = len(emitted)
                emitted_total += m
                pos_new = int(pos0[b]) + m
                self.tokens[b] = emitted[-1]
                self._tok_dev = None    # host token write: chain invalid
                self.pos[b] = pos_new
                task.decode_ms += dt * 1e3
                live_tokens += pos_new
                # rollback: free blocks holding only rejected-token KV (the
                # garbage inside kept blocks sits beyond pos and is masked,
                # then overwritten as decoding advances)
                keep = self.allocator.blocks_for(pos_new)
                if len(self._slot_blocks[b]) > keep:
                    extra = self._slot_blocks[b][keep:]
                    del self._slot_blocks[b][keep:]
                    self.allocator.free(extra)
                    self.block_tables[b, keep:] = -1
                    self._tables_dev = None
                # draft rewind: valid through the last draft-cache position
                # whose written token matches the committed sequence (and
                # never past the committed horizon)
                self.draft_states[b].pos = min(int(starts[b]) + n_steps,
                                               int(pos0[b]) + j + 1, pos_new)
            stats.decode_steps += 1
            stats.spec_rounds += 1
            stats.spec_slot_steps += occupied
            stats.spec_emitted_tokens += emitted_total
            stats.ar_tokens += emitted_total
            stats.ar_time_s += dt
            stats.add_decode_step_ms(dt * 1e3)
            stats.occupied_slot_steps += occupied
            stats.block_slot_steps += self.allocator.num_used
            stats.token_slot_steps += live_tokens
            executed = int(chunk_len.sum())
            stats.verify_positions += executed
            sp.set(slots=occupied,
                   **round_annotation(proposed=round_proposed,
                                      accepted=round_accepted,
                                      emitted=emitted_total))
        return fresh

    def _spec_decode_tree(self, stats: EngineStats
                          ) -> List[Tuple[GenerateTask, int]]:
        """One token-tree speculative round (spec.branches > 1).

        Propose: the same lockstep draft replay loop as the chain round,
        via `draft_topk_step` — each step also returns the row's top-b
        candidates, so while the draft's dense cache advances ONLY along
        its sampled chain, the (b - 1) siblings per depth come free.
        Verify: per-slot caterpillar trees (spec.build_tree) flatten into
        one fixed-width [B, 1 + k*b] chunk; `tree_verify_step` scatters
        node KV at pos0 + node_index, applies rope and the sampler's
        position key at pos0 + depth, masks intra-chunk attention to each
        node's ancestors, and returns the target's own choice after every
        node's root path.  Commit: the deepest root path whose node
        tokens all match their parent's choice (spec.accept_tree_path) is
        accepted — its KV rows are compacted into the slot's canonical
        positions pos0 + d (kv_cache.make_row_copy; rope already matches,
        the move is bytes only) — then the usual trim / rollback / draft
        rewind, the draft rewinding to the accepted path's leading CHAIN
        prefix (siblings never entered its cache).  Lossless: every
        acceptance test is the same (seed, position)-keyed equality the
        chain round uses, so committed outputs stay token-identical to
        plain decode."""
        active = self.decoding_slots()
        if not active:
            return []
        C = 1 + self.spec.k * self.tree_branches
        if self._round_depth is None:
            self.spec_lookahead()
        depth_la, width = self._round_depth, self._round_width
        pos0 = np.array(self.pos, np.int64)

        # -- propose (chain replay identical to spec_decode's loop)
        starts = np.zeros((self.B,), np.int64)
        known: Dict[int, List[int]] = {}
        for b in active:
            ds = self.draft_states[b]
            starts[b] = ds.pos
            known[b] = [self._token_at(self.slots[b], p)
                        for p in range(ds.pos, int(pos0[b]) + 1)]
        n_steps = max(max(len(known[b]) - 1 + int(depth_la[b])
                          for b in active), 1)
        with trace.span(trace.SPEC_DRAFT, self.tracer, steps=n_steps,
                        slots=len(active)) as sp:
            lane_d = device_lane(self.lane)
            feed = np.zeros((self.B,), np.int32)
            levels: Dict[int, List[List[int]]] = {b: [] for b in active}
            last_out = np.zeros((self.B,), np.int32)
            for s in range(n_steps):
                for b in active:
                    feed[b] = (known[b][s] if s < len(known[b])
                               else int(last_out[b]))
                out_d, alts_d, _, self.draft_caches = self.draft_topk_step.fn(
                    self.draft_params, jnp.array(feed),
                    jnp.array(starts + s, jnp.int32), self.draft_caches,
                    lane_d)
                (last_out, alts), _ = self._fetch(trace.SPEC_DRAFT_FETCH,
                                                  out_d, alts_d)
                for b in active:
                    if (s >= len(known[b]) - 1
                            and len(levels[b]) < int(depth_la[b])):
                        # alts[b, 0] == the chain token fed next step, by
                        # sample_topn construction
                        levels[b].append([int(t)
                                          for t in alts[b, :int(width[b])]])
        t_draft = sp.seconds
        stats.spec_draft_time_s += t_draft
        stats.add_draft_time_ms(t_draft * 1e3)

        with trace.span(trace.SPEC_VERIFY, self.tracer) as sp:
            # -- verify: one tree-masked target pass over every slot's tree
            chunk = np.zeros((self.B, C), np.int32)
            chunk_len = np.zeros((self.B,), np.int32)
            depth_op = np.zeros((self.B, C), np.int32)
            anc_op = np.zeros((self.B, C, C), bool)
            trees = {}
            for b in active:
                tree = build_tree(int(self.tokens[b]), levels[b])
                trees[b] = tree
                n = tree.n_nodes
                chunk[b, :n] = tree.tokens
                depth_op[b, :n] = tree.depth
                anc_op[b, :n, :n] = tree.anc
                chunk_len[b] = n
            t1 = time.perf_counter()
            choices_d, self.caches, _ = self.tree_verify_step.fn(
                self.params, jnp.array(chunk), jnp.array(pos0, jnp.int32),
                jnp.array(chunk_len), jnp.array(depth_op),
                jnp.array(anc_op), self.caches, self._tables(), lane_d)
            (choices,), fetch = self._fetch(trace.SPEC_VERIFY_FETCH, choices_d)
            dt = fetch.t1 - t1
            self.steps_run += 1

            # -- commit + compact + rollback
            fresh: List[Tuple[GenerateTask, int]] = []
            occupied = live_tokens = emitted_total = 0
            round_proposed = round_accepted = round_nodes = round_branch = 0
            path_depths: List[int] = []
            bs = self.layout.block_size
            for b in active:
                task = self.slots[b]
                occupied += 1
                tree = trees[b]
                n = tree.n_nodes
                path = accept_tree_path(tree.tokens, tree.parent,
                                        choices[b], n)
                stats.spec_proposed_tokens += n - 1
                stats.spec_accepted_tokens += len(path)
                stats.spec_tree_nodes += n
                stats.add_spec_path_depth(len(path))
                round_proposed += n - 1
                round_accepted += len(path)
                round_nodes += n
                path_depths.append(len(path))
                if any(not tree.chain[i] for i in path):
                    stats.spec_branch_hits += 1
                    round_branch += 1
                full = [0] + path
                cand = [int(choices[b, i]) for i in full]
                room = min(task.max_new_tokens - len(task.output),
                           self.max_seq - 1 - int(pos0[b]))
                emitted = trim_emitted(cand, room=room, eos_id=task.eos_id)
                m = len(emitted)
                # compact: committed position pos0 + d must hold the KV that
                # node full[d] wrote at pos0 + full[d] (already roped at its
                # logical position pos0 + d).  full[] is strictly increasing
                # with full[d] >= d, so ascending-d moves never clobber a
                # pending source.
                blks = self._slot_blocks[b]
                for d in range(1, m):
                    if int(full[d]) == d:
                        continue
                    src_p = int(pos0[b]) + int(full[d])
                    dst_p = int(pos0[b]) + d
                    self.caches = self._row_copy(
                        self.caches, blks[src_p // bs], src_p % bs,
                        blks[dst_p // bs], dst_p % bs)
                for tok in emitted:
                    task.output.append(tok)
                    fresh.append((task, len(task.output) - 1))
                emitted_total += m
                pos_new = int(pos0[b]) + m
                self.tokens[b] = emitted[-1]
                self._tok_dev = None    # host token write: chain invalid
                self.pos[b] = pos_new
                task.decode_ms += dt * 1e3
                live_tokens += pos_new
                # rollback: free blocks holding only rejected-node KV
                keep = self.allocator.blocks_for(pos_new)
                if len(self._slot_blocks[b]) > keep:
                    extra = self._slot_blocks[b][keep:]
                    del self._slot_blocks[b][keep:]
                    self.allocator.free(extra)
                    self.block_tables[b, keep:] = -1
                    self._tables_dev = None
                # draft rewind: the dense draft cache followed the CHAIN, so
                # it stays valid through the accepted path's leading chain
                # prefix only (a sibling acceptance diverges from what the
                # draft fed itself)
                j_chain = 0
                for i in path:
                    if not tree.chain[i]:
                        break
                    j_chain += 1
                self.draft_states[b].pos = min(int(starts[b]) + n_steps,
                                               int(pos0[b]) + j_chain + 1,
                                               pos_new)
            self._round_depth = self._round_width = None
            stats.decode_steps += 1
            stats.spec_rounds += 1
            stats.spec_slot_steps += occupied
            stats.spec_emitted_tokens += emitted_total
            stats.ar_tokens += emitted_total
            stats.ar_time_s += dt
            stats.add_decode_step_ms(dt * 1e3)
            stats.occupied_slot_steps += occupied
            stats.block_slot_steps += self.allocator.num_used
            stats.token_slot_steps += live_tokens
            executed = int(chunk_len.sum())
            stats.verify_positions += executed
            sp.set(slots=occupied,
                   **round_annotation(proposed=round_proposed,
                                      accepted=round_accepted,
                                      emitted=emitted_total,
                                      tree_nodes=round_nodes,
                                      path_depths=path_depths,
                                      branch_hits=round_branch))
        return fresh

    # -- execution: encoder-only NAR -----------------------------------
    def encode(self, group: List[EncodeTask], stats: EngineStats):
        """One pooled full-sequence pass for a same-bucket batch of
        EncodeTasks (and same pooling mode).  Fills task.embedding."""
        assert group and len({t.pooling for t in group}) == 1
        n = len(group)
        lens = [t.prompt_len for t in group]
        bucket = self.encode_bucket_for(max(lens))
        step = self._encode_for(bucket, n, group[0].pooling, stats)
        with trace.span(trace.ENCODE, self.tracer, bucket=bucket, group=n,
                        uids=[t.uid for t in group]) as sp:
            padded = np.zeros((n, bucket), np.int32)
            for j, task in enumerate(group):
                padded[j, :task.prompt_len] = np.asarray(task.prompt,
                                                         np.int32)
            batch = {"tokens": jnp.array(padded)}
            if self.cfg.n_patches:
                batch["patches"] = jnp.zeros(
                    (n, self.cfg.n_patches, self.cfg.d_model), jnp.bfloat16)
            if self.cfg.enc_schedule:
                batch["frames"] = jnp.zeros(
                    (n, self.cfg.enc_seq_padded, self.cfg.d_model),
                    jnp.bfloat16)
            pooled = step.fn(self.params, batch, jnp.array(lens, jnp.int32))
            (pooled_np,), fetch = self._fetch(trace.ENCODE_FETCH, pooled)
        now = fetch.t1
        dt = now - sp.t0
        for j, task in enumerate(group):
            task.bucket = bucket
            task.embedding = pooled_np[j]
            task.encode_ms = dt * 1e3 / n
            task.latency_ms = (now - task._t_submit) * 1e3
            task.done = True
            stats.encode_tokens += task.prompt_len
            stats.padded_encode_tokens += bucket
            stats.add_encode_latency_ms(task.latency_ms)
            stats.bucket_hits[bucket] = stats.bucket_hits.get(bucket, 0) + 1
        stats.encode_time_s += dt
        stats.encode_batches += 1
