"""Session-based continuous-batching inference engine — thin façade wiring
queue -> SchedulerPolicy -> ModelRunner.

The pre-split engine fused admission policy, step execution and cache
bookkeeping into one class; it is now three layers with explicit seams:

  serving/tasks.py      what a client wants: `GenerateTask` (NAR prefill +
                        AR decode, the paper's decoder topology) and
                        `EncodeTask` (one pooled NAR pass, the paper's
                        encoder topology), each with priority/deadline.
  serving/scheduler.py  what runs next: `SchedulerPolicy` (FCFS / priority
                        + aging / chunked prefill) — pure host-side
                        ordering + preemption-victim selection.
  serving/runner.py     how it runs: `ModelRunner` owns the compiled
                        steps, caches, block pool and sampling lanes, and
                        exposes prefill / chunk_step / decode / encode with
                        no policy logic.

Engine mechanics preserved from the pre-split engine (see runner.py for
the paging details): a fixed decode batch of B slots runs lockstep AR
steps; finished rows are immediately replaced by prefilling queued
requests; KV memory is block-paged with recompute preemption when the pool
exhausts; admission is batched per prefill-length bucket; per-request
sampling happens inside the jitted steps; `generate()` streams
`TokenEvent`s and `stats()` returns `EngineStats`.

New in the split:

  scheduler=            any SchedulerPolicy; FCFSPolicy (default) is
                        token-for-token identical to the pre-split engine.
  EncodeTask serving    encoder-only requests batch into pooled
                        full-sequence passes (no slots, no KV) interleaved
                        with generate traffic — mixed workloads share one
                        engine.
  chunked prefill       ChunkedPrefillPolicy(chunk_tokens=N): prompts
                        longer than N prefill in N-token pieces between
                        decode steps, so a long admission never stalls
                        running AR slots for its whole prefill (outputs
                        stay token-identical to FCFS; the decode-stall p95
                        drop is measured by benchmarks/serving_bench.py).

Back-compat: `InferenceEngine(cfg, params, batch_size=..., max_seq=...,
policy=<precision>)`, `submit/generate/run/stats/reset_stats/has_work`,
`Request` (= GenerateTask), `ServingEngine` (= InferenceEngine), and the
paged internals tests/benches touch (`allocator`, `layout`,
`block_tables`, `steps_run`, `bucket_for`) all keep working unmodified.
"""
from __future__ import annotations

import time
from typing import Iterator, List, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.runner import DecodeHandle, ModelRunner
from repro.serving.sampling import validate_sampling
from repro.serving.scheduler import FCFSPolicy, SchedulerPolicy
from repro.serving.spec import SpecConfig
from repro.serving import trace
from repro.serving.stats import EngineStats
from repro.serving.tasks import (EncodeTask, GenerateTask, Rejection,
                                 Request, Task, TokenEvent, validate_task)


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_size: int = 4,
                 max_seq: int = 256, mesh=None, policy=None,
                 min_bucket: int = 8, paged: bool = True,
                 block_size: int = 16, kv_pool_blocks: Optional[int] = None,
                 scheduler: Optional[SchedulerPolicy] = None,
                 encode_batch: Optional[int] = None,
                 fuse_epilogues: bool = True,
                 spec: Optional[SpecConfig] = None, draft_params=None,
                 draft_checkpoint: Optional[str] = None,
                 prefix_cache: bool = False,
                 cache_blocks: Optional[int] = None,
                 weight_dtype: str = "bfloat16",
                 kv_dtype: Optional[str] = None,
                 overlap: bool = False,
                 tracer=None):
        # `policy` is the PRECISION policy (pre-split name, kept for
        # back-compat); the scheduling policy is `scheduler`.  `spec`
        # turns on speculative decoding (serving/spec.py): the runner
        # owns a draft LM (params from `draft_params`, the target itself
        # for draft="self", or a seeded init) and replaces per-token
        # decode steps with propose->verify->commit rounds.
        # `draft_checkpoint` loads the draft params from a
        # checkpoint/checkpointer.py directory instead (shape-checked
        # against the spec's resolved draft config; mutually exclusive
        # with draft_params).
        # `prefix_cache` turns on refcounted KV prefix sharing
        # (serving/prefix_cache.py): retired requests' blocks stay indexed
        # by token content and warm admissions prefill only their uncached
        # suffix; `cache_blocks` caps how many pool blocks the index may
        # hold (None = bounded by pool pressure alone).
        # `weight_dtype="int8"` quantizes the dense GEMM weights per output
        # channel (models/quantize); `kv_dtype="int8"` stores the paged KV
        # pools int8 with per-block-per-head scales.  Both default to
        # lossless bf16.
        # `tracer` (serving/trace.py Tracer, or None) turns on opt-in
        # structured tracing: request lifecycle spans and the stage spans
        # land in its ring buffer, exportable as a Chrome trace.  The
        # stage spans reach a running jax.profiler session either way.
        # Pure observer: tokens are identical under all traffic.
        # `overlap=True` switches to the async overlapped host loop: the
        # engine dispatches a decode step and runs host-side scheduling /
        # admission (and, in steady state, even the NEXT dispatch) before
        # fetching the step's tokens, hiding host work under device time.
        # Token-identical to the synchronous loop for greedy and sampled
        # traffic (tests/test_goodput.py).
        if draft_checkpoint is not None:
            if spec is None:
                raise ValueError(
                    "draft_checkpoint requires a SpecConfig (`spec=`)")
            if draft_params is not None:
                raise ValueError(
                    "pass draft_params OR draft_checkpoint, not both")
            draft_params = self._restore_draft(cfg, params, spec,
                                               draft_checkpoint)
        self.runner = ModelRunner(cfg, params, batch_size=batch_size,
                                  max_seq=max_seq, mesh=mesh, policy=policy,
                                  min_bucket=min_bucket, paged=paged,
                                  block_size=block_size,
                                  kv_pool_blocks=kv_pool_blocks,
                                  fuse_epilogues=fuse_epilogues,
                                  spec=spec, draft_params=draft_params,
                                  prefix_cache=prefix_cache,
                                  cache_blocks=cache_blocks,
                                  weight_dtype=weight_dtype,
                                  kv_dtype=kv_dtype,
                                  tracer=tracer)
        self.tracer = tracer
        self.scheduler = scheduler or FCFSPolicy()
        self.encode_batch = encode_batch or batch_size
        self.queue: List[Task] = []
        self.completed: List[Task] = []
        # requests dropped unserved by the scheduler's shed decision, each
        # carrying a typed `rejection` (they also appear in `completed` so
        # run()/generate() callers see every submitted uid resolve)
        self.shed: List[Task] = []
        self.overlap = overlap
        self._pending: Optional[DecodeHandle] = None
        self._degrade = 0                  # current scheduler degrade level
        self._stats = self._fresh_stats()
        self._prefix_base = self._prefix_snapshot()
        self._t_last_decode: Optional[float] = None
        self._step_n = 0                   # engine.step spans opened

    @staticmethod
    def _restore_draft(cfg: ModelConfig, params, spec: SpecConfig,
                       directory: str):
        """Load draft params from a Checkpointer directory: resolve the
        spec's draft config, eval_shape the init to get the reference
        tree (leaf count / shapes / dtypes checked by restore — a
        mismatched checkpoint fails loudly, not with silent garbage), and
        restore into it.  The draft inherits the target params' dtype,
        matching the in-memory seeded-init convention bit for bit."""
        import functools
        import jax
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.models import lm as lm_mod
        from repro.serving.spec import resolve_draft
        dcfg = resolve_draft(spec, cfg)
        pdtype = jax.tree.leaves(params)[0].dtype
        like = jax.eval_shape(
            functools.partial(lm_mod.init_lm, cfg=dcfg, dtype=pdtype),
            jax.random.key(0))
        return Checkpointer(directory).restore(like)

    # -- delegated runner state (back-compat surface) -------------------
    @property
    def cfg(self):
        return self.runner.cfg

    @property
    def params(self):
        return self.runner.params

    @property
    def B(self) -> int:
        return self.runner.B

    @property
    def max_seq(self) -> int:
        return self.runner.max_seq

    @property
    def paged(self) -> bool:
        return self.runner.paged

    @property
    def layout(self):
        return self.runner.layout

    @property
    def allocator(self):
        return self.runner.allocator

    @property
    def block_tables(self):
        return self.runner.block_tables

    @property
    def prefix_cache(self):
        return self.runner.prefix_cache

    @property
    def slots(self):
        return self.runner.slots

    @property
    def steps_run(self) -> int:
        return self.runner.steps_run

    def bucket_for(self, prompt_len: int) -> int:
        return self.runner.bucket_for(prompt_len)

    def _fresh_stats(self) -> EngineStats:
        st = EngineStats(batch_size=self.runner.B)
        if self.runner.paged:
            st.kv_pool_blocks = self.runner.layout.num_blocks
            st.kv_block_size = self.runner.layout.block_size
        st.weight_dtype = self.runner.weight_dtype
        st.kv_dtype = self.runner.kv_dtype
        st.weight_bytes_per_device = self.runner.weight_bytes_per_device()
        st.kv_pool_bytes = self.runner.kv_pool_bytes()
        return st

    # -- admission -----------------------------------------------------
    def submit(self, task: Task):
        """Queue a GenerateTask (alias: Request) or EncodeTask."""
        # re-validate priority/deadline at submit: construction validated
        # too, but tasks can be mutated or dataclasses.replace'd since
        validate_task(task)
        n = len(task.prompt)
        if isinstance(task, EncodeTask):
            cap = self.runner.max_seq - self.runner._n_prefix
        else:
            cap = self.runner.prompt_cap
        assert 0 < n <= cap, (
            f"prompt length {n} not in [1, {cap}] "
            f"(max_seq={self.runner.max_seq}, "
            f"patch prefix={self.runner._n_prefix})")
        if isinstance(task, GenerateTask):
            assert task.max_new_tokens >= 1, (
                f"max_new_tokens must be >= 1 (the prefill emits the first "
                f"token): {task.max_new_tokens}")
            # submit-time sampling validation: a clear ValueError here
            # instead of a silent clamp (top_k) or misbehavior deep in the
            # jitted step (covers params built around __post_init__ too)
            validate_sampling(task.sampling)
            spec = self.runner.spec
            if (spec is not None and spec.acceptance == "greedy"
                    and not task.sampling.is_greedy):
                raise ValueError(
                    f"request {task.uid} samples (temperature="
                    f"{task.sampling.temperature}) but SpecConfig "
                    f"acceptance='greedy' serves greedy traffic only; "
                    f"use acceptance='lossless' for exact sampled "
                    f"speculation")
        task.prompt_len = n
        task._t_submit = time.perf_counter()
        self.queue.append(task)
        self._stats.requests_submitted += 1
        if self.tracer:
            ann = {"prompt_len": n}
            if getattr(task, "deadline_ms", None) is not None:
                ann["deadline_ms"] = task.deadline_ms
            self.tracer.instant("submit", task._t_submit, tid=task.uid,
                                **ann)

    def _first_admission(self, task: Task):
        # fresh clock, not the step-start timestamp: blocking encode/prefill
        # calls (possibly compiles) may have run earlier in this same step,
        # and they are part of this task's wait
        now = time.perf_counter()
        task.queue_wait_ms = (now - task._t_submit) * 1e3
        self._stats.add_queue_wait_ms(task.queue_wait_ms)
        if self.tracer:
            self.tracer.request_span(
                task.uid, "queue", task._t_submit, now,
                queue_wait_ms=task.queue_wait_ms,
                **self.scheduler.admission_annotation(task, now))

    def _chunk_budget(self) -> Optional[int]:
        """The per-step chunked-prefill token budget at the current
        degrade level (DeadlinePolicy halves it under pressure; chunk
        width only moves prefill FLOPs in time, never changes tokens)."""
        return self.scheduler.effective_chunk_tokens(self._degrade)

    def _chunkable(self, task: GenerateTask) -> bool:
        ct = self._chunk_budget()
        return (ct is not None and self.runner.supports_chunked
                and self.runner.full_len(task) > ct)

    def _note_admitted(self, task: Task):
        """Degrade ladder, per-request half: a generate task admitted
        while the scheduler reports pressure is served without speculation
        (spec_lookahead proposes 0 for it — exact, just no lookahead).
        The flag is sticky: 'admitted under pressure' stays true for the
        request's lifetime.

        Tree runners (spec.branches > 1) have a gentler first rung —
        level 1 only shrinks their trees to single-branch chains
        (step() flips runner._tree_chain_only), so per-request
        speculation-off waits for level 2.  Single-branch runners keep
        degrading at level 1, exactly as before trees existed."""
        thresh = 2 if self.runner.tree_branches > 1 else 1
        if (self._degrade >= thresh and isinstance(task, GenerateTask)
                and self.runner.spec is not None and not task.degraded):
            task.degraded = True
            self._stats.requests_degraded += 1
            if self.tracer:
                self.tracer.instant("degrade", time.perf_counter(),
                                    tid=task.uid, rung=self._degrade)

    def _shed_expired(self):
        """Drop queued requests whose SLO the policy proves unattainable:
        each gets a typed Rejection, done=True, and lands in both `shed`
        and `completed` unserved — capacity goes to requests that can
        still meet their deadline."""
        cands = self.scheduler.shed_candidates(self.queue,
                                               time.perf_counter())
        for task in cands:
            self.queue.remove(task)
            task.rejection = Rejection(
                "slo_unattainable",
                f"deadline_ms={task.deadline_ms:.1f} already exceeded "
                f"after {task.age_s() * 1e3:.1f}ms in queue "
                f"(policy={self.scheduler.name})")
            task.done = True
            self.shed.append(task)
            self.completed.append(task)
            self._stats.record_shed(task)
            if self.tracer:
                self.tracer.request_span(
                    task.uid, "shed", task._t_submit, time.perf_counter(),
                    reason=task.rejection.kind,
                    detail=task.rejection.detail)

    def _next_group(self, order: List[GenerateTask], max_n: int):
        """The next whole-prompt admission group: up to `max_n` tasks
        sharing the policy head's length bucket, each with its pool blocks
        allocated (all-or-nothing per task).  Empty when the head cannot
        get blocks — the caller waits for running requests to free some."""
        runner = self.runner
        head_bucket = runner.bucket_for(runner.full_len(order[0]))
        cands = [t for t in order
                 if runner.bucket_for(runner.full_len(t)) == head_bucket]
        cands = cands[:max_n]
        group = []
        for task in cands:
            blk = runner.alloc_for(task)
            if blk is None:
                break
            group.append((task, blk))
        if not group:
            self._pool_too_small_check(order[0])
        return group

    def _pool_too_small_check(self, head: GenerateTask):
        """Admission got nothing: fatal only when nothing is running (no
        retirement can ever free blocks for the head)."""
        runner = self.runner
        if runner.has_running():
            return
        need = runner.blocks_needed(head)
        raise RuntimeError(
            f"KV pool too small: request {head.uid} needs "
            f"{need} blocks, pool has {runner.allocator.num_blocks} "
            f"({runner.allocator.num_free} free) and no running "
            f"request can be preempted to free more")

    def _gen_queue(self) -> List[GenerateTask]:
        return [t for t in self.queue if isinstance(t, GenerateTask)]

    def _admit(self, fresh: List) -> int:
        """Admit generate tasks into free slots per the scheduling policy:
        cached-prefix hits seat with shared blocks and prefill only their
        suffix; whole-prompt groups prefill immediately; prompts over the
        chunk budget park in their slot and advance chunk-by-chunk."""
        runner = self.runner
        admitted = 0
        while True:
            free = runner.free_slots()
            gen = self._gen_queue()
            if not free or not gen:
                return admitted
            # fresh clock per iteration: earlier groups in this same step
            # ran blocking prefills, which age the remaining queue
            order = self.scheduler.admission_order(gen,
                                                   time.perf_counter())
            if runner.prefix_cache is not None:
                order = self.scheduler.cached_order(
                    order, runner.cached_tokens_for)
            head = order[0]
            if runner.prefix_cache is not None:
                res = runner.admit_cached(head, free[0])
                if res is False:
                    if runner.has_running():
                        return admitted    # retirement will free blocks
                    # nothing running and the warm layout still does not
                    # fit (e.g. the COW duplicate when the pool exactly
                    # matches the request): flush the cache and admit cold
                    runner.prefix_cache.clear()
                    res = None
                if res:
                    self.queue.remove(head)
                    if not head.output:
                        self._first_admission(head)
                    self._note_admitted(head)
                    ct = self._chunk_budget()
                    suffix = runner.full_len(head) - head.prefilled
                    if ct is not None and suffix > ct:
                        # over the chunk budget: stays parked, the budget
                        # loop in step() advances it
                        admitted += 1
                        continue
                    # run the whole suffix now (one bucketed chunk pass)
                    width = runner.bucket_for(suffix)
                    while runner.prefilling[free[0]]:
                        ev = runner.chunk_step(head, width, self._stats)
                        if ev is not None:
                            fresh.append(ev)
                    admitted += 1
                    continue
            if self._chunkable(head):
                blk = runner.alloc_for(head)
                if blk is None:
                    self._pool_too_small_check(head)
                    return admitted
                self.queue.remove(head)
                if not head.output:
                    self._first_admission(head)
                self._note_admitted(head)
                runner.begin_chunked(head, blk, free[0])
                admitted += 1
                continue
            group = self._next_group(order, len(free))
            if not group:
                return admitted
            for task, _ in group:
                self.queue.remove(task)
                if not task.output:
                    self._first_admission(task)
                self._note_admitted(task)
            fresh.extend(runner.prefill(group, free, self._stats))
            admitted += len(group)

    def _run_encode(self) -> int:
        """Run ONE same-bucket encode batch (policy-ordered) — encode uses
        no slots or cache, so it interleaves with generate admission; one
        batch per engine step keeps long encode backlogs from starving
        decode."""
        enc = [t for t in self.queue if isinstance(t, EncodeTask)]
        if not enc:
            return 0
        runner = self.runner
        order = self.scheduler.admission_order(enc, time.perf_counter())
        head = order[0]
        bucket = runner.encode_bucket_for(head.prompt_len)
        group = [t for t in order
                 if runner.encode_bucket_for(t.prompt_len) == bucket
                 and t.pooling == head.pooling][:self.encode_batch]
        for task in group:
            self.queue.remove(task)
            self._first_admission(task)
        runner.encode(group, self._stats)
        now = time.perf_counter()
        for task in group:
            self._stats.record_slo(task)
            self.completed.append(task)
            self._stats.requests_completed += 1
            if self.tracer:
                self.tracer.request_span(
                    task.uid, "request", task._t_submit, now,
                    latency_ms=task.latency_ms,
                    prompt_len=task.prompt_len, encode=True)
        return len(group)

    # -- retirement ------------------------------------------------------
    def _retire(self, skip=()):
        """Release finished decode slots.  `skip` holds slot indices with
        an UNCOMMITTED in-flight decode step (overlapped loop): their
        output/pos lag by one token, so the finished-check would both
        misjudge and drop the flying token — they retire after commit."""
        with trace.span(trace.RETIRE, self.tracer):
            runner = self.runner
            pos = np.asarray(runner.pos)
            for b, task in enumerate(runner.slots):
                if task is None or runner.prefilling[b] or b in skip:
                    continue
                tok = task.output[-1]
                if (len(task.output) >= task.max_new_tokens
                        or (task.eos_id is not None and tok == task.eos_id)
                        or int(pos[b]) >= self.runner.max_seq - 1):
                    task.done = True
                    now = time.perf_counter()
                    task.latency_ms = (now - task._t_submit) * 1e3
                    n = len(task.output)
                    task.tpot_ms = ((task.latency_ms - task.ttft_ms)
                                    / (n - 1) if n > 1 else 0.0)
                    if n > 1:
                        self._stats.add_tpot_ms(task.tpot_ms)
                    self._stats.record_slo(task)
                    self.completed.append(task)
                    self._stats.requests_completed += 1
                    if self.tracer:
                        self.tracer.request_span(
                            task.uid, "request", task._t_submit, now,
                            ttft_ms=task.ttft_ms, tpot_ms=task.tpot_ms,
                            latency_ms=task.latency_ms, tokens=n,
                            prompt_len=task.prompt_len,
                            degraded=task.degraded,
                            cached_prefix=task.cached_prefix)
                    runner.release_slot(b)

    # -- engine loop ------------------------------------------------------
    def step(self) -> List[TokenEvent]:
        """One engine iteration: encode batch -> shed -> admit -> chunk
        advance -> AR step -> retire.  Returns the TokenEvents produced
        (prefill first-tokens + decoded tokens), with `is_last` resolved
        against retirement.  With overlap=True the AR step's token fetch
        is deferred into the NEXT iteration so host scheduling work runs
        while the device computes (token-identical either way).

        Feeds `engine_steps` and `host_s`: the step's seconds less those
        the runner spent blocked in token fetches."""
        fetch0 = self.runner.fetch_s
        with trace.span(trace.ENGINE_STEP, self.tracer, n=self._step_n,
                        queued=len(self.queue),
                        running=sum(s is not None
                                    for s in self.runner.slots)) as sp:
            events = self._step_inner()
        self._step_n += 1
        self._stats.engine_steps += 1
        self._stats.host_s += sp.seconds - (self.runner.fetch_s - fetch0)
        return events

    def _step_inner(self) -> List[TokenEvent]:
        self._shed_expired()
        self._degrade = self.scheduler.degrade_level(
            len(self._gen_queue()), self.runner.B)
        if self.runner.spec is not None and self.runner.tree_branches > 1:
            # degrade ladder, rung 1 (lossless): backlogged tree rounds
            # shrink to single-branch chains for as long as the pressure
            # lasts — a round-scoped flag, not sticky like `degraded`
            self.runner._tree_chain_only = self._degrade >= 1
        if self.overlap:
            return self._step_overlapped()
        return self._step_sync()

    def _advance_chunks(self, fresh: List):
        """Chunked-prefill advancement under a per-STEP token budget: the
        point of chunking is bounding the prefill work between two decode
        steps, so the budget is shared across prefilling slots (oldest
        admitted first), not per-slot — several long admissions in flight
        still cost at most ~chunk_tokens before the next AR step."""
        runner = self.runner
        ct = self._chunk_budget()
        budget = ct or 0
        for task in sorted((runner.slots[b] for b in range(runner.B)
                            if runner.slots[b] is not None
                            and runner.prefilling[b]),
                           key=lambda t: t._seq):
            if budget <= 0:
                break
            ev = runner.chunk_step(task, ct, self._stats)
            # every call costs one full compiled chunk_tokens-wide pass
            # (short final chunks are padded), so the budget is spent per
            # CALL, not per true token — with budget == chunk_tokens that
            # is exactly one chunk pass between AR steps
            budget -= ct
            if ev is not None:
                fresh.append(ev)

    def _token_events(self, fresh: List) -> List[TokenEvent]:
        return [TokenEvent(task.uid, task.output[i],
                           task.done and i == len(task.output) - 1)
                for task, i in fresh]

    def _admit_until_settled(self, fresh: List):
        """Admit/retire until slots are full or the queue drains: a request
        finished by its prefill token alone (max_new_tokens=1, prompt-eos,
        pos cap) frees its slot (and blocks) for another admission before
        the AR step.  A free slot the pool cannot serve yet is not
        progress — stop and let the decode/retire cycle free blocks."""
        runner = self.runner
        with trace.span(trace.ADMIT, self.tracer) as sp:
            total = 0
            while True:
                n_done = len(self.completed)
                admitted = self._admit(fresh)
                total += admitted
                self._retire()
                if not self._gen_queue() or not runner.free_slots():
                    break
                if not admitted and len(self.completed) == n_done:
                    break
            sp.set(admitted=total)

    def _step_sync(self) -> List[TokenEvent]:
        runner = self.runner
        fresh: List = []                  # (task, output index) pairs
        self._run_encode()
        self._admit_until_settled(fresh)
        self._advance_chunks(fresh)
        # retire before decode: the final chunk's token may end its request
        self._retire()
        if runner.decoding_slots():
            victim = lambda running: self.scheduler.select_victim(
                running, time.perf_counter())
            # speculation needs the verify chunk's blocks up front: the
            # lookahead extends the per-slot need to pos + k_eff
            la = runner.spec_lookahead() if runner.spec else None
            # each eviction goes to the queue head (most recently evicted
            # first), matching the pre-split engine's re-queue order
            with trace.span(trace.BLOCKS, self.tracer):
                for task in runner.ensure_decode_blocks(victim, self._stats,
                                                        lookahead=la):
                    self.queue.insert(0, task)
            if runner.decoding_slots():
                t0 = time.perf_counter()
                if self._t_last_decode is not None:
                    # time decode slots sat idle since the last AR step —
                    # admission prefill work between decode steps shows up
                    # here (chunked prefill exists to bound it)
                    self._stats.add_decode_stall_ms(
                        (t0 - self._t_last_decode) * 1e3)
                fresh.extend(runner.spec_decode(self._stats) if runner.spec
                             else runner.decode(self._stats))
                self._t_last_decode = time.perf_counter()
                self._retire()
        if not runner.decoding_slots():
            self._t_last_decode = None    # idle gaps are not decode stalls
        return self._token_events(fresh)

    # -- async overlapped loop (overlap=True) ----------------------------
    def _pending_slots(self):
        return (frozenset(b for b, _ in self._pending.decoding)
                if self._pending is not None else frozenset())

    def _commit_pending(self, fresh: List):
        """Fetch the in-flight step's tokens.  Everything the engine did
        since its dispatch — encode, admission, chunk advancement, even the
        next dispatch — ran while the device computed it; that hidden host
        wall is the overlap win (`host_overlap_ratio`)."""
        handle = self._pending
        self._pending = None
        self._stats.overlap_host_s += max(
            0.0, time.perf_counter() - handle.t0)
        self._stats.overlapped_steps += 1
        fresh.extend(self.runner.decode_commit(handle, self._stats))
        self._t_last_decode = time.perf_counter()

    def _fast_dispatch_ok(self) -> bool:
        """True when the NEXT decode step may be dispatched BEFORE the
        pending step's tokens are fetched — the double-buffered steady
        state.  Requires proving, from host mirrors alone, that the
        pending commit cannot change any scheduling state: no retirement
        is possible (no eos watch, output budget and sequence horizon not
        at their last token) and every decoding slot already owns its next
        write block exclusively (no allocation, no preemption, no COW).
        Sampled traffic needs no special-casing: lanes sample inside the
        step keyed by (seed, position), independent of neighbors."""
        runner = self.runner
        if (runner.spec is not None          # acceptance is data-dependent
                or runner._tok_dev is None   # host token write intervened
                or self.queue                # admission may reseat slots
                or any(runner.prefilling)):  # chunk landing writes tokens
            return False
        pend = self._pending.decoding
        if not pend:
            return False
        for b, task in pend:
            if task.eos_id is not None:
                return False     # the flying token may be EOS
            if len(task.output) + 1 >= task.max_new_tokens:
                return False     # commit reaches the output budget
            if int(runner.pos[b]) >= runner.max_seq - 1:
                return False     # commit reaches the sequence horizon
            if not runner.next_token_block_ready(b):
                return False     # needs allocator/COW work first
        return True

    def _step_overlapped(self) -> List[TokenEvent]:
        runner = self.runner
        fresh: List = []
        if self._pending is not None and self._fast_dispatch_ok():
            # steady-state fast path: dispatch step N+1 chained on step
            # N's device-side token future, THEN fetch N's tokens — the
            # device never waits for the host round-trip.  No retirement
            # or admission is possible by _fast_dispatch_ok construction.
            nxt = runner.decode_dispatch()
            self._commit_pending(fresh)
            self._pending = nxt
            return self._token_events(fresh)
        # regular path: run every piece of host + non-decode device work
        # that cannot disturb the in-flight step BEFORE fetching its
        # tokens (encode batches, admission prefills and chunk advancement
        # chain device-side behind it; pending slots are skipped by
        # retirement until the commit lands their token)
        self._run_encode()
        pend = self._pending_slots()
        with trace.span(trace.ADMIT, self.tracer) as sp:
            sp.set(admitted=self._admit(fresh))
            self._retire(skip=pend)
        self._advance_chunks(fresh)
        if self._pending is not None:
            self._commit_pending(fresh)
        self._retire()
        # settle: slots freed by retirement admit more work this step,
        # exactly like the synchronous loop's admit/retire cycle
        self._admit_until_settled(fresh)
        if runner.decoding_slots():
            victim = lambda running: self.scheduler.select_victim(
                running, time.perf_counter())
            la = runner.spec_lookahead() if runner.spec else None
            with trace.span(trace.BLOCKS, self.tracer):
                for task in runner.ensure_decode_blocks(victim, self._stats,
                                                        lookahead=la):
                    self.queue.insert(0, task)
            if runner.decoding_slots():
                t0 = time.perf_counter()
                if self._t_last_decode is not None:
                    self._stats.add_decode_stall_ms(
                        (t0 - self._t_last_decode) * 1e3)
                if runner.spec:
                    # speculation never pipelines: the round's commit /
                    # rollback depends on how many proposals verify
                    fresh.extend(runner.spec_decode(self._stats))
                    self._t_last_decode = time.perf_counter()
                    self._retire()
                else:
                    self._pending = runner.decode_dispatch()
        if not runner.decoding_slots() and self._pending is None:
            self._t_last_decode = None
        return self._token_events(fresh)

    def has_work(self) -> bool:
        return (bool(self.queue) or self.runner.has_running()
                or self._pending is not None)

    def generate(self, max_steps: int = 10_000) -> Iterator[TokenEvent]:
        """Streaming interface: run engine steps until queue + slots drain,
        yielding each token the moment its step completes."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            yield from self.step()

    def run(self, max_steps: int = 10_000) -> List[Task]:
        """Batch interface: drain `generate()`; returns the tasks that
        completed during THIS call (`self.completed` keeps the full session
        history).  EncodeTasks carry their result in `.embedding`."""
        start = len(self.completed)
        for _ in self.generate(max_steps):
            pass
        return self.completed[start:]

    # -- telemetry --------------------------------------------------------
    def _prefix_snapshot(self):
        """Prefix-cache counters are cumulative on the cache/runner; the
        engine diffs them against the last reset so `stats()` windows
        compose like every other counter."""
        pc = self.runner.prefix_cache
        if pc is None:
            return None
        return (pc.lookups, pc.hits, pc.hit_tokens, pc.evicted_blocks,
                self.runner.cow_copies)

    def stats(self) -> EngineStats:
        """Live serving telemetry (accumulated since construction or the
        last `reset_stats()`)."""
        if self.runner.paged:
            # the allocator tracks the true high-water mark on every alloc,
            # including admissions that never reach a decode step
            self._stats.peak_blocks_used = self.runner.allocator.peak_used
        pc = self.runner.prefix_cache
        if pc is not None:
            base = self._prefix_base
            self._stats.prefix_lookups = pc.lookups - base[0]
            self._stats.prefix_hits = pc.hits - base[1]
            self._stats.cached_prefix_tokens = pc.hit_tokens - base[2]
            self._stats.evicted_blocks = pc.evicted_blocks - base[3]
            self._stats.cow_copies = self.runner.cow_copies - base[4]
            self._stats.cached_blocks = pc.cached_blocks
        return self._stats

    def reset_stats(self):
        """Drop accumulated telemetry, keeping compiled steps AND the
        prefix-cache contents (benchmarks: warm buckets + cache up, reset,
        then measure)."""
        if self.runner.paged:
            self.runner.allocator.peak_used = self.runner.allocator.num_used
        self._stats = self._fresh_stats()
        self._prefix_base = self._prefix_snapshot()
        # a stall sample must never span a reset (warm-up-then-measure)
        self._t_last_decode = None


# The original fixed-prompt-length engine grew into the session API above;
# the scheduler/runner split kept the façade.  The old names stay
# importable: `Request` is GenerateTask, `ServingEngine` is this class.
ServingEngine = InferenceEngine

__all__ = ["InferenceEngine", "ServingEngine", "Request", "GenerateTask",
           "EncodeTask", "TokenEvent"]
