"""Serving benchmark: mixed-length request traces through InferenceEngine.

    PYTHONPATH=src python benchmarks/serving_bench.py [--arch phi4-mini-3.8b]

Unlike the dry-run roofline benchmarks (benchmarks/run.py), this measures
the *engine* end to end on this host: wall-clock NAR prompt-encoding tok/s,
AR decode tok/s, TTFT and decode-step p50/p95, and the paged-KV pool
telemetry (peak utilization, blocks-per-token, preemptions) over a
deterministic trace mixing prompt lengths, greedy and sampled requests.  A
warmup pass compiles every (length bucket, group size) first
(`engine.reset_stats()` then separates compile time from the measured run),
so the JSON tracks steady-state serving performance across PRs:
artifacts/bench/BENCH_serving.json.

Three scheduler/runner-split scenarios ride along in `record["scenarios"]`:

  mixed            encode + generate traffic through one engine — the
                   per-task-class throughput split (paper's encoder and
                   decoder topologies sharing the serving stack)
  chunked_prefill  a long prompt admitted while short requests decode,
                   FCFS vs ChunkedPrefillPolicy: decode-stall p95 (the gap
                   running AR slots sit idle behind the admission) must be
                   strictly lower chunked
  spec_decode      the same trace with speculative decoding on (self-draft
                   by default — greedy acceptance is then exact, isolating
                   the amortization win from draft quality): the target's
                   AR throughput (tokens committed per second of target
                   decode time) must be >= the non-spec baseline with a
                   positive acceptance rate, or the bench exits nonzero
                   (the CI gate for the subsystem)
  tree_spec        a k x branch-count sweep of token-tree speculation on a
                   rejection-heavy draft (the auto draft's params blended
                   toward a decorrelated init by --tree-alpha, so chain
                   acceptance sits mid-range instead of the self-draft
                   100%): every cell must stay token-identical to
                   non-speculative decode, and at equal k the widest tree
                   must STRICTLY raise accepted tokens per target step
                   over single-branch — with at least one sibling-branch
                   acceptance — or the bench exits nonzero (the CI gate
                   for the token-tree subsystem)
  shared_prefix    N requests sharing a long system prompt, prefix cache
                   off (cold) vs on (warm, measured after a populating
                   pass): warm must prefill strictly fewer prompt tokens
                   AND land a strictly lower TTFT p95 than cold, with
                   token-identical outputs, or the bench exits nonzero
                   (the CI gate for the prefix-cache subsystem)
  goodput          the SAME over-capacity open-loop Poisson trace
                   (serving/loadgen.py) through FCFS vs DeadlinePolicy,
                   both on the overlapped host loop, scored in goodput —
                   requests/sec meeting their TTFT SLO.  Capacity and the
                   TTFT budget are calibrated on this host first; the
                   deadline policy must strictly beat FCFS goodput at the
                   calibrated over-capacity rate, or the bench exits
                   nonzero (the CI gate for the goodput subsystem).  A
                   third, TRACED deadline run then re-plays the same load
                   with serving/trace.py attached: it must emit a
                   schema-clean non-empty Chrome trace (--trace-out, the
                   CI artifact) and land within 5% of the untraced
                   goodput, or the bench exits nonzero (the overhead gate
                   for the observability subsystem).  Pass --trace-dir to
                   also capture per-scenario traces.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving import (ArrivalSpec, ChunkedPrefillPolicy, DeadlinePolicy,
                           EncodeTask, FCFSPolicy, InferenceEngine, LoadSpec,
                           PromptSpec, Request, SamplingParams, SLOSpec,
                           SpecConfig, Tracer, make_policy, make_trace,
                           percentiles, replay, spec_support_reason,
                           validate_chrome_trace)

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")


def build_trace(cfg, *, requests: int, min_len: int, max_len: int,
                max_new: int, seed: int) -> list:
    """Deterministic mixed trace: lengths uniform in [min_len, max_len],
    odd uids sampled (temperature/top-k), even uids greedy."""
    rng = np.random.default_rng(seed)
    out = []
    for uid in range(requests):
        n = int(rng.integers(min_len, max_len + 1))
        out.append(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
            max_new_tokens=max_new,
            sampling=SamplingParams(temperature=0.8, top_k=40, seed=uid)
            if uid % 2 else SamplingParams()))
    return out


def _mk_engine(cfg, params, args, scheduler=None, tracer=None):
    return InferenceEngine(cfg, params, batch_size=args.batch,
                           max_seq=args.max_seq,
                           block_size=args.block_size,
                           kv_pool_blocks=args.kv_pool_blocks or None,
                           scheduler=scheduler,
                           weight_dtype=args.weight_dtype,
                           kv_dtype=args.kv_dtype,
                           tracer=tracer)


def _scenario_tracer(args):
    """Per-scenario Tracer when --trace-dir is set, else None (the no-op
    fast path — scenario engines then carry zero tracing branches)."""
    return Tracer(capacity=args.trace_buffer) if args.trace_dir else None


def _write_trace(tracer, args, name: str) -> None:
    if tracer is None:
        return
    os.makedirs(args.trace_dir, exist_ok=True)
    path = os.path.join(args.trace_dir, f"TRACE_{name}.json")
    n = tracer.write(path)
    print(f"  trace[{name}]: {n} events -> {path}")


def mixed_workload(cfg, params, args, tracer=None) -> dict:
    """Encode + generate through one engine: half the trace becomes
    EncodeTasks.  Reports the per-task-class split."""
    def submit_all(engine):
        rng = np.random.default_rng(args.seed + 1)
        for uid in range(args.requests):
            n = int(rng.integers(args.min_prompt_len,
                                 args.max_prompt_len + 1))
            prompt = rng.integers(0, cfg.vocab, n, dtype=np.int32)
            if uid % 2:
                engine.submit(EncodeTask(uid=uid, prompt=prompt))
            else:
                engine.submit(Request(uid=uid, prompt=prompt,
                                      max_new_tokens=args.max_new))

    engine = _mk_engine(cfg, params, args, tracer=tracer)
    submit_all(engine)                            # warmup: compile buckets
    engine.run()
    engine.reset_stats()
    if tracer:
        tracer.clear()
    t0 = time.perf_counter()
    submit_all(engine)
    done = engine.run()
    wall = time.perf_counter() - t0
    st = engine.stats()
    return {
        "requests": len(done),
        "wall_s": wall,
        "encode_completed": st.encode_completed,
        "encode_tok_s": st.encode_tok_s,
        "encode_latency_p95_ms": st.encode_latency_p95_ms,
        "nar_tok_s": st.nar_tok_s,
        "ar_tok_s": st.ar_tok_s,
        "queue_wait_p50_ms": st.queue_wait_p50_ms,
        "queue_wait_p95_ms": st.queue_wait_p95_ms,
    }


def long_admission(cfg, params, args, scheduler, tracer=None) -> dict:
    """Long prompts arrive one at a time while a long-running request
    decodes: each admission's prefill work lands between that request's AR
    steps, and decode-stall p95 captures how long it sat idle behind it
    (whole-prompt prefill = one long stall per admission; chunked = many
    bounded ones).

    The scenario pins its own geometry rather than inheriting --batch /
    --max-seq, because the comparison is only meaningful in the regime
    chunked prefill exists for:

      batch_size=2     one stall victim + one admission slot, so each long
                       prefills ALONE (with more free slots, same-bucket
                       longs admit as one amortized group prefill and the
                       whole-prompt stall shrinks below the per-call
                       overhead chunking pays)
      max_seq>=256     a whole-prompt prefill call must cost well above
                       one chunk call; on this host a warm prefill is
                       ~2.5ms + ~0.03ms/token vs ~2.5ms per chunk call, so
                       the long prompt needs hundreds of tokens for the
                       stall gap to clear the dispatch-overhead noise
                       floor"""
    seq = max(args.max_seq, 256)
    long_len = (3 * seq) // 4
    long_len = min(long_len, seq - 2 * args.max_new - 2)

    n_long = 4
    n_slots = 2

    def run_once(engine):
        rng = np.random.default_rng(args.seed + 2)
        # slot 0: decodes for the whole scenario (the stall victim);
        # slot 1: max_new=1, freeing right after prefill so the long
        # prompts admit (serially) while slot 0 still decodes
        for uid in range(n_slots):
            n = int(rng.integers(args.min_prompt_len,
                                 args.max_prompt_len + 1))
            engine.submit(Request(
                uid=uid, prompt=rng.integers(0, cfg.vocab, n,
                                             dtype=np.int32),
                max_new_tokens=4 * args.max_new if uid == 0 else 1))
        steps = 0
        while engine.has_work():
            engine.step()
            steps += 1
            if steps == 2:
                # a stream of long admissions: each lands between slot 0's
                # AR steps (several stalls, so the p95 sees them; a single
                # admission would hide in the tail)
                for j in range(n_long):
                    engine.submit(Request(
                        uid=990 + j,
                        prompt=rng.integers(0, cfg.vocab, long_len,
                                            dtype=np.int32),
                        max_new_tokens=2))

    engine = InferenceEngine(cfg, params, batch_size=n_slots, max_seq=seq,
                             block_size=args.block_size,
                             scheduler=scheduler, tracer=tracer)
    run_once(engine)                              # warmup: compile
    engine.reset_stats()
    if tracer:
        tracer.clear()
    run_once(engine)
    st = engine.stats()
    return {
        "long_prompt_len": long_len,
        "decode_stall_p50_ms": st.decode_stall_p50_ms,
        "decode_stall_p95_ms": st.decode_stall_p95_ms,
        "prefill_chunks": st.prefill_chunks,
        "ttft_p95_ms": st.ttft_p95_ms,
        "ar_tok_s": st.ar_tok_s,
    }


def spec_workload(cfg, params, args, baseline_ar_tok_s: float,
                  tracer=None) -> dict:
    """The base trace with speculative decoding on.  AR tok/s here is
    tokens committed per second of TARGET decode (verify) time — the
    quantity speculation amortizes the per-step weight read over; the
    draft's overhead is reported separately (draft_time_ms percentiles,
    spec_draft_time_s), mirroring EngineStats' split."""
    reason = spec_support_reason(cfg)
    if reason is not None:
        return {"supported": False, "reason": reason}
    spec = SpecConfig(draft=args.spec_draft, k=args.spec_k)
    engine = InferenceEngine(cfg, params, batch_size=args.batch,
                             max_seq=args.max_seq,
                             block_size=args.block_size,
                             kv_pool_blocks=args.kv_pool_blocks or None,
                             spec=spec, tracer=tracer)
    trace_kw = dict(requests=args.requests, min_len=args.min_prompt_len,
                    max_len=args.max_prompt_len, max_new=args.max_new)
    for req in build_trace(cfg, seed=args.seed, **trace_kw):
        engine.submit(req)                        # warmup: compile
    engine.run()
    engine.reset_stats()
    if tracer:
        tracer.clear()
    t0 = time.perf_counter()
    for req in build_trace(cfg, seed=args.seed, **trace_kw):
        engine.submit(req)
    done = engine.run()
    wall = time.perf_counter() - t0
    st = engine.stats()
    # ar_tok_s (the gated metric) counts target decode time only — the
    # weight-read amortization speculation exists for; ar_tok_s_incl_draft
    # folds the propose phase back in so a draft whose overhead eats the
    # win is visible in the artifact even though the gate tracks the
    # target-step number
    decode_incl = st.ar_time_s + st.spec_draft_time_s
    return {
        "supported": True,
        "draft": engine.runner.draft_cfg.name,
        "k": args.spec_k,
        "requests": len(done),
        "wall_s": wall,
        "ar_tok_s": st.ar_tok_s,
        "ar_tok_s_incl_draft": (st.ar_tokens / decode_incl
                                if decode_incl else 0.0),
        "baseline_ar_tok_s": baseline_ar_tok_s,
        "ar_speedup": (st.ar_tok_s / baseline_ar_tok_s
                       if baseline_ar_tok_s else 0.0),
        "spec_rounds": st.spec_rounds,
        "spec_acceptance_rate": st.spec_acceptance_rate,
        "spec_tokens_per_step": st.spec_tokens_per_step,
        "spec_draft_time_s": st.spec_draft_time_s,
        "draft_time_ms_p50": st.draft_time_ms_p50,
        "draft_time_ms_p95": st.draft_time_ms_p95,
    }


def _rejection_heavy_draft(cfg, args, alpha: float):
    """A draft with tunable MID-RANGE acceptance, no training required.

    Seeded init gives bimodal drafts: the target's seed reproduces its
    own embedding / unembedding / leading layers (a truncated-target
    draft — near-100% acceptance on reduced configs, where the 2-layer
    "auto" draft IS the reduced target), while any other seed is fully
    decorrelated (~0%, and its top-k sets carry no signal, so trees
    can't show their win either).  Interpolating the two parameter trees
    by `alpha` yields a draft whose distribution is a noisy copy of the
    target's — top-1 is wrong often enough to reject, but the top-b set
    still contains the target's choice — exactly the regime real
    trained drafts occupy and the one the k x branches sweep gates on."""
    from repro.configs import make_draft
    dcfg = make_draft(cfg)
    p0 = lm.init_lm(jax.random.key(args.seed), dcfg, jnp.float32)
    p1 = lm.init_lm(jax.random.key(args.seed + 1234), dcfg, jnp.float32)
    mixed = jax.tree.map(lambda a, b: (1 - alpha) * a + alpha * b, p0, p1)
    return dcfg, jax.tree.map(lambda x: x.astype(jnp.bfloat16), mixed)


def tree_spec_workload(cfg, params, args) -> dict:
    """k x branch-count acceptance sweep on a rejection-heavy draft.

    Every cell runs the base trace through a spec engine sharing ONE
    interpolated draft (alpha = --tree-alpha) and records acceptance
    telemetry plus token identity against the non-speculative outputs.
    The gate (check_tree_spec): all cells token-identical, and at every
    k the widest tree strictly raises accepted tokens per slot-round
    over the single-branch chain — the claim the tentpole makes, on a
    draft that actually rejects (the self-draft smoke can't distinguish
    tree from chain: at 100% acceptance the chain already saturates)."""
    reason = spec_support_reason(cfg)
    if reason is not None:
        return {"supported": False, "reason": reason}
    dcfg, draft_params = _rejection_heavy_draft(cfg, args, args.tree_alpha)
    trace_kw = dict(requests=args.requests, min_len=args.min_prompt_len,
                    max_len=args.max_prompt_len, max_new=args.max_new)
    # the identity reference runs on the same default-precision engine
    # the sweep cells use (the base trace engine may be int8)
    ref = InferenceEngine(cfg, params, batch_size=args.batch,
                          max_seq=args.max_seq, block_size=args.block_size,
                          kv_pool_blocks=args.kv_pool_blocks or None)
    for req in build_trace(cfg, seed=args.seed, **trace_kw):
        ref.submit(req)
    baseline_outputs = {t.uid: list(t.output) for t in ref.run()}
    ks = sorted({2, args.spec_k})
    bs = sorted({1, 2, max(2, args.tree_branches)})
    cells = []
    for k in ks:
        for b in bs:
            spec = SpecConfig(draft="auto", k=k, branches=b)
            engine = InferenceEngine(cfg, params, batch_size=args.batch,
                                     max_seq=args.max_seq,
                                     block_size=args.block_size,
                                     kv_pool_blocks=args.kv_pool_blocks
                                     or None,
                                     spec=spec, draft_params=draft_params)
            for req in build_trace(cfg, seed=args.seed, **trace_kw):
                engine.submit(req)
            done = engine.run()
            st = engine.stats()
            outputs = {t.uid: list(t.output) for t in done}
            cells.append({
                "k": k,
                "branches": b,
                "tokens_match": outputs == baseline_outputs,
                "spec_acceptance_rate": st.spec_acceptance_rate,
                "accepted_per_round": (st.spec_accepted_tokens
                                       / st.spec_slot_steps
                                       if st.spec_slot_steps else 0.0),
                "spec_tokens_per_step": st.spec_tokens_per_step,
                "spec_tree_nodes": st.spec_tree_nodes,
                "spec_branch_hits": st.spec_branch_hits,
                "spec_branch_utilization": st.spec_branch_utilization,
                "spec_path_depth_p50": st.spec_path_depth_p50,
                "spec_path_depth_p95": st.spec_path_depth_p95,
            })
    return {"supported": True, "draft": dcfg.name,
            "alpha": args.tree_alpha, "ks": ks, "branch_counts": bs,
            "cells": cells}


def check_tree_spec(rec: dict) -> list:
    """The token-tree acceptance gate: losslessness at every cell, and
    at equal k the widest tree must STRICTLY out-accept the chain."""
    if not rec.get("supported"):
        return []
    problems = []
    by = {(c["k"], c["branches"]): c for c in rec["cells"]}
    for c in rec["cells"]:
        if not c["tokens_match"]:
            problems.append(
                f"k={c['k']} b={c['branches']}: committed outputs diverged "
                f"from non-speculative decode — tree verify is not lossless")
    b_max = max(rec["branch_counts"])
    for k in rec["ks"]:
        chain, tree = by[(k, 1)], by[(k, b_max)]
        if not tree["accepted_per_round"] > chain["accepted_per_round"]:
            problems.append(
                f"k={k}: tree (b={b_max}) accepted/round "
                f"{tree['accepted_per_round']:.3f} does not strictly beat "
                f"single-branch {chain['accepted_per_round']:.3f} on the "
                f"rejection-heavy draft (alpha={rec['alpha']})")
        if tree["spec_branch_hits"] <= 0:
            problems.append(
                f"k={k}: the b={b_max} tree never accepted through a "
                f"sibling branch — the tree is decorative at this alpha")
    return problems


def shared_prefix_workload(cfg, params, args, tracer=None) -> dict:
    """N requests share a long system prompt (each with a short unique
    tail): prefix cache off (cold) vs on (warm).  The warm engine runs two
    populating passes first — pass 1 fills the radix index (and picks up
    in-batch sharing), pass 2 hits it end to end so every warm suffix
    bucket is compiled — then `reset_stats()` and a measured third pass,
    mirroring the cold engine's warmup/measure split.  Request uids differ
    across passes but sampling seeds are keyed by trace position, so the
    measured passes must be token-identical cold vs warm.

    The scenario pins its own geometry rather than inheriting --batch /
    --kv-pool-blocks:

      batch=2            admissions interleave, so pass 1 already exercises
                         in-batch sharing (request i hits blocks request
                         i-1 indexed at prefill landing)
      max_seq>=128       the shared prefix (3/4 of max_seq) must dwarf the
                         unique tails for the TTFT gap to clear the
                         per-call dispatch-overhead noise floor
      pool = 2x default  retired blocks stay indexed only while the pool
                         has room; a pool sized for live slots alone would
                         evict the prefix between passes and the gate
                         would measure reclaim, not reuse"""
    seq = max(args.max_seq, 128)
    n_req, batch = 6, 2
    blocks = 2 * batch * (-(-seq // args.block_size))
    prefix_len = min((3 * seq) // 4, seq - args.max_new - 12)

    rng = np.random.default_rng(args.seed + 3)
    prefix = rng.integers(0, cfg.vocab, prefix_len, dtype=np.int32)
    tails = [rng.integers(0, cfg.vocab, int(rng.integers(3, 9)),
                          dtype=np.int32) for _ in range(n_req)]

    def run_pass(engine, uid0):
        for i in range(n_req):
            engine.submit(Request(
                uid=uid0 + i,
                prompt=np.concatenate([prefix, tails[i]]),
                max_new_tokens=args.max_new,
                sampling=SamplingParams(temperature=0.8, top_k=40, seed=i)
                if i % 2 else SamplingParams()))
        t0 = time.perf_counter()
        done = engine.run()
        wall = time.perf_counter() - t0
        return {r.uid - uid0: list(r.output) for r in done}, wall

    def mk(prefix_cache):
        # only the warm engine traces: its spans carry the warm_hit /
        # cow_copy instants the observability layer exists to surface
        return InferenceEngine(
            cfg, params, batch_size=batch, max_seq=seq,
            block_size=args.block_size, kv_pool_blocks=blocks,
            scheduler=make_policy("fcfs", cache_aware=prefix_cache),
            prefix_cache=prefix_cache,
            tracer=tracer if prefix_cache else None)

    cold = mk(False)
    run_pass(cold, 0)                             # warmup: compile buckets
    cold.reset_stats()
    cold_out, cold_wall = run_pass(cold, 100)
    cst = cold.stats()

    warm = mk(True)
    if warm.prefix_cache is None:
        return {"supported": False,
                "reason": warm.runner.prefix_cache_reason}
    run_pass(warm, 200)                           # populate the index
    run_pass(warm, 300)                           # compile warm buckets
    warm.reset_stats()
    if tracer:
        tracer.clear()
    warm_out, warm_wall = run_pass(warm, 400)
    wst = warm.stats()

    return {
        "supported": True,
        "requests": n_req,
        "shared_prefix_len": prefix_len,
        "tokens_match": warm_out == cold_out,
        "cold": {
            "wall_s": cold_wall,
            "prefill_tokens": cst.nar_tokens,
            "ttft_p50_ms": cst.ttft_p50_ms,
            "ttft_p95_ms": cst.ttft_p95_ms,
        },
        "warm": {
            "wall_s": warm_wall,
            "prefill_tokens": wst.nar_tokens,
            "ttft_p50_ms": wst.ttft_p50_ms,
            "ttft_p95_ms": wst.ttft_p95_ms,
            "prefix_cache_hit_rate": wst.prefix_cache_hit_rate,
            "cached_prefix_tokens": wst.cached_prefix_tokens,
            "cached_blocks": wst.cached_blocks,
            "cow_copies": wst.cow_copies,
            "evicted_blocks": wst.evicted_blocks,
        },
    }


def check_shared_prefix(rec: dict) -> list:
    """The prefix-cache acceptance gate: the warm pass must show the cache
    actually skipping prefill work — correctly — not just running."""
    if not rec.get("supported"):
        return []
    problems = []
    if not rec["tokens_match"]:
        problems.append("warm outputs diverged from cold — cached-prefix "
                        "reuse changed the sampled tokens")
    if not rec["warm"]["prefix_cache_hit_rate"] > 0:
        problems.append("prefix_cache_hit_rate is 0 — no admission ever "
                        "reused a cached prefix")
    if not rec["warm"]["prefill_tokens"] < rec["cold"]["prefill_tokens"]:
        problems.append(
            f"warm prefilled {rec['warm']['prefill_tokens']} prompt tokens, "
            f"not strictly fewer than cold's {rec['cold']['prefill_tokens']}")
    if not rec["warm"]["ttft_p95_ms"] < rec["cold"]["ttft_p95_ms"]:
        problems.append(
            f"warm TTFT p95 {rec['warm']['ttft_p95_ms']:.1f}ms is not "
            f"strictly below cold's {rec['cold']['ttft_p95_ms']:.1f}ms")
    return problems


def check_spec(spec_rec: dict) -> list:
    """The spec-decode acceptance gate: recorded numbers must show the
    subsystem actually amortizing target steps, not just running."""
    if not spec_rec.get("supported"):
        return []
    problems = []
    if not spec_rec["spec_acceptance_rate"] > 0:
        problems.append("spec_acceptance_rate is 0 — no draft token was "
                        "ever accepted")
    if spec_rec["ar_tok_s"] < spec_rec["baseline_ar_tok_s"]:
        problems.append(
            f"spec AR tok/s {spec_rec['ar_tok_s']:.1f} fell below the "
            f"non-spec baseline {spec_rec['baseline_ar_tok_s']:.1f}")
    # end-to-end guard: target-only ar_tok_s cannot see a propose phase
    # gone pathological (draft time is tracked separately), so the
    # draft-inclusive decode throughput must clear the baseline too
    if spec_rec["ar_tok_s_incl_draft"] < spec_rec["baseline_ar_tok_s"]:
        problems.append(
            f"spec AR tok/s incl. draft "
            f"{spec_rec['ar_tok_s_incl_draft']:.1f} fell below the "
            f"non-spec baseline {spec_rec['baseline_ar_tok_s']:.1f} — "
            f"the propose phase is eating the amortization win")
    return problems


def goodput_workload(cfg, params, args) -> dict:
    """Open-loop goodput comparison: the same over-capacity Poisson trace
    through FCFS vs DeadlinePolicy, both on the overlapped host loop,
    scored in goodput (requests/sec meeting their TTFT SLO).

    The regime is calibrated on this host rather than hardcoded, because
    the gate only discriminates in the middle: a too-loose TTFT budget
    lets every request win under both policies (goodput ties on wall
    noise) and a too-tight one lets none win under either.  A closed-loop
    drain (arrival clock collapsed, after a compile pass) measures
    `capacity_rps`; the Poisson rate is `--goodput-overload` times it, and
    the TTFT budget defaults to 3x the calibrated per-request service
    time.  At that operating point FCFS burns full prefill + decode on
    requests that already expired in queue, while the deadline policy
    sheds them at admission and spends the capacity on requests that can
    still meet their deadline — a structural win, not a tuning artifact.

    Both engines replay traces built from the same seeds (fresh task
    objects per engine — tasks are mutable), so arrivals, prompts, and
    per-uid sampling seeds are identical across policies."""
    n = args.goodput_requests
    prompts = PromptSpec(min_len=args.min_prompt_len,
                         max_len=args.max_prompt_len, sampled_frac=0.5)

    def mk(policy, tracer=None):
        return InferenceEngine(cfg, params, batch_size=args.batch,
                               max_seq=args.max_seq,
                               block_size=args.block_size,
                               kv_pool_blocks=args.kv_pool_blocks or None,
                               scheduler=policy, overlap=True,
                               weight_dtype=args.weight_dtype,
                               kv_dtype=args.kv_dtype, tracer=tracer)

    def trace(slo, uid0, rps):
        spec = LoadSpec(requests=n, vocab=cfg.vocab,
                        arrival=ArrivalSpec(rate_rps=rps),
                        prompts=prompts, slo=slo, max_new=args.max_new)
        return make_trace(spec, arrival_seed=args.seed,
                          prompt_seed=args.seed, uid0=uid0)

    # calibrate: closed-loop drain (time_scale=0 collapses the arrival
    # clock) after an identical compile pass = max sustainable throughput
    cal = mk(FCFSPolicy())
    replay(cal, trace(SLOSpec(), 10_000, 1.0), time_scale=0)
    done, wall = replay(cal, trace(SLOSpec(), 20_000, 1.0), time_scale=0)
    capacity_rps = len(done) / wall
    service_ms = 1e3 * wall / len(done)
    rate = args.goodput_overload * capacity_rps
    ttft_slo = args.goodput_ttft_slo_ms or 3.0 * service_ms
    slo = SLOSpec(ttft_ms=ttft_slo)

    out = {"requests": n, "capacity_rps": capacity_rps,
           "service_ms": service_ms, "overload": args.goodput_overload,
           "rate_rps": rate, "ttft_slo_ms": ttft_slo, "policies": {}}
    for policy in (FCFSPolicy(), DeadlinePolicy()):
        engine = mk(policy)
        # warmup without SLOs, closed-loop: nothing sheds, so every
        # (bucket, group size) the measured run can hit gets compiled
        replay(engine, trace(SLOSpec(), 30_000, 1.0), time_scale=0)
        engine.reset_stats()
        done, wall = replay(engine, trace(slo, 0, rate))
        st = engine.stats()
        att = percentiles(st.ttft_slo_ratio)
        out["policies"][policy.name] = {
            "completed": len(done),
            "wall_s": wall,
            "slo_met": st.slo_met,
            "slo_attainment": st.slo_attainment,
            "goodput_rps": st.slo_met / wall if wall else 0.0,
            "requests_shed": st.requests_shed,
            "requests_degraded": st.requests_degraded,
            "ttft_slo_ratio_p50": att["p50"],
            "ttft_slo_ratio_p95": att["p95"],
            "ttft_slo_ratio_p99": att["p99"],
            "host_overlap_ratio": st.host_overlap_ratio,
            "overlapped_steps": st.overlapped_steps,
        }

    # traced re-run of the winning policy: the CI trace artifact, plus the
    # overhead gate's evidence that tracing rides along for free.  Same
    # arrival/prompt seeds as the measured deadline run (uid offsets only
    # re-key per-uid sampling seeds — shapes and arrivals are identical).
    tracer = Tracer(capacity=args.trace_buffer)
    engine = mk(DeadlinePolicy(), tracer=tracer)
    replay(engine, trace(SLOSpec(), 40_000, 1.0), time_scale=0)   # warmup
    engine.reset_stats()
    tracer.clear()
    done, wall = replay(engine, trace(slo, 50_000, rate))
    st = engine.stats()
    if args.trace_out:
        os.makedirs(os.path.dirname(args.trace_out) or ".", exist_ok=True)
        tracer.write(args.trace_out)
    out["traced"] = {
        "policy": "deadline",
        "trace_out": args.trace_out,
        "trace_events": len(tracer.events),
        "trace_dropped": tracer.dropped,
        "trace_problems": validate_chrome_trace(tracer.chrome_trace()),
        "completed": len(done),
        "slo_met": st.slo_met,
        "wall_s": wall,
        "goodput_rps": st.slo_met / wall if wall else 0.0,
    }
    return out


def check_goodput(rec: dict) -> list:
    """The goodput acceptance gate: at the calibrated over-capacity rate
    the deadline policy must strictly out-goodput FCFS, and must actually
    be serving (not shedding its way to an empty win)."""
    f, d = rec["policies"]["fcfs"], rec["policies"]["deadline"]
    problems = []
    if not d["goodput_rps"] > f["goodput_rps"]:
        problems.append(
            f"deadline goodput {d['goodput_rps']:.2f} req/s does not "
            f"strictly beat FCFS {f['goodput_rps']:.2f} req/s at "
            f"{rec['overload']:.1f}x capacity "
            f"(TTFT SLO {rec['ttft_slo_ms']:.0f}ms)")
    if not d["slo_met"] > 0:
        problems.append(
            f"deadline policy met 0 of {rec['requests']} SLOs — the TTFT "
            f"budget {rec['ttft_slo_ms']:.0f}ms is unattainable on this "
            f"host (calibration broke) or shedding ate the whole trace")
    tr = rec.get("traced")
    if tr:
        if tr["trace_problems"]:
            problems.append(
                f"trace artifact failed schema validation: "
                f"{tr['trace_problems'][:3]}")
        if not tr["trace_events"] > 0:
            problems.append("traced goodput run emitted an empty trace")
        # the overhead gate: tracing must cost < 5% goodput.  slo_met is
        # integer-valued, so on short smoke traces one request stepping
        # over its deadline can alone exceed 5% — forgive the gap only
        # when a single-request discretization step fully explains it.
        if (tr["goodput_rps"] < 0.95 * d["goodput_rps"]
                and tr["slo_met"] < d["slo_met"] - 1):
            problems.append(
                f"traced goodput {tr['goodput_rps']:.2f} req/s fell more "
                f"than 5% below untraced {d['goodput_rps']:.2f} req/s "
                f"({tr['slo_met']} vs {d['slo_met']} SLOs met) — tracing "
                f"is not riding along for free")
    return problems


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--min-prompt-len", type=int, default=4)
    ap.add_argument("--max-prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunk budget for the chunked_prefill scenario")
    ap.add_argument("--spec-draft", default="self",
                    help="spec_decode scenario draft: 'self' (default — "
                         "exact greedy acceptance isolates the "
                         "amortization win), 'auto', or a config name")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="spec_decode scenario speculation length")
    ap.add_argument("--tree-branches", type=int, default=3,
                    help="tree_spec scenario: widest tree in the "
                         "k x branches sweep")
    ap.add_argument("--tree-alpha", type=float, default=0.15,
                    help="tree_spec scenario: draft decorrelation — 0 is "
                         "the truncated-target draft (near-100%% accept), "
                         "1 a random draft (~0%%); mid values make the "
                         "rejection-heavy draft the tree gate needs")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV pool block size (tokens)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="KV pool capacity in blocks (0 => engine default "
                         "of batch * ceil(max_seq / block_size))")
    ap.add_argument("--weight-dtype", choices=("bfloat16", "int8"),
                    default="bfloat16",
                    help="base trace + mixed scenario weight storage "
                         "(scenario engines gating other subsystems stay "
                         "bf16); int8 quantizes per output channel")
    ap.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                    default="bfloat16",
                    help="base trace + mixed scenario paged-KV pool "
                         "storage; quant-specific gates live in "
                         "benchmarks/quant_bench.py")
    ap.add_argument("--goodput-requests", type=int, default=28,
                    help="goodput scenario trace length (open-loop "
                         "arrivals; smaller in CI smoke)")
    ap.add_argument("--goodput-overload", type=float, default=3.0,
                    help="goodput scenario Poisson rate as a multiple of "
                         "the calibrated closed-loop capacity")
    ap.add_argument("--goodput-ttft-slo-ms", type=float, default=0.0,
                    help="goodput scenario per-request TTFT budget (0 => "
                         "auto: 3x the calibrated service time)")
    ap.add_argument("--trace-out",
                    default=os.path.join(ART, "TRACE_goodput.json"),
                    help="Chrome trace artifact from the traced goodput "
                         "run (Perfetto-viewable; '' disables the write "
                         "but the traced run and overhead gate still "
                         "execute)")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="tracer ring capacity (events); the oldest are "
                         "evicted beyond it")
    ap.add_argument("--trace-dir", default="",
                    help="also trace the base run and each scenario, "
                         "writing TRACE_<name>.json per scenario here "
                         "(default: off — scenarios run untraced)")
    ap.add_argument("--skip-scenarios", action="store_true",
                    help="base trace only (no mixed / chunked scenarios)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ART, "BENCH_serving.json"))
    args = ap.parse_args(argv)
    if args.min_prompt_len > args.max_prompt_len:
        ap.error(f"--min-prompt-len {args.min_prompt_len} exceeds "
                 f"--max-prompt-len {args.max_prompt_len}")

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    params = lm.init_lm(jax.random.key(args.seed), cfg, jnp.bfloat16)
    base_tracer = _scenario_tracer(args)
    engine = _mk_engine(cfg, params, args, tracer=base_tracer)

    trace_kw = dict(requests=args.requests, min_len=args.min_prompt_len,
                    max_len=args.max_prompt_len, max_new=args.max_new)

    # warmup: the same trace as the measured run, so every length bucket the
    # measurement hits is compiled before the clock starts
    for req in build_trace(cfg, seed=args.seed, **trace_kw):
        engine.submit(req)
    engine.run()
    warm_compiles = engine.stats().prefill_compiles
    engine.reset_stats()
    if base_tracer:
        base_tracer.clear()

    # measured run
    t0 = time.perf_counter()
    for req in build_trace(cfg, seed=args.seed, **trace_kw):
        engine.submit(req)
    done = engine.run()
    wall = time.perf_counter() - t0
    stats = engine.stats()

    record = {
        "bench": "serving",
        "arch": cfg.name,
        "host": "cpu-wallclock",
        "requests": args.requests,
        "batch": args.batch,
        "prompt_len_range": [args.min_prompt_len, args.max_prompt_len],
        "max_new": args.max_new,
        "wall_s": wall,
        "warmup_prefill_compiles": warm_compiles,
        **stats.to_dict(),
    }

    _write_trace(base_tracer, args, "base")

    if not args.skip_scenarios:
        tr_mixed = _scenario_tracer(args)
        mixed = mixed_workload(cfg, params, args, tracer=tr_mixed)
        _write_trace(tr_mixed, args, "mixed")
        unchunked = long_admission(cfg, params, args, FCFSPolicy())
        tr_chunk = _scenario_tracer(args)
        chunked = long_admission(cfg, params, args,
                                 ChunkedPrefillPolicy(args.prefill_chunk),
                                 tracer=tr_chunk)
        _write_trace(tr_chunk, args, "chunked_prefill")
        tr_spec = _scenario_tracer(args)
        spec_rec = spec_workload(cfg, params, args, stats.ar_tok_s,
                                 tracer=tr_spec)
        _write_trace(tr_spec, args, "spec_decode")
        tree_rec = tree_spec_workload(cfg, params, args)
        tr_warm = _scenario_tracer(args)
        prefix_rec = shared_prefix_workload(cfg, params, args,
                                            tracer=tr_warm)
        _write_trace(tr_warm, args, "shared_prefix")
        goodput_rec = goodput_workload(cfg, params, args)
        record["scenarios"] = {
            "mixed": mixed,
            "chunked_prefill": {
                "chunk_tokens": args.prefill_chunk,
                "unchunked": unchunked,
                "chunked": chunked,
                "stall_p95_ratio": (
                    chunked["decode_stall_p95_ms"]
                    / unchunked["decode_stall_p95_ms"]
                    if unchunked["decode_stall_p95_ms"] else 0.0),
            },
            "spec_decode": spec_rec,
            "tree_spec": tree_rec,
            "shared_prefix": prefix_rec,
            "goodput": goodput_rec,
        }

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"served {len(done)} requests in {wall:.2f}s")
    print(stats.summary())
    if stats.kv_pool_blocks:
        dense_positions = args.batch * args.max_seq
        print(f"  KV: {stats.peak_blocks_used * stats.kv_block_size} peak "
              f"pool positions vs {dense_positions} dense (B x max_seq), "
              f"{stats.blocks_per_token:.2f} block-positions/live-token, "
              f"decode step p50 {stats.decode_step_p50_ms:.2f}ms "
              f"p95 {stats.decode_step_p95_ms:.2f}ms")
    print(f"  bytes: weights {stats.weight_bytes_per_device / 2**20:.1f}MiB"
          f"/device ({stats.weight_dtype}), KV pool "
          f"{stats.kv_pool_bytes / 2**20:.1f}MiB ({stats.kv_dtype})")
    if not args.skip_scenarios:
        print(f"  mixed: {mixed['encode_completed']} encode @ "
              f"{mixed['encode_tok_s']:.0f} tok/s + generate @ "
              f"{mixed['ar_tok_s']:.0f} tok/s AR")
        print(f"  long admission ({unchunked['long_prompt_len']} tokens): "
              f"decode-stall p95 {unchunked['decode_stall_p95_ms']:.1f}ms "
              f"unchunked -> {chunked['decode_stall_p95_ms']:.1f}ms chunked "
              f"({chunked['prefill_chunks']} chunks of "
              f"{args.prefill_chunk})")
        if spec_rec.get("supported"):
            print(f"  spec decode (draft={spec_rec['draft']}, "
                  f"k={spec_rec['k']}): {spec_rec['spec_acceptance_rate']:.0%}"
                  f" accept, {spec_rec['spec_tokens_per_step']:.2f} tok/step,"
                  f" AR {spec_rec['baseline_ar_tok_s']:.0f} -> "
                  f"{spec_rec['ar_tok_s']:.0f} tok/s "
                  f"({spec_rec['ar_speedup']:.2f}x target-step; "
                  f"{spec_rec['ar_tok_s_incl_draft']:.0f} tok/s incl "
                  f"draft), draft p95 "
                  f"{spec_rec['draft_time_ms_p95']:.1f}ms")
        else:
            print(f"  spec decode: skipped ({spec_rec.get('reason')})")
        if tree_rec.get("supported"):
            print(f"  tree spec sweep (draft={tree_rec['draft']}, "
                  f"alpha={tree_rec['alpha']}):")
            for c in tree_rec["cells"]:
                print(f"    k={c['k']} b={c['branches']}: "
                      f"{c['accepted_per_round']:.3f} accepted/round, "
                      f"{c['spec_tokens_per_step']:.2f} tok/step, branch "
                      f"{c['spec_branch_utilization']:.0%}, tokens "
                      f"{'identical' if c['tokens_match'] else 'DIVERGED'}")
        else:
            print(f"  tree spec: skipped ({tree_rec.get('reason')})")
        if prefix_rec.get("supported"):
            pw, pc = prefix_rec["warm"], prefix_rec["cold"]
            print(f"  shared prefix ({prefix_rec['shared_prefix_len']} "
                  f"tokens x {prefix_rec['requests']} requests): "
                  f"{pc['prefill_tokens']} -> {pw['prefill_tokens']} "
                  f"prefill tokens, TTFT p95 {pc['ttft_p95_ms']:.1f} -> "
                  f"{pw['ttft_p95_ms']:.1f}ms "
                  f"({pw['prefix_cache_hit_rate']:.0%} hit, "
                  f"{pw['cow_copies']} COW), tokens "
                  f"{'identical' if prefix_rec['tokens_match'] else 'DIVERGED'}")
        else:
            print(f"  shared prefix: skipped ({prefix_rec.get('reason')})")
        gp = goodput_rec["policies"]
        print(f"  goodput ({goodput_rec['requests']} req @ "
              f"{goodput_rec['rate_rps']:.1f} rps = "
              f"{goodput_rec['overload']:.1f}x capacity, TTFT SLO "
              f"{goodput_rec['ttft_slo_ms']:.0f}ms): fcfs "
              f"{gp['fcfs']['goodput_rps']:.2f} -> deadline "
              f"{gp['deadline']['goodput_rps']:.2f} req/s "
              f"({gp['deadline']['slo_met']}/{goodput_rec['requests']} met, "
              f"{gp['deadline']['requests_shed']} shed, "
              f"{gp['deadline']['requests_degraded']} degraded)")
        tr = goodput_rec["traced"]
        print(f"  goodput traced (deadline): {tr['goodput_rps']:.2f} req/s "
              f"({tr['slo_met']} met), {tr['trace_events']} events"
              f"{', ' + str(tr['trace_dropped']) + ' dropped' if tr['trace_dropped'] else ''}"
              f" -> {tr['trace_out'] or '(unwritten)'}")
        problems = check_spec(spec_rec)
        problems += [f"TREE: {p}" for p in check_tree_spec(tree_rec)]
        problems += [f"PREFIX: {p}" for p in check_shared_prefix(prefix_rec)]
        problems += [f"GOODPUT: {p}" for p in check_goodput(goodput_rec)]
        if problems:
            for p in problems:
                print(f"  SCENARIO CHECK FAILED: {p}", file=sys.stderr)
            return 1
    print(f"  -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
