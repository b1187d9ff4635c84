"""Serving driver: session-based continuous-batching engine demo.

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --reduced --requests 8 --batch 4 --prompt-len 48 --min-prompt-len 8 \
        --max-new 16 --temperature 0.7 --top-k 40

Drives a mixed-length request trace through `InferenceEngine` and reports
the paper's two serving metrics from `engine.stats()`: NAR prompt-encoding
throughput and AR decode throughput (tokens/s, counted from true per-request
prompt lengths, not padded buckets), plus TTFT / queue-wait percentiles,
decode-slot occupancy, and prefill bucket hits.

Scheduler/runner split knobs:
  --policy {fcfs,priority,chunked,deadline}
                                     scheduling policy (fcfs = classic;
                                     deadline = EDF with SLO shedding and
                                     degrade, serving/scheduler.py)
  --prefill-chunk N                  chunk budget for --policy chunked
  --task {generate,encode}           decoder AR traffic vs encoder-only
                                     pooled-embedding traffic (EncodeTask)
  --overlap                          overlapped host loop: dispatch decode
                                     step N+1 before fetching step N's
                                     tokens (token-identical to the sync
                                     loop; engine.py)
  --deadline-ms MS                   per-request TTFT budget stamped onto
                                     every generated request (0 = none) —
                                     the deadline policy schedules, sheds,
                                     and scores SLO attainment on it

Speculative decoding (serving/spec.py):
  --spec-draft NAME                  turn on speculation: "self" (the
                                     target proposes for itself — the
                                     zero-risk upper bound), "auto"
                                     (derive a 2-layer draft), or a
                                     registered draft config (e.g.
                                     "gpt-j-draft")
  --spec-k K                         draft tokens proposed per verify step
  --spec-branches B                  token-tree width: B > 1 proposes the
                                     draft's top-B candidates per depth
                                     and verifies the whole tree in one
                                     tree-masked target pass (still
                                     token-identical; 1 = classic chain)
  --draft-checkpoint DIR             load draft params from a
                                     checkpoint/checkpointer.py directory
                                     instead of seeded init

Prefix caching (serving/prefix_cache.py, on by default):
  --no-prefix-cache                  cold prefills: no KV block sharing
                                     across requests
  --cache-blocks N                   cap the radix index at N pool blocks
                                     (default: bounded by pool pressure —
                                     lazy LRU eviction on alloc failure)

Observability (serving/trace.py):
  --trace-out PATH                   record a structured trace and write
                                     it as Chrome trace-event JSON (open
                                     at https://ui.perfetto.dev); tracing
                                     is opt-in and token-identical
  --trace-buffer N                   tracer ring-buffer capacity (events)
  --metrics-out PATH                 write a Prometheus-style text
                                     snapshot of the final engine stats

Low-precision serving (models/quantize.py; both default to lossless bf16):
  --weight-dtype int8                weight-only int8: per-output-channel
                                     quantization, dequant fused into the
                                     GEMM epilogues
  --kv-dtype int8                    int8 paged KV pools with per-block-
                                     per-head scales (quantize-on-write,
                                     dequant-on-read in the paged kernels)
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models import lm
from repro.serving import (EncodeTask, InferenceEngine, Request,
                           SamplingParams, SpecConfig, Tracer, make_policy,
                           prometheus_text)


def build_trace(cfg, args) -> list:
    """Mixed-length request trace; lengths uniform in
    [min_prompt_len, prompt_len] (fixed-length when min == max).
    --task encode emits EncodeTasks (pooled embeddings) instead."""
    rng = np.random.default_rng(args.seed)
    lo = args.min_prompt_len or args.prompt_len
    reqs = []
    for uid in range(args.requests):
        n = int(rng.integers(lo, args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab, n, dtype=np.int32)
        deadline = args.deadline_ms or None
        if args.task == "encode":
            reqs.append(EncodeTask(uid=uid, prompt=prompt,
                                   pooling=args.pooling,
                                   priority=uid % 3,
                                   deadline_ms=deadline))
            continue
        sampling = (SamplingParams(temperature=args.temperature,
                                   top_k=args.top_k, seed=uid)
                    if args.temperature > 0 else SamplingParams())
        reqs.append(Request(
            uid=uid,
            prompt=prompt,
            max_new_tokens=args.max_new,
            priority=uid % 3,
            deadline_ms=deadline,
            sampling=sampling))
    return reqs


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length")
    ap.add_argument("--min-prompt-len", type=int, default=0,
                    help="min prompt length (0 => fixed at --prompt-len)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 => greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--policy",
                    choices=("fcfs", "priority", "chunked", "deadline"),
                    default="fcfs", help="scheduling policy")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped host loop: dispatch the next decode "
                         "step before fetching the previous step's tokens "
                         "(token-identical to the sync loop)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request TTFT SLO budget in ms (0 = no "
                         "deadline); pairs with --policy deadline")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked prefill token budget (--policy chunked)")
    ap.add_argument("--task", choices=("generate", "encode"),
                    default="generate",
                    help="generate: AR decode requests; encode: "
                         "encoder-only pooled-embedding requests")
    ap.add_argument("--pooling", choices=("last", "mean"), default="last",
                    help="EncodeTask pooling (--task encode)")
    ap.add_argument("--spec-draft", default="",
                    help="speculative decoding draft: 'self', 'auto', or a "
                         "registered draft config name (empty = off)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculation length: draft tokens proposed per "
                         "verify step (--spec-draft)")
    ap.add_argument("--spec-branches", type=int, default=1,
                    help="token-tree width: candidates proposed per "
                         "speculation depth (1 = single-chain rounds)")
    ap.add_argument("--draft-checkpoint", default="",
                    help="checkpoint directory to load draft params from "
                         "(default: seeded init; requires --spec-draft)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV pool block size (tokens)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="KV pool capacity in blocks (0 => engine default); "
                         "undersize it to exercise preemption")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share cached prompt-prefix KV blocks across "
                         "requests (serving/prefix_cache.py); "
                         "--no-prefix-cache restores cold prefills")
    ap.add_argument("--cache-blocks", type=int, default=0,
                    help="cap on pool blocks the prefix-cache index may "
                         "hold (0 => bounded by pool pressure alone)")
    ap.add_argument("--weight-dtype", choices=("bfloat16", "int8"),
                    default="bfloat16",
                    help="GEMM weight storage: int8 quantizes per output "
                         "channel once at startup (models/quantize) and "
                         "dequantizes inside the fused fp32 epilogues")
    ap.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                    default="bfloat16",
                    help="paged KV pool storage: int8 quantizes on write "
                         "with per-block-per-head scales (dense fallback "
                         "layouts stay bf16)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run "
                         "(Perfetto-viewable); empty = tracing off")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="tracer ring-buffer capacity in events "
                         "(oldest dropped beyond it)")
    ap.add_argument("--metrics-out", default="",
                    help="write a Prometheus-style text snapshot of the "
                         "final stats (empty = off)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable the fused prologue/epilogue GEMM "
                         "pipeline (A/B parity baseline)")
    ap.add_argument("--single-device", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.min_prompt_len > args.prompt_len:
        ap.error(f"--min-prompt-len {args.min_prompt_len} exceeds "
                 f"--prompt-len {args.prompt_len}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None if args.single_device else make_mesh_for(len(jax.devices()))
    params = lm.init_lm(jax.random.key(args.seed), cfg, jnp.bfloat16)

    spec = (SpecConfig(draft=args.spec_draft, k=args.spec_k,
                       branches=args.spec_branches)
            if args.spec_draft else None)
    tracer = Tracer(capacity=args.trace_buffer) if args.trace_out else None
    engine = InferenceEngine(
        cfg, params, batch_size=args.batch, max_seq=args.max_seq, mesh=mesh,
        block_size=args.block_size,
        kv_pool_blocks=args.kv_pool_blocks or None,
        scheduler=make_policy(args.policy, chunk_tokens=args.prefill_chunk,
                              cache_aware=args.prefix_cache),
        fuse_epilogues=not args.no_fuse, spec=spec,
        draft_checkpoint=args.draft_checkpoint or None,
        prefix_cache=args.prefix_cache,
        cache_blocks=args.cache_blocks or None,
        weight_dtype=args.weight_dtype, kv_dtype=args.kv_dtype,
        overlap=args.overlap, tracer=tracer)
    if (args.policy == "chunked"
            and not engine.runner.supports_chunked):
        print(f"note: {cfg.name} cannot chunk prefills "
              f"(recurrent/ring/cross-attn cache state) — "
              f"falling back to whole-prompt admission")
    if args.prefix_cache and engine.prefix_cache is None:
        print(f"note: prefix cache disabled for {cfg.name} — "
              f"{engine.runner.prefix_cache_reason}")
    for req in build_trace(cfg, args):
        engine.submit(req)

    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    stats = engine.stats()

    print(f"served {len(done)} requests in {wall:.2f}s over "
          f"{engine.steps_run} AR steps "
          f"[policy={args.policy}"
          f"{', overlap' if args.overlap else ''}] "
          f"({stats.prefill_compiles} prefill buckets compiled: "
          f"{sorted(stats.bucket_hits)})")
    print(stats.summary())
    if spec is not None:
        print(f"  spec: draft={engine.runner.draft_cfg.name} k={args.spec_k}"
              f" | {stats.spec_acceptance_rate:.0%} of "
              f"{stats.spec_proposed_tokens} proposals accepted, "
              f"{stats.spec_tokens_per_step:.2f} tokens/target-step, "
              f"draft p50 {stats.draft_time_ms_p50:.1f}ms p95 "
              f"{stats.draft_time_ms_p95:.1f}ms")
        if engine.runner.tree_branches > 1:
            print(f"  tree: b={engine.runner.tree_branches} | "
                  f"{stats.spec_tree_nodes} nodes verified, accepted-path "
                  f"depth p50 {stats.spec_path_depth_p50:.1f} p95 "
                  f"{stats.spec_path_depth_p95:.1f}, branch utilization "
                  f"{stats.spec_branch_utilization:.0%}")
    if tracer is not None:
        n_ev = tracer.write(args.trace_out)
        print(f"  trace: {n_ev} events -> {args.trace_out} "
              f"({tracer.dropped} dropped; open at https://ui.perfetto.dev)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(prometheus_text(stats.to_dict()))
        print(f"  metrics: -> {args.metrics_out}")
    for r in sorted(done, key=lambda r: r.uid)[:3]:
        if isinstance(r, EncodeTask):
            e = np.asarray(r.embedding)
            print(f"  enc {r.uid}: prompt {r.prompt_len} (bucket "
                  f"{r.bucket}), {r.encode_ms:.0f}ms, |emb|="
                  f"{float(np.linalg.norm(e)):.3f} [{e[0]:+.4f} "
                  f"{e[1]:+.4f} ...]")
        else:
            print(f"  req {r.uid}: prompt {r.prompt_len} (bucket "
                  f"{r.bucket}), prefill {r.prefill_ms:.0f}ms, "
                  f"{len(r.output)} tokens, first: {r.output[:8]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
