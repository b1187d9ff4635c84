"""Set-up: the parameter layout, and a warm-up of every program shape the
cell's traffic will use, and no other.

The engine compiles a prefill program (and its KV scatter) per (length
bucket, group size), a warm-admission chunk program per suffix bucket and
one decode program.  The generator's sizes are the same multiset for every
seed, so the buckets below are too: the warm-up covers each bucket the
traffic's prompts fall in, at every group size up to the harness's cap.
"""
from __future__ import annotations

import numpy as np

from . import traffic as trafmod

WARM_SEED = 0x57A12      # warm-up prompts: content only, never served


def max_seq_for(traffic, block_size: int) -> int:
    return -(-traffic.max_len() // block_size) * block_size


def param_shardings(pcfg, mesh, eng_cfg: dict, max_seq: int):
    """Shardings of the engine's parameters, as its decode step lays them
    out, so that the weights are made in place (no copy at start-up)."""
    from repro.configs.base import ShapeConfig
    from repro.launch import steps
    dstep = steps.make_decode_step(
        pcfg, ShapeConfig("engine_decode", "decode", max_seq,
                          eng_cfg["batch_size"]), mesh,
        max_seq=max_seq, with_sampling=True,
        paged=(eng_cfg["kv_pool_blocks"], eng_cfg["block_size"]))
    return steps.to_shardings(dstep.aux["param_specs"], mesh)


def size_sets(mix: dict, phases):
    """(prompt lengths, suffix lengths) the traffic can send over its
    `phases` (seconds each): whole prompt lengths of cold admissions, and
    the suffix lengths that warm admissions of a prefixed mix encode."""
    if mix["loop"] == "open":
        counts = [trafmod.phase_count(float(mix["rate_rps"]), s)
                  for s in phases]
    else:
        counts = [4 * int(mix["clients"])]
    own = sorted({int(x) for n in counts if n
                  for x in trafmod.lengths(mix["prompt"], n)})
    pre = mix.get("prefixes")
    if not pre:
        return own, []
    p = int(pre["len"])
    return [p] + [p + s for s in own], own


def warm(engine, mix: dict, prefixes, eng_cfg: dict, vocab: int,
         phases, make_task) -> dict:
    """Run one request group of every shape through the engine; -> what
    was warmed.  `prefixes` are the traffic's shared prompts: set-up puts
    each into the prefix cache, as the traffic's own set-up."""
    rng = np.random.default_rng(WARM_SEED)
    cap = int(eng_cfg["prefill_group_cap"])
    prompts, suffixes = size_sets(mix, phases)
    buckets = sorted({engine.bucket_for(n) for n in prompts})
    uid = [-1]

    def req(tokens, max_new=1):
        uid[0] -= 1
        return make_task(trafmod.Req(uid=uid[0], prompt=np.asarray(
            tokens, np.int32), max_new=max_new, sampled=False,
            sample_seed=0))

    def serve(reqs):
        for r in reqs:
            engine.submit(r)
        engine.run()

    longest = engine.runner.prompt_cap   # the top bucket may pass it
    for b in buckets:
        for g in range(1, cap + 1):
            serve([req(rng.integers(0, vocab, min(b, longest)))
                   for _ in range(g)])
    serve([req(rng.integers(0, vocab, buckets[0]), max_new=3)])
    widths = []
    if engine.prefix_cache is not None:
        # a warm admission encodes its uncached suffix in one chunk pass
        # as wide as the suffix's bucket.  Besides the suffixes after a
        # shared prefix, a prompt whose first token equals a cached
        # sequence's is a one-token hit (the cache matches partial
        # blocks), and its chunk is as wide as the prompt's bucket less one
        if prefixes:
            for i in range(0, len(prefixes), cap):
                serve([req(p) for p in prefixes[i:i + cap]])
            head = prefixes[0]
            widths = {engine.bucket_for(s) for s in suffixes}
        else:
            head = rng.integers(0, vocab, 1).astype(np.int32)
            serve([req(np.concatenate([head, rng.integers(0, vocab,
                                                          buckets[0])]))])
            widths = {engine.bucket_for(n - 1) for n in prompts if n > 1}
        widths = sorted(widths)
        for w in widths:
            serve([req(np.concatenate([head, rng.integers(
                0, vocab, min(w, longest - len(head)))]))])
    return {"prefill_buckets": buckets, "groups": cap,
            "chunk_widths": widths}
