#!/usr/bin/env python3
"""Chip smoke test: the serving path end to end on TPU, at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One chip: phi4-mini-3.8b at its published widths (32 layers, d_model 3072,
24/8 heads of 128, vocab 200064; seeded random bf16 weights, no checkpoint)
behind an `InferenceEngine` built as `repro.launch.serve` builds it (paged
KV in blocks of 16, prefix cache on, FCFS, batch 4).  Six greedy and
sampled requests of 8 to 512 prompt tokens go through it.  Checks:

  * every request retires with `max_new` in-vocab tokens;
  * two greedy requests follow a direct `lm.forward_prefill` +
    `lm.forward_decode` loop on the chip (the oracle of
    tests/test_serving.py): equal tokens, or equal up to a bf16 near-tie
    (see PART_RTOL);
  * first-step logits of the default dispatch (Pallas on TPU) match the
    same forward under `kernel_mode("ref")` (see LOGIT_COS);
  * no op that has a Pallas kernel took the reference
    (`kernels.ops.hidden_fallbacks()`).

Four chips (`--chips 4`): chatglm3-6b at its published widths (28 layers,
d_model 4096, 32 heads with KV 2, vocab 65024) served on the (1, 4)
tensor-parallel mesh `make_mesh_for(4)` gives, then freed, then the same
seeded weights served on one of the four chips.  Greedy tokens must be
equal, or equal up to a bf16 near-tie (see PART_RTOL).  Per-device
`bytes_in_use` shows the weights sharded.

Earlier lines of stdout are free-form; the last is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
The script exits non-zero without it on any failed check, when JAX finds
no TPU, when REPRO_KERNEL_MODE forces interpret or ref kernels, and when
the checkout around it is missing.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
MAX_NEW = 16
BLOCK = 16
BATCH = 4
# Pallas vs reference first-step logits: cosine >= LOGIT_COS over the
# vocabulary, and the Pallas argmax a near-tie of the reference's (below).
# The Pallas GEMMs round their operands to bf16 (the BF16 policy) where the
# reference keeps normalized activations in fp32: 0.1-0.5% per GEMM at phi4
# widths (interpret mode vs fp32 on the CPU), which 64 random-weight
# sublayers amplify to about 6.5% of max |logit| at the head (on a v5e).
# A mis-tiled or mis-indexed kernel decorrelates the logits instead.
LOGIT_COS = 0.99
# Two greedy streams computed in a different arithmetic order (paged
# 16-token online softmax vs the oracle's dense softmax, padded buckets,
# bf16 vs fp32 operands, a tensor-parallel psum) may part where the two
# best logits are within that bf16 drift: at the first step where they
# part, the reference forward over the common prefix must rank both tokens
# within PART_RTOL * max |logits|.  Random weights make such near-ties
# common.
PART_RTOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class Phases:
    """Wall seconds, and XLA backend-compile seconds from jax.monitoring,
    per named phase."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.name = "setup"
        self.compile_s = collections.defaultdict(float)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.compile_s[self.name] += duration

    def start(self, name: str) -> float:
        self.name = name
        print(f"[{name}]", flush=True)
        return time.perf_counter()

    def end(self, t0: float) -> None:
        print(f"  {self.name}: {time.perf_counter() - t0:.1f}s wall, "
              f"{self.compile_s[self.name]:.1f}s compiling", flush=True)


def refuse_unless_tpu():
    """-> jax.devices() when they are TPUs; exits 2 otherwise."""
    mode = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode in ("interpret", "ref"):
        sys.exit(f"chip_smoke: REPRO_KERNEL_MODE={mode} would keep the "
                 f"kernels off the chip; unset it")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it "
                 f"from a checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this smoke test runs on the "
                 f"chip only")
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"(platform {devices[0].platform}); jax {jax.__version__}; "
          f"compile cache {cache}", flush=True)
    return devices


def seeded_params(cfg, mesh, max_seq: int, num_blocks: int):
    """Seeded random bf16 weights, built on the device(s) directly in the
    layout the serving steps shard them by (no host copy, no checkpoint)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.launch import steps
    from repro.models import lm
    dstep = steps.make_decode_step(
        cfg, ShapeConfig("engine_decode", "decode", max_seq, BATCH), mesh,
        max_seq=max_seq, with_sampling=True, paged=(num_blocks, BLOCK))
    shardings = steps.to_shardings(dstep.aux["param_specs"], mesh)
    init = functools.partial(lm.init_lm, cfg=cfg, dtype=jnp.bfloat16)
    params = jax.jit(init, out_shardings=shardings)(jax.random.key(SEED))
    return jax.block_until_ready(params)


def make_engine(cfg, params, mesh, max_seq: int, num_blocks: int):
    """The engine as `repro.launch.serve` constructs it by default."""
    from repro.serving import InferenceEngine, make_policy
    return InferenceEngine(
        cfg, params, batch_size=BATCH, max_seq=max_seq, mesh=mesh,
        block_size=BLOCK, kv_pool_blocks=num_blocks,
        scheduler=make_policy("fcfs", chunk_tokens=32, cache_aware=True),
        fuse_epilogues=True, spec=None, draft_checkpoint=None,
        prefix_cache=True, cache_blocks=None, weight_dtype="bfloat16",
        kv_dtype="bfloat16", overlap=False, tracer=None)


def make_requests(cfg, lengths, sampled):
    import numpy as np
    from repro.serving import Request, SamplingParams
    rng = np.random.default_rng(SEED)
    reqs = []
    for uid, n in enumerate(lengths):
        sp = (SamplingParams(temperature=0.8, top_k=40, seed=uid)
              if uid in sampled else SamplingParams())
        reqs.append(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab, n, dtype=np.int32), max_new_tokens=MAX_NEW,
            sampling=sp))
    return reqs


def serve(engine, reqs, cfg) -> dict:
    """Run `reqs` through the engine -> {uid: tokens}; checks retirement."""
    for r in reqs:
        engine.submit(r)
    done = engine.run()
    check(sorted(r.uid for r in done) == [r.uid for r in reqs],
          f"all {len(reqs)} requests retired")
    for r in sorted(done, key=lambda r: r.uid):
        print(f"  req {r.uid}: prompt {r.prompt_len} (bucket {r.bucket}), "
              f"prefill {r.prefill_ms:.1f}ms, {len(r.output)} tokens, "
              f"{'sampled' if r.sampling.temperature > 0 else 'greedy'}, "
              f"first {list(r.output[:6])}", flush=True)
        check(len(r.output) == MAX_NEW
              and all(0 <= t < cfg.vocab for t in r.output),
              f"req {r.uid}: {MAX_NEW} in-vocab tokens")
    st = engine.stats()
    print(f"  engine: {st.prefill_compiles} prefill buckets compiled "
          f"{sorted(st.bucket_hits)}, {engine.steps_run} decode steps",
          flush=True)
    return {r.uid: list(r.output) for r in done}


def direct_greedy(cfg, params, prompt, n_new: int, max_seq: int):
    """The oracle: unpadded `forward_prefill` + `forward_decode` loop."""
    import jax
    import jax.numpy as jnp
    from repro.core.precision import BF16
    from repro.models import lm
    from repro.sharding.plan import UNSHARDED
    prefill = jax.jit(lambda p, t: lm.forward_prefill(
        p, {"tokens": t}, plan=UNSHARDED, cfg=cfg, policy=BF16,
        max_seq=max_seq))
    decode = jax.jit(lambda p, t, pos, c: lm.forward_decode(
        p, t, pos, c, plan=UNSHARDED, cfg=cfg, policy=BF16),
        donate_argnums=(3,))
    tok, caches, pos = prefill(params, jnp.asarray(prompt)[None])
    out = [int(tok[0])]
    for _ in range(n_new - 1):
        tok, caches = decode(params, tok, pos, caches)
        pos = pos + 1
        out.append(int(tok[0]))
    return out


def first_logits(cfg, params, prompt, mode: str):
    """fp32 logits after `prompt` under kernel dispatch `mode`, on the
    vocabulary columns.  A fresh jit per call: the mode is read at trace."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.precision import BF16
    from repro.kernels import ops
    from repro.models import lm
    from repro.sharding.plan import UNSHARDED
    with ops.kernel_mode(mode):
        z = jax.jit(lambda p, t: lm.prefill_logits(
            p, {"tokens": t}, plan=UNSHARDED, cfg=cfg, policy=BF16))(
                params, jnp.asarray(prompt)[None])
        z = np.asarray(z)
    return z[0, :cfg.vocab]


def compare_logits(cfg, params, prompt) -> None:
    import numpy as np
    zp = first_logits(cfg, params, prompt, "auto")
    zr = first_logits(cfg, params, prompt, "ref")
    check(np.isfinite(zp).all() and np.isfinite(zr).all(), "finite logits")
    scale = float(np.max(np.abs(zr)))
    cos = float(zp @ zr / (np.linalg.norm(zp) * np.linalg.norm(zr)))
    gap = float(zr.max() - zr[zp.argmax()])
    print(f"  logits over {cfg.vocab}: max |pallas - ref| "
          f"{float(np.max(np.abs(zp - zr))):.5f}, max |ref| {scale:.4f}, "
          f"cosine {cos:.6f}, argmax pallas {int(zp.argmax())} ref "
          f"{int(zr.argmax())} (ref gap {gap:.5f})", flush=True)
    check(cos >= LOGIT_COS,
          f"Pallas vs reference logits: cosine >= {LOGIT_COS}")
    check(gap <= PART_RTOL * scale,
          f"Pallas argmax is the reference's, or a near-tie of it")


def report_dispatch() -> None:
    """Print, for every dispatched op, whether it ran Pallas or the
    reference on the chip; fail on a hidden fallback."""
    from repro.kernels import ops
    rec = ops.dispatch_record()
    for op in ops.KERNEL_OPS + ops.REF_ONLY:
        row = rec.get(op)
        if op in ops.REF_ONLY:
            how = "reference only (no Pallas kernel)"
        elif row is None:
            how = "not on this path"
        elif row["ref"]:
            how = "REFERENCE"
        else:
            how = "pallas"
        counts = (f" [pallas {row['pallas']}, ref {row['ref']}]"
                  if row else "")
        print(f"  op {op}: {how}{counts}", flush=True)
    check(not ops.hidden_fallbacks(),
          f"no kernel op took the reference "
          f"(fallbacks: {ops.hidden_fallbacks()})")


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        ms = d.memory_stats() or {}
        parts.append(f"dev{d.id} {ms.get('bytes_in_use', 0) / 2**30:.2f}"
                     f"/peak {ms.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                     f" GiB")
    return ", ".join(parts)


def one_chip(devices, phases: Phases) -> None:
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh_for
    cfg = get_config("phi4-mini-3.8b")
    lengths = (8, 24, 100, 130, 300, 512)   # buckets 8 24 128 192 384 512
    max_seq = 576                           # longest prompt + MAX_NEW, /16
    num_blocks = BATCH * (max_seq // BLOCK)  # every slot at max_seq at once
    ops.reset_dispatch_record()
    mesh = make_mesh_for(1)                 # as launch/serve.py on 1 chip

    t = phases.start("init phi4-mini-3.8b weights")
    params = seeded_params(cfg, mesh, max_seq, num_blocks)
    phases.end(t)
    print(f"  {cfg.n_params() / 1e9:.2f}B params; {memory_line(devices)}",
          flush=True)

    t = phases.start("serve")
    engine = make_engine(cfg, params, mesh, max_seq, num_blocks)
    reqs = make_requests(cfg, lengths, sampled={1, 3, 5})
    out = serve(engine, reqs, cfg)
    phases.end(t)

    t = phases.start("oracle")
    for uid in (0, 4):
        want = direct_greedy(cfg, params, reqs[uid].prompt, MAX_NEW,
                             max_seq)
        print(f"  req {uid}: engine {out[uid]}", flush=True)
        print(f"  req {uid}: direct {want}", flush=True)
        follows(cfg, params, reqs[uid].prompt, out[uid], want,
                f"req {uid}: engine vs direct decode loop")
    phases.end(t)

    t = phases.start("pallas vs reference logits")
    compare_logits(cfg, params, reqs[2].prompt)
    phases.end(t)

    print("[dispatch]", flush=True)
    report_dispatch()
    print(f"  memory: {memory_line(devices)}", flush=True)


def follows(cfg, params, prompt, got, want, what: str) -> None:
    """Check greedy stream `got` against the reference stream `want`:
    equal, or equal up to a step where the reference forward over the
    common prefix ranks got[i] within PART_RTOL of want[i]."""
    import numpy as np
    if got == want:
        check(True, f"{what}: greedy tokens equal")
        return
    i = next(k for k, (a, b) in enumerate(zip(got, want)) if a != b)
    z = first_logits(cfg, params,
                     np.concatenate([prompt, np.asarray(want[:i],
                                                        np.int32)]), "auto")
    gap = float(z[want[i]] - z[got[i]])
    tol = PART_RTOL * float(np.max(np.abs(z)))
    print(f"    part at step {i}: reference logit gap {gap:.5f}, "
          f"max |logit| {float(np.max(np.abs(z))):.4f}", flush=True)
    check(gap <= tol, f"{what}: tokens equal for {i} steps, then a "
          f"near-tie (gap <= {PART_RTOL} x max |logit|)")


def four_chips(devices, phases: Phases) -> None:
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh_for
    check(len(devices) == 4, f"4 devices (found {len(devices)})")
    cfg = get_config("chatglm3-6b")
    lengths = (16, 40, 90, 200)
    max_seq = 224                            # longest prompt + MAX_NEW
    num_blocks = BATCH * (max_seq // BLOCK)
    ops.reset_dispatch_record()

    mesh = make_mesh_for(4)
    print(f"mesh: {dict(mesh.shape)}", flush=True)
    t = phases.start("init chatglm3-6b weights, sharded")
    params = seeded_params(cfg, mesh, max_seq, num_blocks)
    phases.end(t)
    print(f"  {cfg.n_params() / 1e9:.2f}B params; {memory_line(devices)}",
          flush=True)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    check(max(in_use) < 0.5 * cfg.n_params() * 2,
          "weights sharded: no device holds half of them")

    t = phases.start("serve on 4 chips")
    engine = make_engine(cfg, params, mesh, max_seq, num_blocks)
    reqs = make_requests(cfg, lengths, sampled=set())
    out4 = serve(engine, reqs, cfg)
    phases.end(t)
    print("[dispatch]", flush=True)
    report_dispatch()
    del engine, params
    gc.collect()
    print(f"  freed: {memory_line(devices)}", flush=True)

    mesh1 = make_mesh_for(1)
    t = phases.start("init the same weights on one chip")
    params = seeded_params(cfg, mesh1, max_seq, num_blocks)
    phases.end(t)
    print(f"  {memory_line(devices[:1])}", flush=True)
    t = phases.start("serve on 1 chip")
    engine = make_engine(cfg, params, mesh1, max_seq, num_blocks)
    out1 = serve(engine, make_requests(cfg, lengths, sampled=set()), cfg)
    phases.end(t)

    t = phases.start("compare 4 chips with 1 chip")
    for r in reqs:
        print(f"  req {r.uid}: 4 chips {out4[r.uid]}", flush=True)
        print(f"  req {r.uid}: 1 chip  {out1[r.uid]}", flush=True)
        follows(cfg, params, r.prompt, out4[r.uid], out1[r.uid],
                f"req {r.uid}: 4 chips vs 1 chip")
    phases.end(t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phi4-mini-3.8b on one chip (default); 4: "
                         "chatglm3-6b sharded over four chips vs one")
    args = ap.parse_args(argv)
    devices = refuse_unless_tpu()
    phases = Phases()
    try:
        if args.chips == 4:
            four_chips(devices, phases)
        else:
            one_chip(devices, phases)
    except SmokeFailure as e:
        print(f"FAILED: {e}", flush=True)
        return 1
    print("backend compile seconds per phase: " + ", ".join(
        f"{k} {v:.1f}" for k, v in phases.compile_s.items()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
