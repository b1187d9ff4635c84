"""End-to-end fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --reduced --steps 200 --global-batch 8 --seq 128 \
        --checkpoint-dir /tmp/ckpt [--resume] [--compress int8]

Runs on whatever devices exist (CPU: reduced configs; TPU pod: full).
Features wired in: step-indexed resumable data pipeline, async sharded
checkpoints, SIGTERM -> checkpoint -> exit 42, straggler watchdog,
optional int8 cross-pod gradient compression, XLA latency-hiding flags.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# compute/comm overlap: latency-hiding scheduler (effective on TPU; harmless
# on CPU).  Must be set before jax initializes.  Appended to whatever the
# machine already passes to libtpu, never replacing it.
os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
    os.environ.get("LIBTPU_INIT_ARGS", ""),
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true")))

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import make_stream
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh_for
from repro.runtime import PreemptionGuard, StragglerWatchdog
from repro.runtime.fault_tolerance import RESTART_EXIT_CODE


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data", default="", help="binary token file (optional)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", default="", choices=["", "int8"])
    ap.add_argument("--single-device", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli_train", "train", args.seq, args.global_batch)
    mesh = None if args.single_device else make_mesh_for(len(jax.devices()))
    from repro.optim import cosine_schedule
    bundle = steps_mod.make_train_step(
        cfg, shape, mesh,
        lr_fn=cosine_schedule(args.lr, min(100, args.steps // 10 + 1),
                              args.steps),
        grad_compression=args.compress or None)
    stream = make_stream(cfg, global_batch=args.global_batch,
                         seq_len=args.seq + (cfg.n_patches or 0),
                         path=args.data or None, seed=args.seed)

    ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        shardings = steps_mod.to_shardings(bundle.aux["state_specs"], mesh)
        state = ckpt.restore(bundle.in_structs[0], shardings=shardings)
        start = int(ckpt.latest_step())
        print(f"resumed from step {start}")
    else:
        state = bundle.aux["init_state"](args.seed)

    watchdog = StragglerWatchdog(
        on_straggler=lambda s, dt, mu: print(
            f"[straggler] step {s}: {dt*1e3:.0f}ms vs mean {mu*1e3:.0f}ms"))

    with PreemptionGuard() as guard:
        for step in range(start, args.steps):
            watchdog.start()
            state, metrics = bundle.fn(state, stream.batch(step))
            jax.block_until_ready(metrics["loss"])
            watchdog.stop(step)
            if (step + 1) % args.log_every == 0 or step == start:
                print(f"step {step+1:6d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}")
            if ckpt and (step + 1) % args.checkpoint_every == 0:
                ckpt.save_async(state, step + 1)
            if guard.should_stop:
                print("preempted: checkpointing and exiting for restart")
                if ckpt:
                    ckpt.save(state, step + 1)
                return RESTART_EXIT_CODE
    if ckpt:
        ckpt.save(state, args.steps)
        ckpt.wait()
    print(f"done: {args.steps} steps, final loss "
          f"{float(metrics['loss']):.4f}, stragglers {len(watchdog.events)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
