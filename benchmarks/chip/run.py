#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell names a configuration (configs/<config>.json: the model as run,
the engine settings, the correctness limit) and a traffic mix
(traffic/<mix>.json).  The run makes seeded weights on the chip, builds
the serving engine as `repro.launch.serve` builds it, warms every program
shape the cell's traffic will use, serves the traffic for a lead-in and
then for the measured window, and checks the served tokens against the
plain reference (chipbench/reference.py).

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (with --trace 0 the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read by metrics/<name>.py from the
window and a profiler trace of its first seconds), `device` and, last,
`checks`: each number compared with its limit (also the last lines of
stderr).  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.

`--control int8` judges, in place of the served tokens, the tokens that
the reference computed with int8 weights puts first at the same positions:
the check's control, which must come out not correct (PERF.md).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import check, harness, registry, report  # noqa: E402
from chipbench import model as modelmod, traffic as trafmod  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
MIN_TOKENS = 256        # served greedy tokens the check compares, at least
DRAIN_S = 60.0          # wait past the window for first tokens due in it
TRACE_S = 6.0           # traced seconds at the start of the window


class Refused(Exception):
    """The run cannot measure what the cell asks: no result is printed."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: override the mix's rate (knee sweeps)")
    return ap.parse_args(argv)


def program_path() -> str:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no src/repro under {ROOT}: the system under test "
                      f"is missing from this checkout")
    return src


def require_chips(n: int):
    """The first n devices, which must be TPUs; the compile cache is
    pointed into the checkout before anything compiles."""
    mode = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode in ("interpret", "ref"):
        raise Refused(f"REPRO_KERNEL_MODE={mode} keeps the kernels off "
                      f"the chip")
    # the TPU runtime would otherwise log under /tmp, outside the run's own
    # directories
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform "
                      f"{devices[0].platform!r})")
    if len(devices) < n:
        raise Refused(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def build_engine(pcfg, weights, mesh, engine_cfg: dict, max_seq: int):
    """The engine as `repro.launch.serve` constructs it by default, with
    the configuration file's batch and pool."""
    from repro.serving import InferenceEngine, make_policy
    return InferenceEngine(
        pcfg, weights, batch_size=engine_cfg["batch_size"], max_seq=max_seq,
        mesh=mesh, block_size=engine_cfg["block_size"],
        kv_pool_blocks=engine_cfg["kv_pool_blocks"],
        scheduler=make_policy("fcfs", chunk_tokens=32, cache_aware=True),
        fuse_epilogues=True, spec=None, draft_checkpoint=None,
        prefix_cache=engine_cfg["prefix_cache"], cache_blocks=None,
        weight_dtype="bfloat16",
        kv_dtype="bfloat16", overlap=False, tracer=None)


def make_task_fn(mix: dict):
    """traffic.Req -> the program's request."""
    from repro.serving import Request, SamplingParams
    samp = mix.get("sampling", {})

    def make_task(req):
        sp = (SamplingParams(temperature=float(samp["temperature"]),
                             top_k=int(samp["top_k"]), seed=req.sample_seed)
              if req.sampled else SamplingParams())
        return Request(uid=req.uid, prompt=req.prompt,
                       max_new_tokens=req.max_new, sampling=sp)
    return make_task


def watch_compiles():
    """A list that collects (host time, seconds) of every XLA compile
    from now on."""
    import jax
    seen = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append((time.perf_counter(), duration))
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def main(argv=None) -> int:
    args = parse(argv)
    bench = registry.load(ROOT)
    cell = registry.workload(bench, args.workload)
    config = modelmod.load_config(registry.config_file(ROOT, bench, cell))
    mix = trafmod.load_mix(registry.traffic_file(HERE, cell))
    if args.rate is not None:
        mix["rate_rps"] = args.rate
    sys.path.insert(0, program_path())
    devices = require_chips(int(cell["chips"]))
    res, notes = run_cell(args, bench, cell, config, mix, devices)
    report.emit(res, notes)
    return 0


def run_cell(args, bench, cell, config, mix, devices):
    """One run on `devices`: set-up, lead-in, window, check; -> (result,
    notes)."""
    import dataclasses
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh_for
    from chipbench import devtrace, warmup, work

    m = modelmod.model_of(config)
    pcfg = dataclasses.replace(get_config(config["registry"]),
                               **modelmod.program_overrides(m))
    eng_cfg = config["engine"]
    lead_s = float(mix.get("lead_s", 0.0))
    phases = [lead_s, args.seconds, DRAIN_S]
    traffic = trafmod.Traffic(mix, m.vocab, args.seed)
    max_seq = warmup.max_seq_for(traffic, eng_cfg["block_size"])
    mesh = make_mesh_for(len(devices))
    shardings = warmup.param_shardings(pcfg, mesh, eng_cfg, max_seq)
    engine = build_engine(
        pcfg, modelmod.make_weights(m, args.seed, config["init"],
                                    out_shardings=shardings),
        mesh, eng_cfg, max_seq)
    make_task = make_task_fn(mix)
    warmed = warmup.warm(engine, mix, traffic.prefixes, eng_cfg, m.vocab,
                         phases, make_task)
    print(f"warmed {warmed} in {time.perf_counter() - T_START:.1f} s "
          f"from process start", flush=True)
    compiles = watch_compiles()
    profiler = (devtrace.Profiler(os.path.join(ROOT, ".chipbench_trace"))
                if args.trace else None)
    out = harness.drive(engine, traffic, make_task=make_task,
                        cap=int(eng_cfg["prefill_group_cap"]),
                        lead_s=lead_s, seconds=args.seconds, drain_s=DRAIN_S,
                        trace=(profiler, min(TRACE_S, args.seconds))
                        if profiler else None)
    setup_s = out.w0 - T_START
    mem_peak = peak_bytes(devices)
    batch = engine.B
    del engine
    gc.collect()

    window = None
    if profiler is not None:
        window = devtrace.reduce(devtrace.load(profiler.trace_file()),
                                 out.steps, m,
                                 work.peaks_for(devices[0].device_kind),
                                 len(devices))
    # the reference's weights are made anew from the seed, after the
    # program's state is gone: it takes nothing the program held
    weights = modelmod.make_weights(m, args.seed, config["init"])
    picked = check.sample(out.records, args.seed, MIN_TOKENS)
    reading = check.compare(weights, m, picked, control=bool(args.control))
    limit = float(config["correct"]["mean_logit_gap"])
    gap = reading["control_mean_gap" if args.control else "mean_gap"]
    checks = {"mean_logit_gap": {"value": gap, "limit": limit},
              "tokens_compared": {"value": reading["tokens"],
                                  "limit": MIN_TOKENS}}
    correct = gap <= limit and reading["tokens"] >= MIN_TOKENS
    notes = report.summary_lines(out, mix["loop"] == "open")
    late = [d for t, d in compiles if out.w0 - lead_s <= t <= out.t_end]
    notes.append(f"compiles after set-up: {len(late)} "
                 f"({sum(late):.2f} s)")
    notes.append(f"setup_s {setup_s:.3f} (lead-in {lead_s} s); memory "
                 f"peak {mem_peak / 2**30:.3f} GiB; check reading "
                 f"{json.dumps(reading)}")
    if window is not None:
        notes.append(f"trace: {json.dumps(window)}")
    res = report.result(
        bench=bench, cell=cell, trace=bool(args.trace), out=out,
        open_loop=mix["loop"] == "open", batch=batch, model=m,
        setup_s=setup_s, devices=devices, mem_peak=mem_peak, window=window,
        checks=checks, correct=correct, here=HERE)
    return res, notes


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(f"run.py: refused: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
