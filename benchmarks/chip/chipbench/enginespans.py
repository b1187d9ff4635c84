"""The serving engine's stage spans in a traced run, and what they tell.

The program opens one `jax.profiler.TraceAnnotation` per stage of an
engine step, all named `engine.<stage>` (`engine.step`, `engine.admit`,
`engine.prefill`, `engine.decode.dispatch`, `engine.decode.fetch`, ...),
nested as the work nests.  In the traced run's trace they sit in the host
process on the clock of the harness's `bench.*` spans and the chip's ops,
with their args as strings.  A program without them leaves every reading
here empty (None), never an error.

  decode_attn_roofline   the paged decode kernel's share of its roofline:
      the least time the chip needs for the decode attention of the
      window's steps (from each `engine.decode.dispatch` span's
      `kv_positions` and `slots`), over the union of the kernel's device
      intervals in the window.
  host_ms_per_step       the engine's host time per step in the window,
      from its `engine_steps` and `host_s` counters (the seconds of each
      step outside the `*.fetch` spans).
  idle_by_stage          the chip's idle time inside `bench.step` spans,
      credited to the innermost engine span that covers it.

    python3 -m chipbench.enginespans <trace.json.gz>

prints the idle time by stage and the host time of the traced steps.
"""
from __future__ import annotations

import collections
import gzip
import json
import os
import sys

from . import devtrace, work

PREFIX = "engine."
STEP = "engine.step"
DISPATCH = "engine.decode.dispatch"
FETCH = ".fetch"
# the harness's trace directory (run.py), at the root of the checkout
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))), ".chipbench_trace")


def _number(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return v


def load_spans(path: str):
    """The engine's spans in a trace file: [(name, start_us, end_us,
    args)], sorted by start, outer spans first; numeric args as floats."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    spans = []
    for e in events:
        name = e.get("name", "")
        if e.get("ph") != "X" or not name.startswith(PREFIX):
            continue
        t0 = float(e["ts"])
        t1 = t0 + float(e.get("dur", 0.0))
        args = {k: _number(v) for k, v in (e.get("args") or {}).items()}
        spans.append((name, t0, t1, args))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return spans


def load(path: str) -> dict:
    """devtrace.load's reading of the file, with the engine's spans under
    "engine"."""
    trace = devtrace.load(path)
    trace["engine"] = load_spans(path)
    return trace


def window(trace):
    """(start, end) of the traced window: the extent of the harness's
    spans, as devtrace.reduce takes it."""
    host = trace["host"]
    if not host:
        raise ValueError("the trace holds none of the harness's spans")
    return min(h[1] for h in host), max(h[2] for h in host)


def decode_attn_work(model, kv_positions: float, slots: float):
    """(operations, bytes) that the decode attention of steps needs, whose
    decoding slots (`slots` in all) attend `kv_positions` cached positions
    in all: scores and values of every layer, each position's K and V read
    once, each slot's query and output once."""
    flops = 4.0 * kv_positions * model.n_heads * model.head_dim
    kv = 2.0 * kv_positions * model.n_kv_heads * model.head_dim
    qo = 2.0 * slots * model.n_heads * model.head_dim
    return flops * model.n_layers, work.BF16 * (kv + qo) * model.n_layers


def decode_attn_roofline(trace, model, peaks):
    """The paged decode kernel's share of its roofline in the window, in
    percent; None where the window holds no dispatch span or no kernel."""
    lo, hi = window(trace)
    kv = slots = 0.0
    for name, t0, _t1, args in trace["engine"]:
        if name == DISPATCH and lo <= t0 < hi:
            kv += float(args.get("kv_positions", 0.0))
            slots += float(args.get("slots", 0.0))
    devices = sorted(trace["devices"])
    if kv <= 0 or not devices:
        return None
    ops = trace["devices"][devices[0]]["ops"]
    kernel = devtrace.union(
        (o[0], o[1]) for o in ops
        if lo <= o[0] < hi and o[4] == "custom-call"
        and devtrace.base_name(o[2]) == devtrace.DECODE_KERNEL)
    t = devtrace.length(kernel) * 1e-6
    if t <= 0:
        return None
    # the work of all chips over the time of the first: a chip holds its
    # share of the heads
    bound = work.bound_s(*decode_attn_work(model, kv, slots), peaks)
    return 100.0 * bound / len(devices) / t


def _minus(merged_a, merged_b):
    """Merged intervals of a not covered by b (both merged)."""
    out = []
    j = 0
    for a0, a1 in merged_a:
        cur = a0
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < a1:
            b0, b1 = merged_b[k]
            if b0 > cur:
                out.append((cur, b0))
            cur = max(cur, b1)
            k += 1
        if cur < a1:
            out.append((cur, a1))
    return out


def idle_by_stage(trace) -> dict:
    """The first chip's idle time inside the harness's `bench.step` spans,
    in seconds: "idle_s" in all, "covered_s" of it inside some engine
    span, and "stages", each piece credited to the innermost engine span
    covering it (a piece that none covers goes to engine.step)."""
    lo, hi = window(trace)
    steps = devtrace.union((h[1], h[2]) for h in trace["host"]
                           if h[0] == "bench.step")
    devices = sorted(trace["devices"])
    busy = devtrace.union(devtrace.clip(
        [(o[0], o[1]) for o in trace["devices"][devices[0]]["ops"]],
        lo, hi)) if devices else []
    idle = _minus(steps, busy)
    spans = trace["engine"]
    # cut the idle intervals at every span boundary, then sweep the pieces
    # in time order with the stack of spans open at each
    cuts = sorted({x for _n, t0, t1, _a in spans for x in (t0, t1)})
    stages = collections.Counter()
    covered = 0.0
    stack = []
    i = c = 0
    for a, b in idle:
        while c < len(cuts) and cuts[c] <= a:
            c += 1
        edges = [a]
        while c < len(cuts) and cuts[c] < b:
            edges.append(cuts[c])
            c += 1
        edges.append(b)
        for p0, p1 in zip(edges, edges[1:]):
            mid = 0.5 * (p0 + p1)
            while i < len(spans) and spans[i][1] <= mid:
                while stack and stack[-1][2] <= spans[i][1]:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1][2] <= mid:
                stack.pop()
            inner = next((s for s in reversed(stack) if s[2] > mid), None)
            stages[inner[0] if inner else STEP] += (p1 - p0) * 1e-6
            if inner:
                covered += (p1 - p0) * 1e-6
    return {"idle_s": devtrace.length(idle) * 1e-6, "covered_s": covered,
            "stages": dict(stages)}


def host_time(trace) -> dict:
    """The traced window's engine steps: their count, and their seconds in
    all and outside the `*.fetch` spans."""
    lo, hi = window(trace)
    step_s = fetch_s = 0.0
    n = 0
    for name, t0, t1, _a in trace["engine"]:
        if not lo <= t0 < hi:
            continue
        if name == STEP:
            n += 1
            step_s += (t1 - t0) * 1e-6
        elif name.endswith(FETCH):
            fetch_s += (t1 - t0) * 1e-6
    return {"steps": n, "step_s": step_s, "host_s": step_s - fetch_s}


def host_ms_per_step(stats):
    """Milliseconds of host time per engine step over the run's window;
    None for a program whose stats lack the counters."""
    steps = getattr(stats, "engine_steps", 0)
    host_s = getattr(stats, "host_s", None)
    if not steps or host_s is None:
        return None
    return 1e3 * host_s / steps


def run_trace():
    """The traced run's trace, or None where there is none."""
    try:
        return load(devtrace.Profiler(TRACE_DIR).trace_file())
    except FileNotFoundError:
        return None


def decode_attn_roofline_of(run):
    """decode_attn_roofline of a traced run (metrics/ readers)."""
    if run.window is None:
        return None
    trace = run_trace()
    if trace is None or not any(s[0] == DISPATCH for s in trace["engine"]):
        return None
    import jax
    return decode_attn_roofline(
        trace, run.model, work.peaks_for(jax.devices()[0].device_kind))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m chipbench.enginespans <trace.json.gz>",
              file=sys.stderr)
        return 2
    trace = load(argv[0])
    idle = idle_by_stage(trace)
    host = host_time(trace)
    total = idle["idle_s"]
    print(f"idle inside bench.step: {1e3 * total:.1f} ms, "
          f"{100 * idle['covered_s'] / total if total else 0:.1f}% inside "
          f"engine spans")
    print(f"{'stage':<28} {'idle ms':>10} {'share':>7}")
    for name, s in sorted(idle["stages"].items(), key=lambda kv: -kv[1]):
        print(f"{name:<28} {1e3 * s:>10.1f} "
              f"{100 * s / total if total else 0:>6.1f}%")
    print(f"engine steps: {host['steps']}, {1e3 * host['step_s']:.1f} ms; "
          f"host (outside fetches) {1e3 * host['host_s']:.1f} ms, "
          f"{1e3 * host['host_s'] / host['steps'] if host['steps'] else 0:.2f}"
          f" ms a step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
