"""The profiler trace of a traced run, and its reduction to numbers.

A traced run records the first seconds of its window with the JAX
profiler.  The trace viewer's export (plugins/profile/*/*.trace.json.gz)
holds, per TPU, an "XLA Modules" line (one event per program run: name
"jit_<fn>(<fingerprint>)") and an "XLA Ops" line (one event per HLO op,
with args `tf_op` (the jax scope path, e.g. ".../vmemk_chunk/..."),
`hlo_category` and `long_name`), all on the host's clock.  The harness's
own spans ("bench.step", "bench.submit", "bench.wait", the first with its
step number) sit in the host process on that clock.

How the serving path's pieces appear (phi4-mini-3.8b on a v5e):
  * every Pallas kernel is a "custom-call" op named after the kernel:
    the fused GEMMs `matmul` and `matmul_swiglu` (the logits head's
    `matmul` runs under scope `ce_f32`), `flash_attention`,
    `paged_decode_partials`;
  * the paged chunk attention of warm admissions has no kernel: its jnp
    ops run under scope `vmemk_chunk`;
  * the decode, prefill and chunk programs are all `jit_run(...)`: a
    program run is told apart by the ops it holds (a paged decode kernel,
    a flash attention kernel, ops under `vmemk_chunk`);
  * a scan over layers is a `while` op whose event spans its body's ops,
    so times are unions of intervals, never sums over nested events.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
import shutil

from . import work

GEMM_KERNELS = ("matmul", "matmul_swiglu")
DECODE_KERNEL = "paged_decode_partials"
PREFILL_KERNEL = "flash_attention"
CHUNK_SCOPE = "vmemk_chunk"
CONTAINERS = ("while", "conditional", "call")
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait")
TOP = 10


class Profiler:
    """Starts and stops the JAX profiler; annotates host spans."""

    def __init__(self, directory: str):
        self.dir = directory
        self.n = 0

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        # no Python tracer: it records every Python call, and the trace's
        # export keeps only its first million events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def span(self, name: str):
        import jax
        if name == "bench.step":
            self.n += 1
            return jax.profiler.TraceAnnotation(name, n=self.n - 1)
        return jax.profiler.TraceAnnotation(name)

    def trace_file(self) -> str:
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.trace.json.gz"))
        if not files:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return max(files, key=os.path.getmtime)


def base_name(op_name: str) -> str:
    """HLO instruction name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", op_name)


def op_label(op) -> str:
    """An op's name for the breakdown: its HLO name without the numeric
    suffix, and the last element of its jax scope path."""
    scope = op[3].rstrip(":").rsplit("/", 1)[-1]
    return f"{base_name(op[2])} {scope}".strip()


def load(path: str) -> dict:
    """-> {"devices": {device: {"ops": [...], "modules": [...]}},
           "host": [(name, start_us, end_us, step_n)]}
    with ops as (start_us, end_us, name, tf_op, category) and modules as
    (start_us, end_us, name)."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = collections.defaultdict(lambda: {"ops": [], "modules": []})
    host = []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        t0, t1 = float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0))
        if proc.startswith("/device:TPU"):
            line = threads.get((e["pid"], e["tid"]), "")
            a = e.get("args", {})
            if line == "XLA Ops":
                devices[proc]["ops"].append(
                    (t0, t1, e["name"], a.get("tf_op", ""),
                     a.get("hlo_category", "")))
            elif line == "XLA Modules":
                devices[proc]["modules"].append((t0, t1, e["name"]))
        elif e["name"] in HOST_SPANS:
            n = e.get("args", {}).get("n")
            host.append((e["name"], t0, t1, None if n is None else int(n)))
    for d in devices.values():
        d["ops"].sort()
        d["modules"].sort()
    host.sort(key=lambda h: h[1])
    return {"devices": dict(devices), "host": host}


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def overlap(merged_a, merged_b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(merged_a) and j < len(merged_b):
        a0, a1 = merged_a[i]
        b0, b1 = merged_b[j]
        lo, hi = max(a0, b0), min(a1, b1)
        if hi > lo:
            tot += hi - lo
        if a1 < b1:
            i += 1
        else:
            j += 1
    return tot


def _kind(ops_in_module) -> str:
    names = {base_name(o[2]) for o in ops_in_module}
    if DECODE_KERNEL in names:
        return "decode"
    if any(CHUNK_SCOPE in o[3] for o in ops_in_module):
        return "chunk"
    if PREFILL_KERNEL in names:
        return "prefill"
    return "other"


def _ops_within(ops, starts, lo, hi):
    """Ops (sorted, with their start times `starts`) inside [lo, hi]."""
    i, j = bisect.bisect_left(starts, lo), bisect.bisect_right(starts, hi)
    return [o for o in ops[i:j] if o[1] <= hi]


def reduce(trace: dict, steps, model, peaks, chips: int) -> dict:
    """Numbers of the traced window.  `steps` are the harness's StepLogs,
    the k-th matching the trace's "bench.step" span with n = k.  Times in
    the result are seconds; shares are percent, or None where the window
    held nothing to read."""
    host = trace["host"]
    if not host:
        raise ValueError("the trace holds none of the harness's spans")
    w_lo = min(h[1] for h in host)
    w_hi = max(h[2] for h in host)
    step_spans = {h[3]: (h[1], h[2]) for h in host
                  if h[0] == "bench.step" and h[3] is not None}
    work_iv = union(step_spans.values())
    dev_busy, dev_busy_work = [], []
    kind_time = collections.Counter()
    gemm_iv, chunk_iv, top = [], [], collections.Counter()
    flops = collections.Counter()
    gemm_bound = chunk_bound = 0.0
    busy0 = None
    for d, (dev, rec) in enumerate(sorted(trace["devices"].items())):
        busy = union(clip([(o[0], o[1]) for o in rec["ops"]], w_lo, w_hi))
        dev_busy.append(length(busy))
        dev_busy_work.append(overlap(busy, work_iv))
        if d == 0:
            busy0 = busy
        if d:
            continue
        # per-program attribution on the first chip (one replica a chip)
        starts = [o[0] for o in rec["ops"]]
        for m0, m1, _name in rec["modules"]:
            if any(s0 <= m0 and m1 <= s1 for s0, s1 in work_iv):
                ops = _ops_within(rec["ops"], starts, m0, m1)
                kind_time[_kind(ops)] += m1 - m0
        for o in rec["ops"]:
            if not w_lo <= o[0] < w_hi:
                continue
            name = base_name(o[2])
            if o[4] == "custom-call" and name in GEMM_KERNELS:
                gemm_iv.append((o[0], o[1]))
            if CHUNK_SCOPE in o[3]:
                chunk_iv.append((o[0], o[1]))
            if o[4] not in CONTAINERS:
                top[op_label(o)] += (o[1] - o[0])
    for n in step_spans:
        if n >= len(steps):
            continue
        st = steps[n]
        if st.decode:
            flops["decode"] += work.decode_flops(model, st.decode)
            t = len(st.decode)
            gemm_bound += work.bound_s(
                work.gemm_flops(model, t) + work.head_flops(model, t),
                work.gemm_bytes(model, t) + work.head_bytes(model, t), peaks)
        for spans in st.prefill.values():
            t = sum(b - a for a, b in spans)
            flops["prefill"] += sum(work.prefill_flops(model, a, b)
                                    for a, b in spans)
            rows = len(spans)
            gemm_bound += work.bound_s(
                work.gemm_flops(model, t) + work.head_flops(model, rows),
                work.gemm_bytes(model, t) + work.head_bytes(model, rows),
                peaks)
        for a, b in st.chunks:
            flops["chunk"] += work.prefill_flops(model, a, b)
            gemm_bound += work.bound_s(
                work.gemm_flops(model, b - a) + work.head_flops(model, 1),
                work.gemm_bytes(model, b - a) + work.head_bytes(model, 1),
                peaks)
            chunk_bound += work.bound_s(*work.chunk_attn_work(model, a, b),
                                        peaks)
    us = 1e-6
    peak = chips * peaks.bf16_flops

    def share(num, den_us):
        return 100.0 * num / (den_us * us * peak) if num > 0 and den_us > 0 \
            else None

    gemm_t = length(union(gemm_iv)) * us
    chunk_t = length(union(chunk_iv)) * us
    work_len = length(work_iv)
    gaps = _idle_gaps(busy0, host, w_lo, w_hi)
    return {
        "window_s": (w_hi - w_lo) * us,
        "busy_s": sum(dev_busy) / len(dev_busy) * us if dev_busy else 0.0,
        "idle_share": (100.0 * (1.0 - sum(dev_busy_work) / len(dev_busy_work)
                                / work_len) if work_len > 0 else None),
        "decode_mfu": share(flops["decode"], kind_time["decode"]),
        "prefill_mfu": share(flops["prefill"] + flops["chunk"],
                             kind_time["prefill"] + kind_time["chunk"]),
        "step_mfu": share(sum(flops.values()), overlap(busy0 or [], work_iv)),
        "gemm_roofline": (100.0 * gemm_bound / gemm_t
                          if gemm_t > 0 and gemm_bound > 0 else None),
        "chunk_attn_roofline": (100.0 * chunk_bound / chunk_t
                                if chunk_t > 0 and chunk_bound > 0 else None),
        "program_s": {k: v * us for k, v in kind_time.items()},
        "device_ops": [[k, v * us] for k, v in top.most_common(TOP)],
        "idle_gaps": gaps,
    }


def _idle_gaps(busy, host, lo, hi):
    """The longest idle gaps of the first chip inside [lo, hi), each named
    by the host span that covers its middle ("host" where none does)."""
    if busy is None:
        return []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
            if b > a]
    gaps.sort(reverse=True)
    out = []
    for dur, a, b in gaps[:TOP]:
        mid = 0.5 * (a + b)
        name = next((h[0] for h in host if h[1] <= mid < h[2]), "host")
        out.append([name, dur * 1e-6])
    return out
