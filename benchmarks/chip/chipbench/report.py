"""The run's result: its metrics, its device, and what the check compared.

Earlier stdout lines are free-form (medians, counts, generator lag, the
check's readings); the last stdout line is the result's JSON object, and
the last stderr lines are the compared numbers with their limits.
"""
from __future__ import annotations

import json
import sys
import types

from . import registry, tails


def end_to_end(name: str, out, setup_s: float):
    """An end-to-end metric of the run.  The quantity is named before the
    first '.'; a suffix names the cells that hold it under a bound of
    their own (`itl_p95_ms.prefix` is `itl_p95_ms`)."""
    recs = out.records
    quantity = name.split(".", 1)[0]
    if quantity == "setup_s":
        return setup_s
    if quantity == "itl_p95_ms":
        return tails.p95_ms(tails.itl_samples(recs, out.w0, out.w1))
    if quantity == "output_tok_s":
        return tails.output_tok_s(recs, out.w0, out.w1)
    raise KeyError(f"no end-to-end metric {name!r} in this harness")


def counts(out, open_loop: bool):
    """(attempted, failed): requests due in the window, and those of them
    that an open loop never answered (a closed loop's requests still in
    flight at the close are in progress, not failed)."""
    due = [r for r in out.records if out.w0 <= r.due < out.w1]
    failed = sum(1 for r in due if not r.times) if open_loop else 0
    return len(due), failed


def result(*, bench, cell, trace: bool, out, open_loop: bool, batch: int,
           model, setup_s: float, devices, mem_peak: int, window,
           checks: dict, correct: bool, here: str) -> dict:
    metrics = {}
    if trace:
        run = types.SimpleNamespace(
            records=out.records, w0=out.w0, w1=out.w1, stats=out.stats,
            window=window, batch=batch, model=model)
        for m in registry.metrics_for(bench, cell, "per_layer"):
            v = registry.reader(here, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in registry.metrics_for(bench, cell, "end_to_end"):
            v = end_to_end(m["name"], out, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted, failed = counts(out, open_loop)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(mem_peak)}
    res = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if window is not None:
        device["busy_s"] = window["busy_s"]
        device["window_s"] = window["window_s"]
        res["breakdown"] = {"device_ops": window["device_ops"],
                            "idle_gaps": window["idle_gaps"]}
    res["checks"] = checks
    return res


def summary_lines(out, open_loop: bool):
    """Medians and sample counts beside the tails, and how late the
    generator ran (submit lag: due time to the engine's submit)."""
    lines = []
    if open_loop:
        tt = tails.ttft_samples(out.records, out.w0, out.w1, out.t_end)
        if tt:
            lines.append(f"ttft: n={len(tt)} p50 "
                         f"{1e3 * tails.percentile(tt, 50):.1f} ms p95 "
                         f"{1e3 * tails.percentile(tt, 95):.1f} ms")
    itl = tails.itl_samples(out.records, out.w0, out.w1)
    if itl:
        lines.append(f"itl: n={len(itl)} p50 "
                     f"{1e3 * tails.percentile(itl, 50):.1f} ms p95 "
                     f"{1e3 * tails.percentile(itl, 95):.1f} ms")
    lines.append(f"tokens in window: "
                 f"{tails.tokens_in(out.records, out.w0, out.w1)}")
    slow = sorted((r for r in out.records if out.w0 <= r.due < out.w1),
                  key=lambda r: -((r.times[0] if r.times else out.t_end)
                                  - r.due))[:5]
    if open_loop and slow:
        lines.append("slowest first tokens (ttft ms, prompt, backlog ms): "
                     + ", ".join(
                         f"({1e3 * ((r.times[0] if r.times else out.t_end) - r.due):.0f}, "
                         f"{len(r.req.prompt)}, "
                         f"{1e3 * ((r.submitted or out.t_end) - r.due):.0f})"
                         for r in slow))
    lag = [r.submitted - r.due for r in out.records
           if r.submitted is not None and out.w0 <= r.due < out.w1]
    if lag:
        lines.append(f"submit lag: n={len(lag)} p50 "
                     f"{1e3 * tails.percentile(lag, 50):.2f} ms p95 "
                     f"{1e3 * tails.percentile(lag, 95):.2f} ms max "
                     f"{1e3 * max(lag):.2f} ms")
    return lines


def emit(res: dict, notes) -> None:
    for line in notes:
        print(line, flush=True)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
