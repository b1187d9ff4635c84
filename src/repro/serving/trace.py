"""Engine stage spans on the profiler's clock, and request-level tracing.

Two recorders share one set of hook sites in engine.py and runner.py:

  stage spans     `with span(name, tracer, **args):` around every stage of
      an engine step.  Each opens a `jax.profiler.TraceAnnotation`, which
      records only while a profiler session runs (`jax.profiler.trace`)
      and then sits on the same clock as the device's ops; otherwise it
      costs about a microsecond.  The span also keeps its own
      `perf_counter` readings, from which the engine feeds
      `EngineStats.engine_steps` and `EngineStats.host_s` (the seconds
      of each step spent outside the `*.fetch` spans).  Names are the
      constants below, all under `engine.`, nested as the work nests:

        engine.step                     one InferenceEngine.step()
          engine.encode / .fetch        an encode batch / its host copy
          engine.admit                  admission and retirement loop
            engine.prefill / .fetch     a prefill group / its token fetch
            engine.chunk / .fetch       a warm-suffix or chunked piece
            engine.retire               release of finished slots
          engine.blocks                 decode blocks: allocation, COW,
                                        preemption
          engine.decode.dispatch        input uploads and the program call
          engine.decode.fetch           the blocking token fetch
          engine.decode.commit          per-slot bookkeeping
          engine.spec.draft / .fetch    speculation's draft proposals
          engine.spec.verify / .fetch   its verify pass and commit

      `engine.decode.dispatch` carries `kv_positions`, the KV positions
      the step's attention reads summed over its decoding slots, so that
      a profiler trace holds the decode attention's work beside its
      kernel time.  Prefill, chunk and decode spans carry `uids`.

  ring Tracer     (opt-in, `serve.py --trace-out`) a bounded ring buffer
      exported as Chrome trace-event JSON for https://ui.perfetto.dev:
      with an enabled Tracer every stage span is also pushed to the
      engine row (pid 1), and the request rows (pid 2, tid = uid) hold
      each request's lifecycle — submit -> queue -> (warm_hit | degrade
      | preempt | shed) -> first_token -> retire ("request" span) — with
      the scheduler's annotations.  Request-row hooks sit behind one
      `if tracer:` branch.

Both are pure observers: tokens are identical with or without a profiler
session or a Tracer.  `prometheus_text` renders an `EngineStats.to_dict()`
snapshot in Prometheus text format.

CLI validator for a Tracer artifact:

    PYTHONPATH=src python -m repro.serving.trace TRACE.json
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Dict, List

from jax.profiler import TraceAnnotation

# Chrome trace-event process rows: one for engine steps, one per-request.
PID_ENGINE = 1
PID_REQUEST = 2

# Stage span names (module docstring).  A name ending in FETCH marks time
# the host spends blocked on the chip's results.
FETCH = ".fetch"
ENGINE_STEP = "engine.step"
ADMIT = "engine.admit"
RETIRE = "engine.retire"
PREFILL = "engine.prefill"
PREFILL_FETCH = PREFILL + FETCH
CHUNK = "engine.chunk"
CHUNK_FETCH = CHUNK + FETCH
BLOCKS = "engine.blocks"
DECODE_DISPATCH = "engine.decode.dispatch"
DECODE_FETCH = "engine.decode" + FETCH
DECODE_COMMIT = "engine.decode.commit"
SPEC_DRAFT = "engine.spec.draft"
SPEC_DRAFT_FETCH = SPEC_DRAFT + FETCH
SPEC_VERIFY = "engine.spec.verify"
SPEC_VERIFY_FETCH = SPEC_VERIFY + FETCH
ENCODE = "engine.encode"
ENCODE_FETCH = ENCODE + FETCH


class Span:
    """One stage of an engine step (see `span`).  `t0`/`t1` are its
    `perf_counter` readings; `set` adds args known only once the stage's
    work is done."""

    __slots__ = ("name", "tracer", "args", "t0", "t1", "_ann")

    def __init__(self, name: str, tracer, args: dict):
        self.name = name
        self.tracer = tracer
        self.args = args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set(self, **args):
        self._ann.set_metadata(**args)
        self.args.update(args)

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self.tracer:
            self.tracer.step_span(self.name, self.t0, self.t1, **self.args)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str, tracer=None, **args) -> Span:
    """A stage span: a profiler annotation named `name` with `args`, and,
    with an enabled ring `tracer`, the same event on its engine row."""
    return Span(name, tracer, args)


class Tracer:
    """Bounded ring-buffer tracer for one engine instance.

    `capacity` bounds the buffer (drop-oldest beyond it; `dropped` counts
    the evictions so a truncated artifact is never mistaken for a complete
    one).  Timestamps are `time.perf_counter()` values converted to
    microseconds relative to the tracer's construction epoch — the same
    clock every EngineStats latency uses, so trace-derived TTFT/TPOT
    reconstruct the stats to within float rounding.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        assert capacity >= 1, capacity
        self.capacity = capacity
        self.enabled = enabled
        self.epoch = time.perf_counter()
        self._ring: deque = deque(maxlen=capacity)
        self.dropped = 0

    def __bool__(self) -> bool:
        # a disabled tracer (or None) records nothing: request-row hooks
        # are `if tracer: ...`, and a stage span pushes only `if tracer`
        return self.enabled

    @property
    def events(self) -> List[dict]:
        return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)

    def clear(self):
        self._ring.clear()
        self.dropped = 0

    # -- recording ------------------------------------------------------
    def _ts(self, t: float) -> float:
        return (t - self.epoch) * 1e6            # µs, Chrome's unit

    def _push(self, ev: dict):
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(ev)

    def span(self, name: str, t0: float, t1: float, *, pid: int, tid: int,
             cat: str, **args):
        """One complete ('X') event: [t0, t1] perf_counter seconds."""
        self._push({"name": name, "ph": "X", "cat": cat,
                    "ts": self._ts(t0), "dur": max(0.0, (t1 - t0) * 1e6),
                    "pid": pid, "tid": tid, "args": args})

    def step_span(self, name: str, t0: float, t1: float, **args):
        """Engine-row span (pid 1, tid 0): one stage span."""
        self.span(name, t0, t1, pid=PID_ENGINE, tid=0, cat="step", **args)

    def request_span(self, uid: int, name: str, t0: float, t1: float,
                     **args):
        """Request-row span (pid 2, tid = uid): queue / shed / request."""
        self.span(name, t0, t1, pid=PID_REQUEST, tid=int(uid),
                  cat="request", **args)

    def instant(self, name: str, t: float, *, tid: int,
                pid: int = PID_REQUEST, **args):
        """One instant ('i') event — submit, first_token, warm_hit,
        cow_copy, degrade, preempt markers."""
        self._push({"name": name, "ph": "i", "cat": "mark", "s": "t",
                    "ts": self._ts(t), "pid": pid, "tid": int(tid),
                    "args": args})

    # -- export ---------------------------------------------------------
    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable).  Events are
        sorted by timestamp; metadata events name the process/thread rows."""
        evs = sorted(self._ring, key=lambda e: e["ts"])
        meta = [
            {"name": "process_name", "ph": "M", "pid": PID_ENGINE, "tid": 0,
             "args": {"name": "engine"}},
            {"name": "thread_name", "ph": "M", "pid": PID_ENGINE, "tid": 0,
             "args": {"name": "steps"}},
            {"name": "process_name", "ph": "M", "pid": PID_REQUEST,
             "tid": 0, "args": {"name": "requests"}},
        ]
        for tid in sorted({e["tid"] for e in evs
                           if e["pid"] == PID_REQUEST}):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": PID_REQUEST, "tid": tid,
                         "args": {"name": f"req {tid}"}})
        return {"traceEvents": meta + evs, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "capacity": self.capacity}}

    def write(self, path: str) -> int:
        """Write the Chrome trace JSON; returns the event count."""
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f, default=_jsonable)
        return len(self._ring)


def _jsonable(x):
    """json.dump fallback for numpy scalars riding in span args."""
    if hasattr(x, "item"):
        return x.item()
    return str(x)


# -- validation (the CI artifact gate) ----------------------------------
_REQUIRED = ("name", "ph", "ts", "pid", "tid")


def validate_chrome_trace(doc: dict) -> List[str]:
    """Schema-check a Chrome trace-event document: non-empty, required
    fields present and numeric where they must be, 'X' events carry a
    non-negative dur, and non-metadata timestamps are monotonic (the
    export sorts; an unsorted artifact means a broken writer).  Returns
    a list of problems (empty = clean)."""
    problems = []
    evs = doc.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    body = [e for e in evs if e.get("ph") != "M"]
    if not body:
        problems.append("no non-metadata events")
    last_ts = None
    for i, e in enumerate(body):
        for k in _REQUIRED:
            if k not in e:
                problems.append(f"event {i} ({e.get('name')!r}): "
                                f"missing field {k!r}")
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i} ({e.get('name')!r}): "
                            f"non-numeric ts {ts!r}")
            continue
        if e.get("ph") == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i} ({e.get('name')!r}): "
                                f"'X' event needs dur >= 0, got {dur!r}")
        if last_ts is not None and ts < last_ts:
            problems.append(f"event {i} ({e.get('name')!r}): timestamp "
                            f"{ts} < predecessor {last_ts} "
                            f"(not monotonic)")
        last_ts = ts
        if len(problems) >= 20:
            problems.append("... (truncated)")
            break
    return problems


# -- flat Prometheus-style text snapshot --------------------------------
def prometheus_text(snapshot: dict, prefix: str = "serving") -> str:
    """Flatten an `EngineStats.to_dict()` into Prometheus text exposition
    format: scalars become `<prefix>_<key> <value>`, one-level dicts
    become labeled series (`bucket_hits` -> {bucket="8"}), and string
    fields collapse into one `<prefix>_info{...} 1` metric."""
    lines: List[str] = []
    info: Dict[str, str] = {}
    for key, val in snapshot.items():
        if isinstance(val, bool):
            lines.append(f"{prefix}_{key} {int(val)}")
        elif isinstance(val, (int, float)):
            lines.append(f"{prefix}_{key} {val:g}")
        elif isinstance(val, str):
            info[key] = val
        elif isinstance(val, dict):
            label = key.rstrip("s") or key
            for k, v in val.items():
                if isinstance(v, (int, float)):
                    lines.append(f'{prefix}_{key}{{{label}="{k}"}} {v:g}')
    if info:
        labels = ",".join(f'{k}="{v}"' for k, v in sorted(info.items()))
        lines.append(f"{prefix}_info{{{labels}}} 1")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    """Validate a trace artifact: non-empty, schema-clean, monotonic
    timestamps.  Exit 1 (with the problems on stderr) otherwise."""
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        description="validate a serving trace artifact (Chrome trace JSON)")
    ap.add_argument("trace", help="path to a Tracer.write() artifact")
    args = ap.parse_args(argv)
    with open(args.trace) as f:
        doc = json.load(f)
    problems = validate_chrome_trace(doc)
    evs = [e for e in doc.get("traceEvents", []) if e.get("ph") != "M"]
    reqs = {e["tid"] for e in evs if e.get("pid") == PID_REQUEST}
    span = (max((e["ts"] + e.get("dur", 0) for e in evs), default=0)
            - min((e["ts"] for e in evs), default=0))
    print(f"{args.trace}: {len(evs)} events, {len(reqs)} request rows, "
          f"{span / 1e3:.1f}ms span, "
          f"{doc.get('otherData', {}).get('dropped_events', 0)} dropped")
    if problems:
        for p in problems:
            print(f"  INVALID: {p}", file=sys.stderr)
        return 1
    print("  schema clean, timestamps monotonic")
    return 0


__all__ = ["Tracer", "Span", "span", "PID_ENGINE", "PID_REQUEST",
           "validate_chrome_trace", "prometheus_text"]

if __name__ == "__main__":
    import sys
    sys.exit(main())
