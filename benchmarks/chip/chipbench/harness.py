"""The measured loop: traffic in, engine steps, token receipts out.

One host thread drives the engine's synchronous loop.  Between two engine
steps it moves every request that has come due into a client-side backlog
and sends from the backlog while the engine's queue holds fewer than `cap`
requests.  The engine compiles one prefill program per (length bucket,
group size), and a group is drawn from its queue, so the cap bounds the
group sizes to those the set-up warmed: nothing compiles in the window.
A request waiting in the backlog is waiting for the server; every latency
is timed from the request's due time, so that wait counts.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import time
from typing import Callable, List, Optional

SLEEP_S = 0.0005        # idle poll while waiting for the next arrival
PROFILER_LEAD_S = 3.0   # the profiler starts this long before the window,
#                         so that its own start-up stalls the lead-in


@dataclasses.dataclass
class Record:
    req: object                     # traffic.Req
    due: float                      # host time it was due (closed: issued)
    task: object = None
    submitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class StepLog:
    """One engine step in the traced window, with the work it did: decode
    input positions, and (start, stop) spans of prompt positions encoded
    by a cold prefill (grouped by length bucket: one program per group)
    or by a warm suffix chunk."""
    start: float
    end: float
    decode: List[int] = dataclasses.field(default_factory=list)
    prefill: dict = dataclasses.field(default_factory=dict)
    chunks: List[tuple] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Outcome:
    records: List[Record]
    w0: float
    w1: float
    t_end: float
    stats: object                   # EngineStats over the window
    steps: List[StepLog]


def drive(engine, traffic, *, make_task: Callable, cap: int, lead_s: float,
          seconds: float, drain_s: float, trace=None,
          clock=time.perf_counter) -> Outcome:
    """Run the traffic: `lead_s` of lead-in, then the `seconds` window.
    An open loop then keeps serving, arrivals and all, until every request
    due in the window has its first token or `drain_s` has passed.
    `trace`, if given, is (profiler, seconds): the profiler records the
    first seconds of the window, with the loop's host spans annotated
    (it starts PROFILER_LEAD_S earlier; the spans start at the window)."""
    mix = traffic.mix
    open_loop = mix["loop"] == "open"
    t0 = clock()
    w0 = t0 + lead_s
    w1 = w0 + seconds
    records: List[Record] = []
    by_uid = {}
    backlog = collections.deque()
    if open_loop:
        schedule = collections.deque(
            traffic.open_requests([lead_s, seconds, drain_s]))
    else:
        for req in traffic.first_requests():
            backlog.append(_record(req, t0, records, by_uid))
    steps: List[StepLog] = []
    stats = None
    opened = False
    profiler, trace_s = trace if trace else (None, 0.0)
    profiling = tracing = False

    def span(name):
        return profiler.span(name) if tracing else _NULL

    while True:
        now = clock()
        if profiler is not None and not profiling and not opened \
                and now >= w0 - PROFILER_LEAD_S:
            profiler.start()
            profiling = True
        if not opened and now >= w0:
            engine.reset_stats()
            opened = True
            tracing = profiling
        if tracing and now >= w0 + trace_s:
            profiler.stop()
            tracing = profiling = False
        if now >= w1:
            if stats is None:
                stats = copy.deepcopy(engine.stats())
            if not open_loop or _window_served(records, w0, w1) \
                    or now >= w1 + drain_s:
                break
        if open_loop:
            while schedule and t0 + schedule[0].due_s <= now:
                req = schedule.popleft()
                backlog.append(_record(req, t0 + req.due_s, records,
                                       by_uid))
        with span("bench.submit"):
            while backlog and len(engine.queue) < cap:
                rec = backlog.popleft()
                rec.task = make_task(rec.req)
                rec.submitted = clock()
                engine.submit(rec.task)
        if not engine.has_work():
            nxt = (t0 + schedule[0].due_s) if open_loop and schedule \
                else now + SLEEP_S
            with span("bench.wait"):
                time.sleep(min(max(nxt - clock(), 0.0), SLEEP_S))
            continue
        log = StepLog(start=clock(), end=0.0) if tracing else None
        with span("bench.step"):
            events = engine.step()
        t = clock()
        for ev in events:
            rec = by_uid[ev.uid]
            if log is not None:
                _note_work(log, rec)
            rec.times.append(t)
            if ev.is_last:
                rec.done = True
                if not open_loop and t < w1:
                    backlog.append(_record(traffic.next_request(), t,
                                           records, by_uid))
        if log is not None:
            log.end = t
            steps.append(log)
    if profiling:
        profiler.stop()
    return Outcome(records=records, w0=w0, w1=w1, t_end=clock(),
                   stats=stats, steps=steps)


def _record(req, due, records, by_uid) -> Record:
    rec = Record(req=req, due=due)
    records.append(rec)
    by_uid[req.uid] = rec
    return rec


def _window_served(records, w0, w1) -> bool:
    return all(r.times for r in records if w0 <= r.due < w1)


def _note_work(log: StepLog, rec: Record) -> None:
    """Log the work behind one token event: output index 0 comes from the
    prompt's encoding (from its cached prefix on), later ones from decode
    steps whose input sat at prompt_len + index - 1."""
    task = rec.task
    i = len(rec.times)
    n = len(task.prompt)
    if i == 0:
        start = int(getattr(task, "cached_prefix", 0) or 0)
        if start > 0:
            log.chunks.append((start, n))
        else:
            log.prefill.setdefault(task.bucket, []).append((0, n))
    else:
        log.decode.append(n + i - 1)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
