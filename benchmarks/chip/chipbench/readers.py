"""What the per-layer readers (metrics/<name>.py) share."""
from __future__ import annotations

from .tails import percentile


def decode_step_p50_ms(run):
    """Median decode step of the window: host wall time around the step
    with its token fetch (EngineStats decode-step samples)."""
    s = list(run.stats.decode_step_ms)
    return percentile(s, 50) if s else None


def trace_value(run, key):
    """A number of the device trace's reduction (chipbench/devtrace.py);
    None in a run without a trace, or where the trace held nothing."""
    return None if run.window is None else run.window.get(key)
