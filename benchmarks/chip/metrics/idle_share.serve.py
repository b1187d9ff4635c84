"""Device: share of the engine's step time in which no op ran on the chip."""
from chipbench.readers import trace_value


def read(run):
    return trace_value(run, "idle_share")
