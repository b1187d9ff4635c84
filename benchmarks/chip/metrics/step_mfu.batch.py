"""Model step: model operations of every token the step programs processed
over the device's busy time in the engine's steps, as a share of the
chips' bf16 peak."""
from chipbench.readers import trace_value


def read(run):
    return trace_value(run, "step_mfu")
