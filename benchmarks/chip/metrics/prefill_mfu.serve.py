"""Model step: model operations of the encoded prompt tokens over the
prefill and chunk programs' device time, as a share of the chips' bf16
peak."""
from chipbench.readers import trace_value


def read(run):
    return trace_value(run, "prefill_mfu")
