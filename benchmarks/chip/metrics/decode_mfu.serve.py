"""Model step: model operations of the decode tokens over the decode
programs' device time, as a share of the chips' bf16 peak."""
from chipbench.readers import trace_value


def read(run):
    return trace_value(run, "decode_mfu")
