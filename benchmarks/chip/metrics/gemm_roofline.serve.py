"""Kernels: the fused GEMM kernels' roofline bound for their work, over
their device time."""
from chipbench.readers import trace_value


def read(run):
    return trace_value(run, "gemm_roofline")
