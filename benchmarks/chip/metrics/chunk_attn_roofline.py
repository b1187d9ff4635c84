"""Kernels: paged chunk attention's roofline bound (query chunk over the
cached K/V read once), over the device time of its ops (scope
vmemk_chunk)."""
from chipbench.readers import trace_value


def read(run):
    return trace_value(run, "chunk_attn_roofline")
