"""Kernels: paged decode attention's roofline bound (the KV positions
each decode step attends, from the engine's engine.decode.dispatch spans,
read once) over the device time of paged_decode_partials."""
from chipbench.enginespans import decode_attn_roofline_of as read  # noqa: F401
