"""Runner: median decode step time in the window (serve cells)."""
from chipbench.readers import decode_step_p50_ms as read  # noqa: F401
