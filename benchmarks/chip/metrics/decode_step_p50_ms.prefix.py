"""Runner: median decode step time in the window (shared-prefix cells)."""
from chipbench.readers import decode_step_p50_ms as read  # noqa: F401
