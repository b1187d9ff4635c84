"""Engine loop: host time per engine step in the window, the seconds of
each step outside its token fetches (EngineStats host_s over
engine_steps, fed by the engine's stage spans)."""
from chipbench.enginespans import host_ms_per_step


def read(run):
    return host_ms_per_step(run.stats)
