"""Scheduler: share of decode slots that held a request, over the decode
steps of the window (EngineStats occupied_slot_steps)."""


def read(run):
    st = run.stats
    if not st.decode_steps:
        return None
    return 100.0 * st.occupied_slot_steps / (st.decode_steps * run.batch)
