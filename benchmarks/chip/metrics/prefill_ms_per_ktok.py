"""Runner: host wall time of prefill and warm-suffix chunk steps per 1,000
prompt tokens they encoded in the window (EngineStats nar_time_s over
nar_tokens; both count first admissions only)."""


def read(run):
    st = run.stats
    return 1e6 * st.nar_time_s / st.nar_tokens if st.nar_tokens else None
