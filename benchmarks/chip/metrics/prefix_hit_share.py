"""Cache manager: share of the prompt tokens admitted in the window that
the prefix cache served (EngineStats cached_prefix_tokens over those plus
the prompt tokens encoded)."""


def read(run):
    st = run.stats
    admitted = st.cached_prefix_tokens + st.nar_tokens
    return 100.0 * st.cached_prefix_tokens / admitted if admitted else None
