"""Device-idle milliseconds per entry call under the program's
``fl.fetch_logs`` host span (``run_simulation_scan`` copying the per-round
logs to the host and assembling them): the idle gaps of the traced window
whose midpoint lies in that span, over the window's calls
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    st = stages.of(ctx)
    if st is None or st.span_gaps is None or st.calls == 0:
        return None
    return st.span_gaps.get("fl.fetch_logs", 0.0) / st.calls * 1e3
