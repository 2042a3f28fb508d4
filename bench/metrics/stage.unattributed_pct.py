"""Share of the traced window's busy device time, in %, that no ``fl.*``
scope of the program names: operations outside every round stage (the
round loop, the round keys, the stacking of the per-round logs) and
outside the engine's module (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    st = stages.of(ctx)
    if st is None or st.stage_seconds is None or st.busy_s <= 0:
        return None
    return 100.0 * st.unattributed_s / st.busy_s
