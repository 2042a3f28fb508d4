"""Device milliseconds per simulated round under the program's
``fl.client_state`` scope: per-client state: each block's read and
write-back of its EF rows. The own time of every operation whose
innermost ``fl.*`` scope is ``fl.client_state``, over the rounds of the
traced window's calls (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.client_state")
