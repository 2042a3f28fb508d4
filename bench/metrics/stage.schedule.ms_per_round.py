"""Device milliseconds per simulated round under the program's
``fl.schedule`` scope: scheduling: the policy, the score-masked view,
the age update. The own time of every operation whose innermost ``fl.*``
scope is ``fl.schedule``, over the rounds of the traced window's calls
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.schedule")
