"""Device milliseconds per simulated round under the program's
``fl.aggregate`` scope: aggregation: the canonical sums of the block
partials, their fold and the masked mean. The own time of every
operation whose innermost ``fl.*`` scope is ``fl.aggregate``, over the
rounds of the traced window's calls (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.aggregate")
