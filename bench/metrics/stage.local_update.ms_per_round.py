"""Device milliseconds per simulated round under the program's
``fl.local_update`` scope: the local update: the vmapped local SGD of
every client of a block and its loss. The own time of every operation
whose innermost ``fl.*`` scope is ``fl.local_update``, over the rounds
of the traced window's calls (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.local_update")
