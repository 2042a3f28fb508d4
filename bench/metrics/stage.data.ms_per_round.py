"""Device milliseconds per simulated round under the program's ``fl.data``
scope: on-device data: each block's batch generation. The own time of
every operation whose innermost ``fl.*`` scope is ``fl.data``, over the
rounds of the traced window's calls (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.data")
