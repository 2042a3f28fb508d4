"""Device milliseconds per simulated round under the program's
``fl.compress`` scope: compression: flattening, the EF correction and
residual, the compressor (the top-k kernel included) and the uplink
bits. The own time of every operation whose innermost ``fl.*`` scope is
``fl.compress``, over the rounds of the traced window's calls
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.compress")
