"""Device milliseconds per simulated round under the program's
``fl.server_update`` scope: the server update: downlink EF compression
and the algorithm's server step. The own time of every operation whose
innermost ``fl.*`` scope is ``fl.server_update``, over the rounds of the
traced window's calls (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.server_update")
