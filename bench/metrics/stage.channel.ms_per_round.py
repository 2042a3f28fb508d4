"""Device milliseconds per simulated round under the program's
``fl.channel`` scope: the channel: fading draw, SNR, rates, uplink and
downlink pricing and the round clock. The own time of every operation
whose innermost ``fl.*`` scope is ``fl.channel``, over the rounds of the
traced window's calls (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_ms_per_round(ctx, "fl.channel")
