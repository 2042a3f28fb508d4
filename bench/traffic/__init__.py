"""Traffic mixes: data files read by the one generator, ``gen.py``."""
