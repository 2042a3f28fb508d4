"""On-chip benchmark of the wireless-FL simulator (see ``bench/run.py``)."""
