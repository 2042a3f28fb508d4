"""Device milliseconds per simulated round in the client model's full
attention layers: the own time of every operation under
``model.attn.full`` in the traced window's calls, over their rounds
(``bench/model_scopes.py``)."""
from bench import model_scopes


def read(ctx):
    return model_scopes.ms_per_round(ctx, "model.attn.full")
