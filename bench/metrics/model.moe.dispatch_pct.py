"""Share of the mixture-of-experts layers' device time spent outside the
grouped products, in %: routing, the sort and gather of the routed rows and
the gated scatter back (``model.moe.route`` and ``model.moe.combine``) over
all of ``model.moe`` (``bench/model_scopes.py``)."""
from bench import model_scopes


def read(ctx):
    moe = model_scopes.ms_per_round(ctx, "model.moe")
    if not moe:
        return None
    experts = model_scopes.ms_per_round(ctx, model_scopes.EXPERTS) or 0.0
    return 100.0 * (moe - experts) / moe
