"""Device time per round inside the fused round's ``safl.server_opt`` scope:
the server's ADA_OPT step (AMSGrad) (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "safl.server_opt")
