"""Device time per round inside the fused round's ``safl.client`` scope:
the clients' K local SGD steps (forward, backward, x_0 - x_K) (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "safl.client")
