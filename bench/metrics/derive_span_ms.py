"""Device time per round inside the fused round's ``safl.derive`` scope:
the derivation of the round's sketch operator (hashes and signs) (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "safl.derive")
