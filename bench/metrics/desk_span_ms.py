"""Device time per round inside the fused round's ``safl.desk`` scope:
the desketch of the mean payload back to R^d (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "safl.desk")
