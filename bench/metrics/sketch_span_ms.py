"""Device time per round inside the fused round's ``safl.sketch`` scope:
the sketch of the cohort's deltas into the (G, b_total) payload (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "safl.sketch")
