"""Device time of the client local steps, run alone (``bench/stages.py``)."""


def read(ctx):
    if not ctx.stages or not ctx.stages.get("client_stage"):
        return None
    return 1e3 * ctx.stages["client_stage"]
