"""Device time of the sketch of the cohort's deltas, run alone."""


def read(ctx):
    if not ctx.stages or not ctx.stages.get("sketch_stage"):
        return None
    return 1e3 * ctx.stages["sketch_stage"]
