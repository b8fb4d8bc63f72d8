"""Device time of cohort mean, desketch and the AMSGrad step, run alone."""


def read(ctx):
    if not ctx.stages or not ctx.stages.get("server_stage"):
        return None
    return 1e3 * ctx.stages["server_stage"]
