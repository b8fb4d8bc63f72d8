"""Share of the traced window in which no operation ran on the device.

1 - (union of the device-op intervals) / window, over the fused round
program's steady chunks, averaged over the chips used.
"""


def read(ctx):
    f = ctx.fused
    return 100.0 * (1.0 - f["busy_s"] / f["window_s"])
