"""The whole round's share of the chips' bf16 peak.

Model FLOPs per round (``bench/counts.py``: forward and backward, no
recompute) over (traced seconds per round x chips x bf16 peak).  bert-100m
runs in float32 and the v5e publishes no float32 peak, so against the bf16
peak this share is conservative.
"""


def read(ctx):
    per_round = ctx.fused["window_s"] / ctx.rounds
    return 100.0 * ctx.flops_per_round / (
        per_round * ctx.chips * ctx.peak["bf16_flops_per_s"])
