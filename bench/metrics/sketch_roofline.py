"""The sketch stage's share of its HBM roofline.

The least time the bytes need: each client's f32 delta read once and its
f32 payload written once (``bench/counts.py``), at the chip's HBM
bandwidth; over the device time ``sketch_ms`` measures.  The bound is
bytes: a count-sketch does one add per element read.
"""


def read(ctx):
    if not ctx.stages or not ctx.stages.get("sketch_stage") \
            or not ctx.sketch_bytes:
        return None
    least = ctx.sketch_bytes / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / ctx.stages["sketch_stage"]
