"""Operations and bytes each stage of a SAFL round needs, from shapes alone.

These are the algorithm's counts, not the program's: a later change to how
a stage is implemented does not move them.

* Model FLOPs per token, counted by the configuration's reference module
  (``flops_per_token`` in ``bench/models``) by these rules: forward plus
  backward (3x forward), every matmul with a weight counted at 2 FLOP per
  multiply-add, attention scores and the probability-value product
  counted in full over the sequence (no causal halving), recomputation not
  counted.  The embedding gather is not a matmul and is not counted; the
  output head is, over the padded vocabulary the configuration states.
* Sketch bytes: each client's f32 delta read once (4 G d) and its f32
  payload written once (4 G b_total).
"""

from __future__ import annotations

import math

from bench import models


def tokens_per_round(traffic: dict) -> int:
    return (traffic["clients"] * traffic["local_steps"]
            * traffic["seqs_per_step"] * traffic["seq_len"])


def flops_per_round(cfg: dict, traffic: dict) -> float:
    return (models.of(cfg).flops_per_token(cfg, traffic["seq_len"])
            * tokens_per_round(traffic))


def sketch_size(n: int, ratio: float, min_b: int) -> int:
    """Per-tensor sketch width: max(min_b, ceil(n ratio)), at most n."""
    return min(max(min_b, int(math.ceil(n * ratio))), n)


def sketch_bytes(clients: int, d: int, b_total: int) -> int:
    return 4 * clients * d + 4 * clients * b_total
