"""Operations and bytes each stage of a SAFL round needs, from shapes alone.

These are the algorithm's counts, not the program's: a later change to how
a stage is implemented does not move them.

* Model FLOPs per token: forward plus backward (3x forward), every matmul
  with a weight counted at 2 FLOP per multiply-add, attention scores and
  the probability-value product counted in full over the sequence (no
  causal halving), recomputation not counted.  The embedding gather is not
  a matmul and is not counted; the output head is, over the padded
  vocabulary the configuration states.
* Sketch bytes: each client's f32 delta read once (4 G d) and its f32
  payload written once (4 G b_total).
"""

from __future__ import annotations

import math


def padded_vocab(cfg: dict) -> int:
    m = cfg.get("pad_vocab_to", 1) or 1
    return -(-cfg["vocab_size"] // m) * m


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul, per model (embedding excluded)."""
    D, H, Hk, hd, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       head_dim(cfg), cfg["d_ff"])
    attn = D * H * hd + 2 * D * Hk * hd + H * hd * D
    mlp = (2 if cfg["mlp_kind"] == "gelu" else 3) * D * F
    return cfg["num_layers"] * (attn + mlp) + D * padded_vocab(cfg)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token."""
    attn = cfg["num_layers"] * 4 * seq_len * cfg["num_heads"] * head_dim(cfg)
    return 3.0 * (2 * matmul_params(cfg) + attn)


def tokens_per_round(traffic: dict) -> int:
    return (traffic["clients"] * traffic["local_steps"]
            * traffic["seqs_per_step"] * traffic["seq_len"])


def flops_per_round(cfg: dict, traffic: dict) -> float:
    return flops_per_token(cfg, traffic["seq_len"]) * tokens_per_round(traffic)


def sketch_size(n: int, ratio: float, min_b: int) -> int:
    """Per-tensor sketch width: max(min_b, ceil(n ratio)), at most n."""
    return min(max(min_b, int(math.ceil(n * ratio))), n)


def sketch_bytes(clients: int, d: int, b_total: int) -> int:
    return 4 * clients * d + 4 * clients * b_total
