"""The benchmark's FLOP and byte counts against counts made by hand."""

import json

import jax
import numpy as np
import pytest

import benchpath  # noqa: F401
from bench import counts
from bench.models import dense

ROOT = benchpath.ROOT
BERT = json.loads((ROOT / "bench/configs/bert-100m-nob2.json").read_text())
# h2o-danube-1.8b (arXiv:2401.16818) at 6 of its 24 layers
DANUBE = {"reference": "dense", "num_layers": 6, "d_model": 2560,
          "num_heads": 32, "num_kv_heads": 8, "d_ff": 6912,
          "vocab_size": 32000, "pad_vocab_to": 128, "norm_kind": "rms",
          "mlp_kind": "swiglu", "pos_kind": "rope", "dtype": "bfloat16"}
FED5 = json.loads((ROOT / "bench/traffic/fed5.local4.json").read_text())
SILO2 = {"clients": 2, "local_steps": 2, "seqs_per_step": 2, "seq_len": 4096}


def _d_and_b(cfg):
    leaves = jax.tree.leaves(dense.param_shapes(cfg),
                             is_leaf=lambda x: isinstance(x, tuple))
    ns = [int(np.prod(s)) for s in leaves]
    return sum(ns), sum(counts.sketch_size(n, 0.02, 64) for n in ns)


def test_bert_matmul_params_and_flops():
    # per layer 4 x 768^2 (attention) + 2 x 768 x 3072 (MLP); head 768 x 30592
    assert dense.matmul_params(BERT) == 12 * (4 * 768 ** 2
                                              + 2 * 768 * 3072) \
        + 768 * 30592 == 108_429_312
    per_token = 3 * (2 * 108_429_312 + 12 * 4 * 512 * 768)
    assert counts.tokens_per_round(FED5) == 40_960
    assert counts.flops_per_round(BERT, FED5) == per_token * 40_960
    assert counts.flops_per_round(BERT, FED5) == pytest.approx(2.897e13,
                                                               rel=1e-3)


def test_bert_flops_per_round_is_pinned():
    assert counts.flops_per_round(BERT, FED5) == 28_966_870_056_960


def test_danube_matmul_params_and_flops():
    attn = 2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560
    mlp = 3 * 2560 * 6912
    assert attn + mlp + 2 * 2560 == 69_473_280          # per layer, norms in
    assert dense.matmul_params(DANUBE) == 6 * (attn + mlp) + 2560 * 32000
    matmul = 3 * 2 * dense.matmul_params(DANUBE) * 32_768
    attention = 3 * 6 * 4 * 4096 * 2560 * 32_768
    assert counts.flops_per_round(DANUBE, SILO2) == matmul + attention
    assert matmul == pytest.approx(9.8e13, rel=0.01)
    assert attention == pytest.approx(2.5e13, rel=0.02)


def test_parameter_and_sketch_sizes():
    assert _d_and_b(BERT) == (132_008_448, 2_640_275)
    assert _d_and_b(DANUBE)[0] == 580_682_240


def test_sketch_bytes():
    d, b = _d_and_b(BERT)
    assert counts.sketch_bytes(5, d, b) == 4 * 5 * (d + b)
    # 2.64 GB of deltas plus 52.8 MB of payload
    assert counts.sketch_bytes(5, d, b) == pytest.approx(2.693e9, rel=1e-3)
