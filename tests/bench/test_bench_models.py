"""Reference models found by a configuration's ``reference`` key: a new
family is taken from new files alone, and a file that names none is
refused."""

import json
import shutil
import sys
import time

import pytest

import benchpath  # noqa: F401
from bench import counts, harness, models, program
from bench.models import dense

ROOT = benchpath.ROOT
CELL = "bert100m-nob2.fed5.local4"
SEED = 2**31 + 4099

# a family of its own that runs the dense model: RMSNorm, SwiGLU and RoPE
# with grouped key/value heads and a sliding window, as the program's
# h2o-danube config does
WRAPPED = '''"""The dense reference under another name."""
from bench.models import dense

check_config = dense.check_config
param_shapes = dense.param_shapes
loss = dense.loss
flops_per_token = dense.flops_per_token
'''
CONFIG = {"name": "tiny-rms", "program_config": "h2o_danube_1_8b",
          "reference": "wrapped", "source": "a test", "reduced": [],
          "num_layers": 2, "d_model": 128, "num_heads": 4,
          "num_kv_heads": 2, "d_ff": 256, "vocab_size": 256,
          "pad_vocab_to": 128, "norm_kind": "rms", "norm_eps": 1e-5,
          "mlp_kind": "swiglu", "pos_kind": "rope", "rope_theta": 10000.0,
          "sliding_window": 16, "attn_bias": False, "tie_embeddings": False,
          "dtype": "float32"}
TRAFFIC = {"clients": 2, "local_steps": 2, "seqs_per_step": 2,
           "seq_len": 32, "zipf_s": 1.1, "heterogeneity": 0.3}


def _files(d):
    return {p.relative_to(d): p.read_bytes() for p in d.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_reference_family_is_taken_from_new_files_alone(tmp_path,
                                                              monkeypatch):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    added = {"bench/models/wrapped.py": WRAPPED,
             "bench/configs/tiny-rms.json": json.dumps(CONFIG),
             "bench/traffic/few.json": json.dumps(TRAFFIC),
             "bench/workloads/tiny-rms.few.json":
                 (ROOT / f"bench/workloads/{CELL}.json").read_text()}
    for path, text in added.items():
        (root / path).write_text(text)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-rms", "source": "a test",
                          "file": "bench/configs/tiny-rms.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-rms.few", "config": "tiny-rms",
                            "traffic": "few", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    # every file the benchmark had is as it was
    new, old = _files(root / "bench"), _files(ROOT / "bench")
    assert {f"bench/{p}" for p in set(new) - set(old)} == set(added)
    assert all(new[p] == old[p] for p in old if p.parts[0] != "out")

    monkeypatch.setattr(models, "__path__", [str(root / "bench/models")]
                        + list(models.__path__))
    monkeypatch.delitem(sys.modules, "bench.models.wrapped", raising=False)
    spec = harness.load_spec("tiny-rms.few", root=root)
    assert models.of(spec.config).__name__ == "bench.models.wrapped"
    assert counts.flops_per_round(spec.config, spec.traffic) == \
        dense.flops_per_token(CONFIG, 32) * counts.tokens_per_round(TRAFFIC)
    result = harness.run_cell(spec, SEED, 0.5, False, time.perf_counter(),
                              check_chips=False, out_dir=tmp_path / "out")
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("name", [None, "no_such_model"])
def test_a_config_without_a_known_reference_is_refused(name):
    spec = harness.load_spec(CELL)
    spec.config.pop("reference")
    if name:
        spec.config["reference"] = name
    with pytest.raises(ValueError, match="dense"):
        models.of(spec.config)
    with pytest.raises(ValueError, match="dense"):
        program.build(spec, SEED)
    with pytest.raises(ValueError, match="dense"):
        counts.flops_per_round(spec.config, spec.traffic)
