"""chip_smoke.py's phases on the CPU at bert-100m's SMOKE size.

The script itself refuses any platform but a TPU; its phase functions take
the expected platform, so the same code runs here, small, with the kernels
in interpret mode.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402

SMOKE = get_config("bert_100m", smoke=True)


def test_main_refuses_a_platform_other_than_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "cpu" in err and "TPU" in err
    assert "{" not in out           # no result line


def test_train_phase_at_smoke_size():
    res = chip_smoke.run_train(SMOKE, "cpu", data_vocab=64, seq_len=32,
                               batch_per_client=4)
    assert res["d"] > 0 and 0 < res["b_total"] < res["d"]
    assert len(res["losses"]) == 4 and np.isfinite(res["losses"]).all()


def test_kernel_phase_interpreted():
    errs = chip_smoke.run_kernels("cpu", 200_000, 4_000, fwht_shape=(8, 256),
                                  gauss_n=2048, gauss_b=128)
    assert set(errs) == {"countsketch_clients", "fwht_rows", "gaussian_sk",
                         "gaussian_desk"}

