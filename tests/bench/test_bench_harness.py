"""The harness at a tiny size on the CPU: the result line, the refusal of a
machine without the chip, and ``correct`` coming out false when the timed
path is broken underneath or replaced by the control."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import benchpath  # noqa: F401
from bench import calibrate, harness

ROOT = benchpath.ROOT
CELL = "bert100m-nob2.fed5.local4"
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]
SEED = 2**31 + 977


def tiny_spec():
    spec = harness.load_spec(CELL)
    spec.config.update(num_layers=2, d_model=128, num_heads=4,
                       num_kv_heads=4, d_ff=256, vocab_size=256)
    spec.traffic.update(clients=2, local_steps=2, seqs_per_step=2,
                        seq_len=32)
    return spec


def run_tiny(seconds=0.5):
    return harness.run_cell(tiny_spec(), SEED, seconds, False,
                            time.perf_counter(), check_chips=False)


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0"]


def test_refuses_a_machine_without_the_chip():
    p = _bench(ARGS, ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _bench(ARGS, tmp_path)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_tiny_rehearsal_prints_the_contract_keys(capsys):
    result = run_tiny()
    harness.print_result(result)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last) == RESULT_KEYS
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] >= 1 and last["failed"] == 0
    assert set(last["metrics"]) == {"round_s", "setup_s"}
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    checks = err.strip().splitlines()[-len(last["checks"]):]
    assert [c.split()[1] for c in checks] == list(last["checks"])


def _state_unchanged(real):
    def round_fn(cfg, loss_fn, params, opt_state, batch, key, *a, **kw):
        _, _, metrics = real(cfg, loss_fn, params, opt_state, batch, key,
                             *a, **kw)
        return params, opt_state, metrics
    return round_fn


def _half_batch(real):
    def round_fn(cfg, loss_fn, params, opt_state, batch, key, *a, **kw):
        half = {k: v[:, :, : v.shape[2] // 2] for k, v in batch.items()}
        return real(cfg, loss_fn, params, opt_state, half, key, *a, **kw)
    return round_fn


def _wrong_key(monkeypatch):
    """The server desketches with another key than the clients sketched."""
    import repro.core.safl as safl
    real = safl.desk_packed

    def desk(plan, rp, payload):
        other = safl.derive_round_params(plan, jax.random.key(7))
        return real(plan, other, payload)
    monkeypatch.setattr(safl, "desk_packed", desk)


def test_a_wrong_desketch_key_is_not_correct(monkeypatch):
    _wrong_key(monkeypatch)
    result = run_tiny()
    assert result["correct"] is False, result["checks"]
    # the norms barely see the key; the probes' dot products do
    limits = tiny_spec().job["limits"]
    over = {k for k, c in result["checks"].items() if c["value"] > limits[k]}
    assert {"grad_dir_gap", "change_dir_gap"} <= over


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    import repro.core.safl as safl
    monkeypatch.setattr(safl, "safl_round", fault(safl.safl_round))
    result = run_tiny()
    assert result["correct"] is False, result["checks"]


def test_control_and_planted_fault_fail_their_limits():
    """The control (the program's bfloat16 parameters against the float32
    reference), the faults planted in the reference (half the batch, the
    wrong desketch key) and a state left unchanged each fail a limit;
    sound runs pass every one."""
    spec = tiny_spec()
    limits = spec.job["limits"]
    res = calibrate.calibrate(spec, [SEED], [SEED], log=lambda *_: None)
    over = lambda r: any(r[k] > limits[k] for k in limits)
    assert not over(res["rows"]["sound"][SEED])
    for kind in ("control", "state_unchanged") + calibrate.FAULTS:
        assert over(res["rows"][kind][SEED]), kind


@pytest.mark.parametrize("side, key, value", [
    ("program", "mlp_output_bias", True), ("program", "no_such_key", 1),
    ("reference", "attn_bias", True)])
def test_a_config_key_the_program_does_not_run_is_refused(side, key, value):
    from bench import program, reference
    check = {"program": program.model_config,
             "reference": reference.check_config}[side]
    with pytest.raises(ValueError):
        check(dict(tiny_spec().config, **{key: value}))
