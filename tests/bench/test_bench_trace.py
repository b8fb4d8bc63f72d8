"""The trace reduction: busy and idle time, module time and collectives."""

import json

import pytest

import benchpath  # noqa: F401
from bench import devtrace
from bench.metrics import idle_share, round_mfu

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
# a cut of a window traced on a TPU v5e in bert100m-nob2.fed5.local4: the
# end of one round's program, the gap while the host fetches its loss, and
# the start of the next round's
RECORDED = benchpath.ROOT / "tests/bench/data/tpu_round_boundary.json"


def _synthetic():
    """Two chips over a 100 us window; times in ns."""
    return [
        [HOST, "python", devtrace.WINDOW, 0, 100_000],
        [HOST, "python", "dispatch", 62_000, 33_000],
        [D0, devtrace.MODULES, "jit_chunk(1)", 0, 100_000],
        [D0, devtrace.OPS, "fusion.1", 0, 40_000],
        [D0, devtrace.OPS, "fusion.2", 30_000, 20_000],    # overlaps fusion.1
        [D0, devtrace.OPS, "all-reduce.3", 70_000, 10_000],
        [D1, devtrace.OPS, "fusion.1", 0, 40_000],
        [D1, devtrace.OPS, "all-reduce-start.3", 70_000, 20_000],
        [D1, devtrace.OPS, "fusion.9", 95_000, 10_000],    # clipped at 100 us
    ]


def test_synthetic_reduction_matches_hand_counts():
    r = devtrace.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(100e-6)
    # device 0 busy 0-50 and 70-80 us = 60 us; device 1 0-40, 70-90,
    # 95-100 us = 65 us
    assert r["busy_s"] == pytest.approx((60e-6 + 65e-6) / 2)
    assert r["collective_s"] == pytest.approx((10e-6 + 20e-6) / 2)
    assert r["modules"] == {"jit_chunk": pytest.approx(100e-6 / 2)}
    gaps = dict(r["idle_gaps"])
    # device 0: gaps 50-70 (middle 60: no span), 80-100 (middle 90: in
    # middle); device 1: 40-70 (middle 55: no span), 90-95 (under 10 us)
    assert gaps["dispatch"] == pytest.approx(20e-6 / 2)
    assert gaps["no host span"] == pytest.approx((20e-6 + 30e-6) / 2)
    assert gaps["between ops (< 10 us)"] == pytest.approx(5e-6 / 2)


def test_metrics_from_a_reduction():
    import types
    r = devtrace.reduce(_synthetic())
    ctx = types.SimpleNamespace(fused=r, rounds=2, chips=2,
                                peak={"bf16_flops_per_s": 1e12},
                                flops_per_round=10e6)
    assert idle_share.read(ctx) == pytest.approx(100 * (1 - 62.5 / 100))
    # 50 us a round on 2 chips of 1 TFLOP/s: 1e8 FLOP possible, 1e7 done
    assert round_mfu.read(ctx) == pytest.approx(10.0)


def test_no_device_op_in_the_window_is_an_error():
    events = [[HOST, "python", devtrace.WINDOW, 0, 10]]
    with pytest.raises(ValueError):
        devtrace.reduce(events)



def test_recorded_tpu_trace_matches_a_count_by_hand():
    events = json.loads(RECORDED.read_text())
    r = devtrace.reduce(events)
    (w0, w1), = [(s, s + d) for p, l, n, s, d in events
                 if n == devtrace.WINDOW]
    clipped = lambda line: sorted((max(s, w0), min(s + d, w1))
                                  for p, l, n, s, d in events if l == line)
    busy, end = 0, w0
    for s, e in clipped(devtrace.OPS):
        busy += max(0, e - max(s, end))
        end = max(end, e)
    modules = sum(e - s for s, e in clipped(devtrace.MODULES))
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert sum(r["modules"].values()) == pytest.approx(modules * 1e-9)
    assert set(r["modules"]) == {"jit_chunk", "jit_convert_element_type"}
    assert r["collective_s"] == 0.0 and r["devices"] == 1
    # the one long gap: the host fetching the finished round's loss
    (name, gap), = [g for g in r["idle_gaps"] if g[1] > 1e-4]
    assert name == "np.asarray(jax.Array)"
    assert gap == pytest.approx((w1 - w0) * 1e-9 - r["busy_s"], rel=0.01)
