"""Stage scopes and driver spans (repro.obs.spans, DESIGN.md §11).

* the compiled chunk of ``run_scan`` carries every stage scope in its ops'
  ``op_name`` metadata, on the materialized round and on the streamed fold;
* a profile of ``run_scan`` holds the driver's three host spans per chunk,
  in order, with ``compile`` set on the first chunk only.
"""

import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.adaptive import AdaConfig
from repro.core.packed import make_packing_plan
from repro.core.safl import SAFLConfig, init_safl, safl_round
from repro.core.sketch import SketchConfig
from repro.data import BigramLMData, LMDataConfig
from repro.launch.driver import make_chunk_fn, run_scan
from repro.models import ModelConfig, init_params, loss_fn
from repro.obs import spans

MODEL = ModelConfig(name="spans", arch_type="dense", num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                    vocab_size=64)
G = 5


def _setup():
    sampler = BigramLMData(LMDataConfig(vocab_size=64, seq_len=16,
                                        num_clients=G)).device_sampler(2, 2)
    params = init_params(MODEL, jax.random.key(0))
    cfg = SAFLConfig(sketch=SketchConfig(kind="countsketch", ratio=0.1,
                                         min_b=8),
                     server=AdaConfig(name="amsgrad", lr=0.01),
                     client_lr=0.5, local_steps=2)
    plan = make_packing_plan(cfg.sketch, params)
    round_fn = functools.partial(safl_round, cfg,
                                 lambda p, b: loss_fn(MODEL, p, b), plan=plan)
    return round_fn, sampler, params, init_safl(cfg, params)


@pytest.mark.parametrize("microbatch", [None, 2], ids=["materialized",
                                                        "streamed"])
def test_chunk_carries_every_stage_scope(microbatch):
    round_fn, sampler, params, state = _setup()
    chunk = make_chunk_fn(round_fn, sampler, 1, donate=False,
                          microbatch=microbatch)
    hlo = chunk.lower(params, state, sampler.init_state(), jax.random.key(0),
                      jnp.int32(0)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    tokens = {t for n in names for t in re.findall(r"[A-Za-z_][\w.]*", n)}
    assert set(spans.STAGES) == {
        "safl.client", "safl.derive", "safl.sketch", "safl.mean",
        "safl.desk", "safl.server_opt", "driver.sample"}
    assert set(spans.STAGES) <= tokens


def test_run_scan_opens_three_spans_per_chunk(tmp_path):
    round_fn, sampler, params, state = _setup()
    seen = []
    with jax.profiler.trace(str(tmp_path)):
        run_scan(round_fn, sampler, params, state, rounds=2,
                 key=jax.random.key(1), chunk_size=1, donate=False,
                 on_chunk=lambda t, *_: seen.append(t))
    assert seen == [1, 2]
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = sorted((ev.start_ns, ev.name, dict(ev.stats))
                    for plane in data.planes for line in plane.lines
                    for ev in line.events if ev.name in spans.HOST_SPANS)
    assert [name for _, name, _ in events] == list(spans.HOST_SPANS) * 2
    d0, d1 = events[0][2], events[3][2]
    assert (d0["t0"], d0["rounds"], d0["compile"]) == (0, 1, 1)
    assert (d1["t0"], d1["rounds"], d1["compile"]) == (1, 1, 0)
