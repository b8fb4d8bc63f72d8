"""Quickstart: SAFL on a tiny LM, inspecting every moving part.

The 60-round run executes as on-device scanned chunks (launch/driver.py):
the PackingPlan is built once, each scan step samples its own federated
batch on device, and losses come back one chunk at a time.

    PYTHONPATH=src python examples/quickstart.py
"""
import functools

import jax
import jax.numpy as jnp

from repro.core.adaptive import AdaConfig
from repro.core.packed import make_packing_plan
from repro.core.safl import SAFLConfig, init_safl, safl_round, \
    uplink_bits_per_round
from repro.core.sketch import SketchConfig
from repro.data import BigramLMData, LMDataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.driver import run_scan
from repro.models import ModelConfig, init_params, loss_fn

enable_compile_cache()
model = ModelConfig(name="tiny", arch_type="dense", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128)
safl = SAFLConfig(
    sketch=SketchConfig(kind="countsketch", ratio=0.05, min_b=16),
    server=AdaConfig(name="amsgrad", lr=0.01),       # Algorithm 2
    client_lr=0.5, local_steps=2)                    # K = 2 local SGD steps

params = init_params(model, jax.random.key(0))
opt = init_safl(safl, params)
d = sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
print(f"model: d = {d:,} parameters")
print(f"uplink per round: {uplink_bits_per_round(safl, params) / 8 / 1024:.1f}"
      f" KiB  (dense would be {d * 4 / 1024:.1f} KiB -> "
      f"{d * 32 / uplink_bits_per_round(safl, params):.0f}x compression)")

data = BigramLMData(LMDataConfig(vocab_size=128, seq_len=32, num_clients=5,
                                 alpha=0.03))
sampler = data.device_sampler(batch_per_client=8, local_steps=2)
loss = lambda p, b: loss_fn(model, p, b)

# static sketch layout once; the round operator re-derives per scanned key
plan = make_packing_plan(safl.sketch, params)
round_fn = functools.partial(safl_round, safl, loss, plan=plan)
bits = uplink_bits_per_round(safl, params)

params, opt, hist = run_scan(
    round_fn, sampler, params, opt, rounds=60, key=jax.random.key(0),
    chunk_size=10, bits_per_round=bits,
    on_chunk=lambda t, p, s, h: print(
        f"round {t - 1:3d}  mean client loss = {h['loss'][-1]:.4f}"))
print(f"done: loss {hist['loss'][0]:.4f} -> {hist['loss'][-1]:.4f} with a "
      f"{d * 32 / bits:.0f}x-compressed uplink, "
      f"{int(hist['uplink_bits'].sum() / 8 / 1024)} KiB total uplink, "
      f"6 device dispatches for 60 rounds.")
