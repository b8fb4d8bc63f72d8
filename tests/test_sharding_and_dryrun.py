"""Sharding rules, input specs, hlo_costs parser, and a subprocess
mini-dry-run on an 8-device host mesh (integration proof that the
distributed train/serve steps lower and compile)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, input_specs, shape_eligible
from repro.models import param_pspecs
from repro.models.model import param_shapes
from repro.models.sharding import hint, use_mesh


def _abstract(cfg):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(tuple(s), cfg.dtype),
                        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))


def test_param_pspec_rules():
    cfg = get_config("llama3_2_1b")
    pa = _abstract(cfg)
    specs = param_pspecs(pa, fsdp=False)
    assert specs["embed"] == P("model", None)
    assert specs["layers"]["l0"]["attn"]["wq"] == P(None, None, "model")
    assert specs["layers"]["l0"]["attn"]["wo"] == P(None, "model", None)
    assert specs["layers"]["l0"]["mlp"]["wi"] == P(None, None, "model")
    # fsdp adds the data axis
    specs2 = param_pspecs(pa, fsdp=True)
    assert specs2["layers"]["l0"]["attn"]["wq"] == P(None, "data", "model")


def test_moe_and_mamba_pspecs():
    moe = param_pspecs(_abstract(get_config("dbrx_132b")), fsdp=True)
    assert moe["layers"]["l0"]["moe"]["wi"] == P(None, "model", "data", None)
    ssm = param_pspecs(_abstract(get_config("falcon_mamba_7b")), fsdp=False)
    assert ssm["layers"]["l0"]["mamba"]["wx"] == P(None, None, "model")
    assert ssm["layers"]["l0"]["mamba"]["a_log"] == P(None, "model", None)


def test_hint_noop_off_mesh():
    x = jnp.ones((4, 4))
    y = hint(x, ("pod", "data"), "model")
    assert y is x or bool((y == x).all())


def test_input_specs_shapes():
    cfg = get_config("llama3_2_1b")
    t = input_specs(cfg, "train_4k", num_clients=16, local_steps=1)
    assert t["batch"]["tokens"].shape == (16, 1, 16, 4096)
    p = input_specs(cfg, "prefill_32k")
    assert p["batch"]["tokens"].shape == (32, 32768)
    d = input_specs(cfg, "decode_32k")
    assert d["tokens"].shape == (128, 1)
    assert d["cache"]["layers"]["l0"]["k"].shape == (16, 128, 32768, 8, 64)


def test_input_specs_swa_cache_is_window_sized():
    cfg = get_config("h2o_danube_1_8b")
    d = input_specs(cfg, "long_500k")
    # SWA ring buffer: cache seq dim == window, not 524288
    assert d["cache"]["layers"]["l0"]["k"].shape[2] == cfg.sliding_window


def test_long_context_eligibility():
    ok, _ = shape_eligible(get_config("falcon_mamba_7b"), "long_500k")
    assert ok
    ok, why = shape_eligible(get_config("qwen2_7b"), "long_500k")
    assert not ok and "full-attention" in why
    ok, _ = shape_eligible(get_config("jamba_1_5_large_398b"), "long_500k")
    assert ok


def test_vlm_and_audio_specs_provide_frontend_embeddings():
    v = input_specs(get_config("qwen2_vl_7b"), "train_4k", num_clients=16)
    assert "patch_embeds" in v["batch"]
    assert v["batch"]["tokens"].shape[-1] == 4096 - 256
    a = input_specs(get_config("whisper_large_v3"), "train_4k", num_clients=16)
    assert a["batch"]["audio_embeds"].shape[-2:] == (1500, 1280)


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import get_config
    from repro.core.safl import SAFLConfig
    from repro.core.sketch import SketchConfig
    from repro.core.adaptive import AdaConfig
    from repro.launch.train import (make_safl_train_step, make_serve_step,
        batch_pspecs, cache_pspecs, opt_pspecs, to_shardings, data_axes_of)
    from repro.launch.dryrun import abstract_params, abstract_opt_state
    from repro.models.sharding import param_pspecs, use_mesh
    from repro.models.model import cache_shapes

    from repro.launch.mesh import _mesh
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = get_config("llama3_2_1b", smoke=True)
    safl = SAFLConfig(sketch=SketchConfig(kind="countsketch", ratio=0.01),
                      server=AdaConfig(name="amsgrad", lr=1e-3),
                      client_lr=0.01, local_steps=2)
    with use_mesh(mesh):
        pa = abstract_params(cfg)
        pspecs = param_pspecs(pa)
        p_sh = to_shardings(mesh, pspecs)
        step, _ = make_safl_train_step(cfg, safl, mesh)
        batch = {"tokens": jax.ShapeDtypeStruct((2, 2, 4, 64), jnp.int32)}
        o_abs = abstract_opt_state(safl.server, pa)
        jit = jax.jit(step, in_shardings=(
            p_sh, to_shardings(mesh, opt_pspecs(safl.server, pspecs)),
            to_shardings(mesh, batch_pspecs(batch, mesh)),
            NamedSharding(mesh, P())))
        c = jit.lower(pa, o_abs, batch,
                      jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        assert c.cost_analysis() is not None
        # serve step
        serve = make_serve_step(cfg)
        daxes = data_axes_of(mesh)
        cshapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(tuple(s), cfg.dtype),
            cache_shapes(cfg, 8, 128), is_leaf=lambda x: isinstance(x, tuple))
        c_sh = to_shardings(mesh, cache_pspecs(cshapes, daxes))
        jit2 = jax.jit(serve, in_shardings=(
            p_sh, c_sh, NamedSharding(mesh, P(daxes, None)),
            NamedSharding(mesh, P())))
        c2 = jit2.lower(pa, cshapes, jax.ShapeDtypeStruct((8, 1), jnp.int32),
                        jax.ShapeDtypeStruct((), jnp.int32)).compile()
        assert c2.cost_analysis() is not None
    print("MINI_DRYRUN_OK")
""")


def test_mini_dryrun_8_devices():
    """Distributed SAFL train + serve lower AND compile on an 8-device host
    mesh (subprocess so the device-count flag never leaks into this test
    session)."""
    import os
    env = {"PYTHONPATH": "src",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", ""),
           # the forced device count is a CPU-backend flag; pinning the child
           # to the CPU also keeps it off an accelerator the parent may hold
           "JAX_PLATFORMS": "cpu"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", MINI_DRYRUN],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=repo)
    assert "MINI_DRYRUN_OK" in r.stdout, r.stderr[-3000:]


def test_hlo_costs_trip_weighting():
    from repro.launch.hlo_costs import analyze_hlo_text
    from jax import lax

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = lax.scan(body, x, None, length=12)
        return y

    comp = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    c = analyze_hlo_text(comp.as_text())
    assert c.flops == 12 * 2 * 8 * 8 * 8
