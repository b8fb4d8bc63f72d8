"""The system under test, as one cell drives it.

``build(spec, seed)`` makes the cell's weights and server state on the
device from the seed and returns a ``Program`` whose ``run(on_chunk)``
drives the program's own scanned driver, ``repro.launch.driver.run_scan``
over ``repro.core.safl.safl_round``, one round per call.  The two probes
read what the comparison needs from the driver's state between calls.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic, weights

ROUNDS_FOREVER = 1 << 30
# what the program does where it has no switch: a file may state such a key
# (listed in ``reduced`` or ``assumed``) only at the value the program runs
NO_SWITCH = {"mlp_output_bias": False}


def model_config(cfg: dict):
    """The program's ModelConfig: its registered config with the file's keys.

    A key the program has no field for is refused, unless it states what the
    program does anyway (``NO_SWITCH``); so program and reference never run
    different models without an error."""
    from repro.configs import get_config
    base = get_config(cfg["program_config"])
    fields = {f.name for f in dataclasses.fields(base)}
    listed = set(cfg.get("reduced", ())) | set(cfg.get("assumed", ()))
    over = {}
    for k, v in cfg.items():
        if k in reference.META:
            continue
        if k in fields:
            over[k] = v
        elif k not in NO_SWITCH or v != NO_SWITCH[k] or k not in listed:
            raise ValueError(f"the program has no setting {k}={v!r}")
    over["dtype"] = reference.dtype_of(cfg["dtype"])
    return dataclasses.replace(base, **over)


def safl_config(job: dict, local_steps: int):
    from repro.core.adaptive import AdaConfig
    from repro.core.safl import SAFLConfig
    from repro.core.sketch import SketchConfig
    sk, sv = job["sketch"], job["server"]
    return SAFLConfig(
        sketch=SketchConfig(kind=sk["kind"], ratio=sk["ratio"],
                            min_b=sk["min_b"], cs_hash=sk["family"]),
        server=AdaConfig(name=sv["name"], lr=sv["lr"], beta1=sv["beta1"],
                         beta2=sv["beta2"], eps=sv["eps"]),
        client_lr=job["client_lr"], local_steps=local_steps)


def check_layout(model_cfg, cfg: dict) -> None:
    """The program's parameter layout must be the one the reference reads."""
    from repro.models.model import param_shapes
    got = jax.tree.map(tuple, param_shapes(model_cfg),
                       is_leaf=lambda x: isinstance(x, tuple))
    want = reference.param_shapes(cfg)
    if got != want:
        raise RuntimeError("the program's parameter layout differs from the "
                           "reference's")


@dataclasses.dataclass
class Program:
    model_cfg: Any
    safl: Any
    sampler: Any
    params: Any
    state: Any
    init: Callable            # key -> initial params (the seed's weights)
    weights_key: Any
    round_key: Any
    probe_key: Any
    plan: Any                 # the packing plan (stage timings)
    run: Callable = None      # on_chunk -> None; raises out of on_chunk

    def grad_readings(self, state) -> tuple[np.ndarray, np.ndarray]:
        """Per leaf, the norm of round 1's server update (from AMSGrad's
        ``m / (1 - beta1)``) and its dot product with the leaf's probe."""
        r = _grad_readings(state["m"], self.safl.server.beta1,
                           self.probe_key)
        return tuple(np.asarray(x, np.float64) for x in r)

    def change_readings(self, params) -> tuple[np.ndarray, np.ndarray]:
        """The same for the parameters' change since the seed's weights."""
        r = _change_readings(self.init, params, self.weights_key,
                             self.probe_key)
        return tuple(np.asarray(x, np.float64) for x in r)


def _norms_dots(leaves, probe_key):
    z = [weights.probe_leaf(probe_key, i, x.shape)
         for i, x in enumerate(leaves)]
    return (jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in leaves]),
            jnp.stack([jnp.sum(x * zi) for x, zi in zip(leaves, z)]))


@functools.partial(jax.jit, static_argnums=1)
def _grad_readings(m, beta1: float, probe_key):
    return _norms_dots([x / (1 - beta1) for x in jax.tree.leaves(m)],
                       probe_key)


@functools.partial(jax.jit, static_argnums=0)
def _change_readings(init, params, key, probe_key):
    return _norms_dots([a.astype(jnp.float32) - b.astype(jnp.float32)
                        for a, b in zip(jax.tree.leaves(params),
                                        jax.tree.leaves(init(key)))],
                       probe_key)


def _schedule(job):
    from repro.optim import cosine
    s = job.get("schedule")
    if not s:
        return None
    return cosine(s["total"], min_frac=s["min_frac"], warmup=s["warmup"])


def build(spec, seed: int, *, dtype: str | None = None) -> Program:
    """The cell's program at ``seed``; ``dtype`` overrides the parameters'
    dtype (the program's own lower-precision path, for the control)."""
    import repro.core.safl as safl_mod
    from repro.core.packed import make_packing_plan
    from repro.launch.driver import run_scan
    from repro.models.model import loss_fn

    if spec.job["driver"] != "run_scan":
        raise ValueError(f"unknown driver {spec.job['driver']!r}")
    cfg = dict(spec.config, **({"dtype": dtype} if dtype else {}))
    model_cfg = model_config(cfg)
    reference.check_config(cfg)
    check_layout(model_cfg, cfg)
    safl = safl_config(spec.job, spec.traffic["local_steps"])
    sampler = traffic.ZipfClients.from_traffic(
        spec.traffic, cfg["vocab_size"], weights.stream(seed, "data"))
    wkey, rkey = weights.stream(seed, "weights"), weights.stream(seed, "rounds")
    init = weights.make_init(reference.param_shapes(cfg),
                             reference.dtype_of(cfg["dtype"]),
                             cfg["num_layers"])
    params = init(wkey)
    state = jax.jit(functools.partial(safl_mod.init_safl, safl))(params)
    plan = make_packing_plan(safl.sketch, params)
    round_fn = functools.partial(safl_mod.safl_round, safl,
                                 functools.partial(loss_fn, model_cfg),
                                 plan=plan)
    sched = _schedule(spec.job)
    kwargs_fn = (lambda t: {"lr_scale": sched(t)}) if sched else None
    prog = Program(model_cfg, safl, sampler, params, state, init, wkey, rkey,
                   weights.stream(seed, "probe"), plan)

    def run(on_chunk):
        p, s = prog.params, prog.state
        prog.params = prog.state = None      # donated by the first call
        run_scan(round_fn, sampler, p, s, rounds=ROUNDS_FOREVER, key=rkey,
                 chunk_size=1, kwargs_fn=kwargs_fn, on_chunk=on_chunk)

    prog.run = run
    return prog
