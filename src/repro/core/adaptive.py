"""ADA_OPT: server-side adaptive optimizers (paper Algorithm 2).

The server consumes the desketched averaged client update ``u = desk(m̄_t)``
as a pseudo-gradient.  AMSGrad is the paper's analyzed instantiation
(Alg. 2); Adam is what the experiments use (§5); AdaGrad / SGD / SGDm round
out the family ("flexibility on the choice of adaptive optimizers").

All optimizers are pure pytree->pytree functions so they jit/shard cleanly;
state tensors inherit the sharding of the parameters they precondition.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs import spans

Pytree = Any


@dataclasses.dataclass(frozen=True)
class AdaConfig:
    name: str = "amsgrad"      # amsgrad | adam | adagrad | sgd | sgdm
    lr: float = 1e-2           # kappa in Alg. 2
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    bias_correction: bool = False  # Alg. 2 uses none; Adam-mode may enable
    weight_decay: float = 0.0
    moment_dtype: Any = jnp.float32  # bf16 option for mega-configs (DESIGN §2)

    def __post_init__(self):
        if self.name not in ("amsgrad", "adam", "adagrad", "sgd", "sgdm"):
            raise ValueError(f"unknown optimizer {self.name}")


def init_opt_state(cfg: AdaConfig, params: Pytree) -> dict:
    zeros = lambda: jax.tree.map(
        lambda p: jnp.zeros(p.shape, cfg.moment_dtype), params)
    state = {"step": jnp.zeros((), jnp.int32)}
    if cfg.name in ("amsgrad", "adam", "sgdm"):
        state["m"] = zeros()
    if cfg.name in ("amsgrad", "adam", "adagrad"):
        state["v"] = zeros()
    if cfg.name == "amsgrad":
        state["vhat"] = zeros()
    return state


@jax.named_scope(spans.SERVER_OPT)
def apply_update(cfg: AdaConfig, state: dict, params: Pytree, update: Pytree,
                 lr_scale: jax.Array | float = 1.0) -> tuple[Pytree, dict]:
    """One ADA_OPT step.  ``update`` is the (pseudo-)gradient direction
    (for SAFL: desk(m̄_t) = desketched averaged local-delta, which already
    carries the client lr eta).  Returns (new_params, new_state)."""
    step = state["step"] + 1
    lr = cfg.lr * lr_scale
    b1, b2, eps = cfg.beta1, cfg.beta2, cfg.eps
    md = cfg.moment_dtype

    u32 = jax.tree.map(lambda u: u.astype(jnp.float32), update)

    if cfg.name == "sgd":
        direction = u32
        new_state = {"step": step}
    elif cfg.name == "sgdm":
        m = jax.tree.map(lambda m, u: (b1 * m.astype(jnp.float32) + u).astype(md),
                         state["m"], u32)
        direction = jax.tree.map(lambda m: m.astype(jnp.float32), m)
        new_state = {"step": step, "m": m}
    elif cfg.name == "adagrad":
        v = jax.tree.map(lambda v, u: (v.astype(jnp.float32) + u * u).astype(md),
                         state["v"], u32)
        direction = jax.tree.map(
            lambda u, v: u / (jnp.sqrt(v.astype(jnp.float32)) + eps), u32, v)
        new_state = {"step": step, "v": v}
    else:  # adam / amsgrad (Alg. 2)
        m = jax.tree.map(lambda m, u: (b1 * m.astype(jnp.float32)
                                       + (1 - b1) * u).astype(md),
                         state["m"], u32)
        v = jax.tree.map(lambda v, u: (b2 * v.astype(jnp.float32)
                                       + (1 - b2) * u * u).astype(md),
                         state["v"], u32)
        new_state = {"step": step, "m": m, "v": v}
        if cfg.name == "amsgrad":
            vhat = jax.tree.map(lambda vh, v: jnp.maximum(vh, v), state["vhat"], v)
            new_state["vhat"] = vhat
            precond = vhat
        else:
            precond = v
        if cfg.bias_correction:
            c1 = 1 - b1 ** step.astype(jnp.float32)
            c2 = 1 - b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = 1.0
        direction = jax.tree.map(
            lambda m, p: (m.astype(jnp.float32) / c1)
            / (jnp.sqrt(p.astype(jnp.float32) / c2) + eps), m, precond)

    if cfg.weight_decay:
        direction = jax.tree.map(
            lambda d, p: d + cfg.weight_decay * p.astype(jnp.float32),
            direction, params)

    new_params = jax.tree.map(
        lambda p, d: (p.astype(jnp.float32) - lr * d).astype(p.dtype),
        params, direction)
    return new_params, new_state


def opt_state_bytes(cfg: AdaConfig, params: Pytree) -> int:
    """Optimizer-state memory footprint (for the dry-run memory report)."""
    n = sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
    per = {"sgd": 0, "sgdm": 1, "adagrad": 1, "adam": 2, "amsgrad": 3}[cfg.name]
    return n * per * jnp.dtype(cfg.moment_dtype).itemsize
