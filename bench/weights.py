"""Seeds, keys and the model weights the benchmark makes from ``--seed``.

The weights follow the program's parameter layout (``param_shapes``) and
are made on the device in one jitted call, in the dtype the configuration
states: norm scales 1, biases 0, the embedding and output head N(0, 0.02),
every other matrix N(0, 1/fan_in) with output projections further scaled by
1/sqrt(2 L).  The reference makes the same arrays from the same seed with
the same function, so it takes nothing the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ZEROS = ("bias", "bi", "bo", "bq", "bk", "bv")


def base_key(seed: int):
    """A key from any non-negative seed, all of its bits kept."""
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def stream(seed: int, name: str):
    """Independent key streams: weights, data, rounds, probe."""
    streams = {"weights": 1, "data": 2, "rounds": 3, "probe": 4}
    return jax.random.fold_in(base_key(seed), streams[name])


def probe_leaf(key, i, shape):
    """Leaf ``i``'s probe, N(0, 1) in float32: the comparison reads the dot
    product of an update with it, which sees the update's direction."""
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


def _paths(shapes):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return [("/".join(str(getattr(k, "key", k)) for k in p), tuple(s))
            for p, s in flat], treedef


def _leaf(key, path: str, shape, num_layers: int):
    name = path.split("/")[-1]
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name in ZEROS:
        return jnp.zeros(shape, jnp.float32)
    if name in ("embed", "lm_head"):
        std = 0.02
    else:
        std = 1.0 / math.sqrt(shape[-2])
        if name == "wo":
            std /= math.sqrt(2.0 * num_layers)
    return jax.random.normal(key, shape, jnp.float32) * std


def make_init(shapes, dtype, num_layers: int, out_shardings=None):
    """Jitted ``key -> params`` for a shape tree (leaves are tuples)."""
    paths, treedef = _paths(shapes)

    def init(key):
        leaves = [_leaf(jax.random.fold_in(key, i), p, s, num_layers)
                  .astype(dtype) for i, (p, s) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(init, out_shardings=out_shardings)
