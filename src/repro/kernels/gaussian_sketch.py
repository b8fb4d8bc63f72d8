"""Pallas TPU kernel: on-the-fly Gaussian sketch (sk/desk of Lemma A.2).

TPU adaptation (DESIGN.md §2): the b x n Gaussian matrix R is never stored.
Each grid step regenerates one (TILE_N, b) tile of R^T from a counter-based
PRNG keyed on (seed, tile, position) and immediately contracts it on the MXU:

    sk:   out[b]      += x_tile[TILE_N] @ R_tile[TILE_N, b]      (accumulate)
    desk: out[TILE_N]  = R_tile[TILE_N, b] @ s[b]                (per tile)

The PRNG is a splitmix32-style integer mixer in plain jnp ops, so the kernel
is bit-identical under interpret=True (CPU validation) and compiled TPU, and
sk/desk regenerate exactly the same R (tested via adjointness
<sk(v), s> == <v, desk(s)>).  Normals come from Box-Muller on two mixed
uint32 streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_N = 512  # input elements per grid step; (TILE_N, b) tile of R in VMEM


def _splitmix32(x: jax.Array) -> jax.Array:
    """Counter-based 32-bit mixer (splitmix64 constants truncated to 32b)."""
    x = (x + jnp.uint32(0x9E3779B9)).astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform01(bits: jax.Array) -> jax.Array:
    # top 24 bits -> (0, 1]; never exactly 0 so log() is safe.  The 24-bit
    # value fits int32 exactly, and Mosaic converts int32 (not uint32) to f32
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return (top.astype(jnp.float32) + 1.0) * (2.0 ** -24)


def _gauss_tile(seed: jax.Array, tile: jax.Array, tile_n: int, b: int) -> jax.Array:
    """Deterministic (tile_n, b) tile of R^T ~ N(0,1), via Box-Muller."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, (tile_n, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (tile_n, b), 1)
    # unique counter per (seed, tile, element, stream)
    base = (seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
            + tile.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    ctr = base + rows * jnp.uint32(2 * b) + cols * jnp.uint32(2)
    u1 = _uniform01(_splitmix32(ctr))
    u2 = _uniform01(_splitmix32(ctr + jnp.uint32(1)))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(2.0 * jnp.pi * u2)


def _sk_kernel(seed_ref, x_ref, o_ref, *, b: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    rt = _gauss_tile(seed_ref[0], i, TILE_N, b)           # (TILE_N, b)
    x = x_ref[...]                                        # (1, TILE_N)
    o_ref[...] += jnp.dot(x, rt, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


def _desk_kernel(seed_ref, s_ref, o_ref, *, b: int):
    i = pl.program_id(0)
    rt = _gauss_tile(seed_ref[0], i, TILE_N, b)           # (TILE_N, b)
    s = s_ref[...]                                        # (1, b)
    o_ref[...] = jnp.dot(s, rt.T, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


def gaussian_sk_pallas(seed: jax.Array, x: jax.Array, b: int, *,
                       interpret: bool) -> jax.Array:
    """sk(x) = R x / sqrt(b) with R regenerated tile-by-tile in-kernel."""
    n = x.shape[0]
    n_pad = ((n + TILE_N - 1) // TILE_N) * TILE_N
    xp = jnp.pad(x.astype(jnp.float32), (0, n_pad - n)).reshape(1, n_pad)
    out = pl.pallas_call(
        functools.partial(_sk_kernel, b=b),
        grid=(n_pad // TILE_N,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scalar seed
            pl.BlockSpec((1, TILE_N), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, b), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32), xp)
    return out.reshape(b) / jnp.sqrt(jnp.asarray(b, jnp.float32))


def gaussian_desk_pallas(seed: jax.Array, s: jax.Array, n: int, *,
                         interpret: bool) -> jax.Array:
    """desk(s) = R^T s / sqrt(b), regenerating the same R tiles as sk."""
    b = s.shape[0]
    n_pad = ((n + TILE_N - 1) // TILE_N) * TILE_N
    sp = s.astype(jnp.float32).reshape(1, b)
    out = pl.pallas_call(
        functools.partial(_desk_kernel, b=b),
        grid=(n_pad // TILE_N,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32), sp)
    return out.reshape(n_pad)[:n] / jnp.sqrt(jnp.asarray(b, jnp.float32))
