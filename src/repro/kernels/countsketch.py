"""Pallas TPU kernel: Count-Sketch aggregation (sk of paper Lemma A.3).

TPU adaptation (DESIGN.md §2): TPUs have no fast scatter, so the classic
``out[h[i]] += s[i] * v[i]`` loop is reformulated as a tile-local one-hot
matmul that runs on the MXU:

    for each tile of T input elements:
        onehot[T, b] = (h_tile[:, None] == iota_b[None, :])
        out[b]      += x_tile[T] @ onehot          # MXU matmul

The batched variant serves the packed sketch engine (DESIGN.md §4): the
whole round's uplink is ONE launch over a ``(client, b-block, tile)`` grid.
The (TILE_N, B_BLOCK) one-hot is built once per (b-block, tile) step and
reused by every client row of the block through a single
``(G_BLOCK, TILE_N) @ (TILE_N, B_BLOCK)`` MXU matmul -- instead of the
per-leaf loop's O(G x num_leaves) kernel calls per round.

VMEM: the fp32 one-hot tile is capped at (TILE_N, B_BLOCK) = 8 MiB; sketch
sizes beyond ``MAX_B_BLOCK`` are handled by the b-block grid axis (each
block compares ``h`` against its own column window), so any ``b`` fits.

Input ``x`` is the sign-multiplied vector ``v * s`` (signs applied by the
caller so the kernel is a pure semantic of "segment-sum with hash h").
The TPU grid is sequential over the LAST axis, so revisiting the same
output block across tile steps accumulates deterministically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 1024      # input elements per grid step (8*128-aligned for the VPU)
MAX_B_BLOCK = 2048  # max output slots per block: (1024, 2048) fp32 = 8 MiB
G_BLOCK = 8        # client rows per block (fp32 sublane multiple)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _countsketch_kernel(x_ref, h_ref, o_ref, *, b_block: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                      # (g_block, TILE_N) f32
    h = h_ref[...]                      # (1, TILE_N) i32
    tile_n = x.shape[1]
    # this b-block owns columns [bb * b_block, (bb+1) * b_block)
    cols = (jax.lax.broadcasted_iota(jnp.int32, (tile_n, b_block), 1)
            + pl.program_id(1) * b_block)
    onehot = (h.reshape(tile_n, 1) == cols).astype(x.dtype)   # (TILE_N, b_block)
    # HIGHEST: an f32 sum, not one over x rounded to bf16
    o_ref[...] += jnp.dot(x, onehot, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


def countsketch_clients_pallas(x: jax.Array, h: jax.Array, b: int, *,
                               interpret: bool) -> jax.Array:
    """Batched count-sketch ``out[g, j] = sum_{h[i]==j} x[g, i]``.

    x: (G, n) float32 (already sign-multiplied), h: (n,) int32 in [0, b),
    shared across the G client rows (paper Remark 3.1: one operator per
    round).  Returns (G, b) float32.  Any ``b`` is supported via the
    b-block grid axis.
    """
    g, n = x.shape
    g_block = G_BLOCK if g > 1 else 1
    g_pad = _round_up(g, g_block)
    n_pad = _round_up(n, TILE_N)
    b_block = min(MAX_B_BLOCK, _round_up(b, 128))
    b_pad = _round_up(b, b_block)
    # pad x with zero rows/cols -> padded elements contribute nothing
    xp = jnp.pad(x.astype(jnp.float32), ((0, g_pad - g), (0, n_pad - n)))
    hp = jnp.pad(h.astype(jnp.int32), (0, n_pad - n),
                 constant_values=-1).reshape(1, n_pad)
    grid = (g_pad // g_block, b_pad // b_block, n_pad // TILE_N)
    out = pl.pallas_call(
        functools.partial(_countsketch_kernel, b_block=b_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((g_block, TILE_N), lambda g_, bb, t: (g_, t)),
            pl.BlockSpec((1, TILE_N), lambda g_, bb, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((g_block, b_block), lambda g_, bb, t: (g_, bb)),
        out_shape=jax.ShapeDtypeStruct((g_pad, b_pad), jnp.float32),
        interpret=interpret,
    )(xp, hp)
    return out[:g, :b]


def countsketch_pallas(x: jax.Array, h: jax.Array, b: int, *,
                       interpret: bool) -> jax.Array:
    """Count-sketch ``segment_sum(x, h, b)`` via the batched Pallas kernel.

    x: (n,) float32 (already sign-multiplied), h: (n,) int32 in [0, b).
    """
    return countsketch_clients_pallas(x.reshape(1, -1), h, b,
                                      interpret=interpret).reshape(b)
