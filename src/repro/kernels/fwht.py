"""Pallas TPU kernel: fast Walsh-Hadamard transform (SRHT backbone).

TPU adaptation (DESIGN.md §2): the FWHT is memory-bound and MXU-hostile, so
we run it on the VPU with all butterflies of a row resident in VMEM.  A
length-n transform (n = n1 * n2 power of two) uses the Kronecker identity

    H_n = H_{n1} (x) H_{n2}    =>    FWHT(x) = H_{n1} X H_{n2}

with X = x.reshape(n1, n2):  pass 1 applies H_{n2} along rows, pass 2 applies
H_{n1} along rows of X^T.  Each kernel call transforms a (ROW_BLOCK, C)
tile fully inside VMEM with log2(C) unrolled butterfly stages.

Each stage is written along the lane axis, in the form Mosaic lowers: the
butterfly partner of lane j is lane j XOR h, read through two lane rotations
(``pltpu.roll``) and chosen by an iota bit mask.  No stage reshapes the
tile.  Rows shorter than one 128-lane vreg row are packed side by side into
128-lane rows outside the kernel; the stages stop at the row length, so the
packed rows never mix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 8          # rows transformed per grid step
MAX_C = 4096           # max per-row transform length held in VMEM
LANES = 128            # lane width of a vreg row


def _fwht_rows_kernel(x_ref, o_ref, *, seg: int):
    """FWHT of every length-``seg`` lane segment of a (ROW_BLOCK, C) tile."""
    x = x_ref[...]
    c = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    h = 1
    while h < seg:
        # roll follows jnp.roll: roll(x, s)[j] == x[j - s  (mod c)]
        nxt = pltpu.roll(x, c - h, 1)          # x[j + h]
        prv = pltpu.roll(x, h, 1)              # x[j - h]
        upper = (lane & h) != 0                # lane j is the "b" of its pair
        x = jnp.where(upper, prv - x, x + nxt)
        h *= 2
    o_ref[...] = x


def fwht_rows_pallas(x: jax.Array, *, interpret: bool) -> jax.Array:
    """Unnormalized FWHT along the last axis of (R, C); C a power of 2."""
    r, c = x.shape
    assert c & (c - 1) == 0 and c <= MAX_C
    pack = max(1, LANES // c)                  # rows per 128-lane row
    width = pack * c
    rows = -(-r // pack)                       # packed rows
    r_pad = -(-rows // ROW_BLOCK) * ROW_BLOCK
    xp = jnp.pad(x.astype(jnp.float32), ((0, r_pad * pack - r), (0, 0)))
    xp = xp.reshape(r_pad, width)
    out = pl.pallas_call(
        functools.partial(_fwht_rows_kernel, seg=c),
        grid=(r_pad // ROW_BLOCK,),
        in_specs=[pl.BlockSpec((ROW_BLOCK, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROW_BLOCK, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, width), jnp.float32),
        interpret=interpret,
    )(xp)
    return out.reshape(r_pad * pack, c)[:r]


def fwht_pallas(v: jax.Array, *, interpret: bool) -> jax.Array:
    """Unnormalized FWHT of a 1-D vector whose length is a power of 2."""
    (n,) = v.shape
    assert n & (n - 1) == 0
    if n <= MAX_C:
        return fwht_rows_pallas(v.reshape(1, n), interpret=interpret).reshape(n)
    # factor n = n1 * n2 with n2 <= MAX_C (two-level Kronecker covers n <= 16M)
    n2 = MAX_C
    n1 = n // n2
    assert n1 <= MAX_C, "fwht_pallas supports n <= MAX_C**2 (16M)"
    xm = v.reshape(n1, n2)
    xm = fwht_rows_pallas(xm, interpret=interpret)          # H_{n2} along rows
    xm = fwht_rows_pallas(xm.T, interpret=interpret).T      # H_{n1} along cols
    return xm.reshape(n)
