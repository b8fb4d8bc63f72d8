"""Jit'd public wrappers over the Pallas kernels.

``_interpret`` is the one place that decides interpret mode: on any backend
other than a TPU the kernels run in the Pallas interpreter; on a TPU they
compile through Mosaic.  The kernel functions themselves take ``interpret``
with no default.  ``repro.core.sketch`` routes through these when
``SketchConfig.use_pallas`` is set.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.countsketch import (countsketch_clients_pallas,
                                       countsketch_pallas)
from repro.kernels.fwht import MAX_C, fwht_pallas, fwht_rows_pallas
from repro.kernels.gaussian_sketch import gaussian_desk_pallas, gaussian_sk_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("b",))
def countsketch(x: jax.Array, h: jax.Array, b: int) -> jax.Array:
    """Count-sketch aggregation: out[j] = sum_{h[i]==j} x[i].

    Any ``b`` is supported: the kernel splits the output into VMEM-sized
    b-blocks on a dedicated grid axis (see kernels/countsketch.py).
    """
    return countsketch_pallas(x, h, b, interpret=_interpret())


@partial(jax.jit, static_argnames=("b",))
def countsketch_clients(x: jax.Array, h: jax.Array, b: int) -> jax.Array:
    """Batched count-sketch over the client axis: x (G, n) -> (G, b).

    One Pallas launch for all G clients; the per-tile one-hot is built once
    and shared by every client row (packed engine hot path, DESIGN.md §4).
    """
    return countsketch_clients_pallas(x, h, b, interpret=_interpret())


@jax.jit
def fwht(v: jax.Array) -> jax.Array:
    """Unnormalized fast Walsh-Hadamard transform of a pow2-length vector."""
    return fwht_pallas(v, interpret=_interpret())


@jax.jit
def fwht_rows(x: jax.Array) -> jax.Array:
    """Unnormalized FWHT along the last axis of an (R, C) batch.

    Rows up to MAX_C transform in one grid sweep; longer rows fall back to
    the per-row two-level Kronecker path of ``fwht_pallas``.
    """
    if x.shape[-1] <= MAX_C:
        return fwht_rows_pallas(x, interpret=_interpret())
    return jnp.stack([fwht_pallas(row, interpret=_interpret()) for row in x])


@partial(jax.jit, static_argnames=("b",))
def gaussian_sk(seed: jax.Array, x: jax.Array, b: int) -> jax.Array:
    return gaussian_sk_pallas(seed, x, b, interpret=_interpret())


@partial(jax.jit, static_argnames=("n",))
def gaussian_desk(seed: jax.Array, s: jax.Array, n: int) -> jax.Array:
    return gaussian_desk_pallas(seed, s, n, interpret=_interpret())
