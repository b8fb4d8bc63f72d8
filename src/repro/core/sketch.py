"""Random linear sketching operators for SAFL (paper §3.2).

Implements the three sketch families the paper's theory covers:

* ``gaussian``    -- i.i.d. isotropic Gaussian projection (Lemma A.2)
* ``srht``        -- Subsampled Randomized Hadamard Transform (Lemma A.1),
                     realized with a fast Walsh--Hadamard transform (FWHT)
* ``countsketch`` -- Count-Sketch (Lemma A.3)
* ``none``        -- identity (the uncompressed "ambient dimension" baseline)

All operators satisfy the paper's three Properties:

1. Linearity:            sk(a v + b w) = a sk(v) + b sk(w)   (exact)
2. Unbiasedness:         E[desk(sk(v))] = v                  (over the seed)
3. Bounded vector products (high-probability JL-style inner products)

Sketching is applied **per tensor** ("per-tensor" mode): each parameter
tensor of size n gets its own sketch of size b = clip(ceil(n * ratio)).
Per-tensor sketching keeps sk/desk shard-local under tensor parallelism
(zero extra collectives) and is the layer-wise variant the paper's
conclusion points to.  A ``concat`` mode (sketching the concatenated
d-vector, exactly the paper's Algorithm 1) is also provided for parity
experiments on small models.

Seeds: one PRNG key per round, shared by all clients (paper Remark 3.1);
per-tensor keys are derived with ``jax.random.fold_in`` on the leaf index,
so the same round key on every device/client reproduces the same operator.

This module is the per-leaf REFERENCE implementation: ``sketch_tree`` /
``desketch_tree`` loop over leaves and re-derive the operator on each side
of the round trip.  The hot path is the packed engine in
``repro.core.packed`` (one fused dispatch per round, operator derived once
and shared by sk/desk); tests/test_packed.py pins the two to exact parity.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Configuration of the sketching compressor."""

    kind: str = "countsketch"  # none | gaussian | srht | countsketch
    ratio: float = 0.01        # b = ceil(n * ratio) per tensor
    min_b: int = 64            # floor on per-tensor sketch size
    max_b: Optional[int] = None
    mode: str = "per_tensor"   # per_tensor | concat
    transport_dtype: Any = jnp.float32  # dtype of the transmitted sketch
    use_pallas: bool = False   # route hot loops through Pallas kernels
    gaussian_chunk: int = 8192  # column chunk for on-the-fly Gaussian R
    # Count-sketch hash family (DESIGN.md §4):
    #   "balanced"    -- block-sparse JL: pad to (m, b) rows, random per-row
    #                    rotation, sum rows.  Collision prob is 0 within a
    #                    row and exactly 1/b across rows, so Lemma A.3's
    #                    variance bound carries.  No scatter: sk adds the
    #                    rotated rows (contiguous slices, no gather); desk
    #                    is still an element gather -- the fast family.
    #   "independent" -- classic per-element uniform hash + segment-sum
    #                    (the seed reference implementation).
    cs_hash: str = "balanced"

    def __post_init__(self):
        if self.kind not in ("none", "gaussian", "srht", "countsketch"):
            raise ValueError(f"unknown sketch kind: {self.kind}")
        if self.mode not in ("per_tensor", "concat"):
            raise ValueError(f"unknown sketch mode: {self.mode}")
        if not (self.kind == "none" or 0.0 < self.ratio <= 1.0):
            raise ValueError("ratio must be in (0, 1]")
        if self.cs_hash not in ("balanced", "independent"):
            raise ValueError(f"unknown cs_hash family: {self.cs_hash}")


def leaf_sketch_size(n: int, cfg: SketchConfig) -> int:
    """Sketch size for a tensor with n elements."""
    if cfg.kind == "none":
        return n
    b = max(cfg.min_b, int(math.ceil(n * cfg.ratio)))
    if cfg.max_b is not None:
        b = min(b, cfg.max_b)
    return min(b, n)


# ---------------------------------------------------------------------------
# Fast Walsh-Hadamard transform (pure jnp; Pallas version in kernels/fwht.py)
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def fwht(x: jax.Array) -> jax.Array:
    """Unnormalized FWHT along the last axis (length must be a power of 2).

    Python loop over log2(n) butterflies -> unrolled into O(log n) HLO ops.
    """
    n = x.shape[-1]
    assert n & (n - 1) == 0, "FWHT length must be a power of 2"
    lead = x.shape[:-1]
    h = 1
    while h < n:
        x = x.reshape(lead + (n // (2 * h), 2, h))
        a = x[..., 0, :]
        b = x[..., 1, :]
        x = jnp.concatenate([a + b, a - b], axis=-1)
        x = x.reshape(lead + (n,))
        # Note: concatenate([a+b, a-b]) along the paired axis reproduces the
        # standard butterfly once we track the (pairs, 2, h) layout.
        h *= 2
    return x


# The reshape trick above needs care: we keep a reference implementation
# that is obviously correct and use it to cross-check in tests.
def fwht_reference(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.float64).copy()
    n = x.shape[-1]
    h = 1
    while h < n:
        for i in range(0, n, h * 2):
            for j in range(i, i + h):
                a, b = x[..., j].copy(), x[..., j + h].copy()
                x[..., j] = a + b
                x[..., j + h] = a - b
        h *= 2
    return x


# ---------------------------------------------------------------------------
# Per-leaf sk / desk
# ---------------------------------------------------------------------------

def _keys(key: jax.Array, *tags: int) -> jax.Array:
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key


def _gaussian_sk(cfg: SketchConfig, key: jax.Array, v: jax.Array, b: int) -> jax.Array:
    """sk(v) = R v / sqrt(b), R ~ N(0,1)^{b x n}, generated chunk-wise."""
    n = v.shape[0]
    c = cfg.gaussian_chunk
    n_pad = ((n + c - 1) // c) * c
    vp = jnp.pad(v, (0, n_pad - n)).reshape(n_pad // c, c)

    def body(acc, args):
        i, vc = args
        r = jax.random.normal(jax.random.fold_in(key, i), (c, b), dtype=v.dtype)
        return acc + vc @ r, None

    acc0 = jnp.zeros((b,), dtype=v.dtype)
    idx = jnp.arange(n_pad // c, dtype=jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, (idx, vp))
    return acc / jnp.sqrt(jnp.asarray(b, v.dtype))


def _gaussian_desk(cfg: SketchConfig, key: jax.Array, s: jax.Array, n: int) -> jax.Array:
    """desk(s) = R^T s / sqrt(b) (so desk(sk(v)) = R^T R v / b, unbiased)."""
    b = s.shape[0]
    c = cfg.gaussian_chunk
    n_pad = ((n + c - 1) // c) * c

    def body(_, i):
        r = jax.random.normal(jax.random.fold_in(key, i), (c, b), dtype=s.dtype)
        return None, r @ s

    idx = jnp.arange(n_pad // c, dtype=jnp.int32)
    _, chunks = jax.lax.scan(body, None, idx)
    out = chunks.reshape(n_pad) / jnp.sqrt(jnp.asarray(b, s.dtype))
    return out[:n]


def _srht_params(key: jax.Array, n: int, b: int):
    n2 = next_pow2(n)
    sign_key, idx_key = jax.random.split(key)
    signs = jax.random.rademacher(sign_key, (n2,), dtype=jnp.float32)
    idx = jax.random.randint(idx_key, (b,), 0, n2)
    return n2, signs, idx


def _srht_sk(cfg: SketchConfig, key: jax.Array, v: jax.Array, b: int) -> jax.Array:
    n = v.shape[0]
    n2, signs, idx = _srht_params(key, n, b)
    vp = jnp.pad(v, (0, n2 - n)) * signs.astype(v.dtype)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        u = kops.fwht(vp) / jnp.sqrt(jnp.asarray(n2, v.dtype))
    else:
        u = fwht(vp) / jnp.sqrt(jnp.asarray(n2, v.dtype))
    scale = jnp.sqrt(jnp.asarray(n2 / b, v.dtype))
    return u[idx] * scale


def _srht_desk(cfg: SketchConfig, key: jax.Array, s: jax.Array, n: int) -> jax.Array:
    b = s.shape[0]
    n2, signs, idx = _srht_params(key, n, b)
    scale = jnp.sqrt(jnp.asarray(n2 / b, s.dtype))
    u = jnp.zeros((n2,), dtype=s.dtype).at[idx].add(s * scale)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        w = kops.fwht(u) / jnp.sqrt(jnp.asarray(n2, s.dtype))
    else:
        w = fwht(u) / jnp.sqrt(jnp.asarray(n2, s.dtype))
    return (w * signs.astype(s.dtype))[:n]


def _cs_hashes(key: jax.Array, n: int, b: int):
    hkey, skey = jax.random.split(key)
    h = jax.random.randint(hkey, (n,), 0, b)
    s = jax.random.rademacher(skey, (n,), dtype=jnp.float32)
    return h, s


def _balanced_cs_params(key: jax.Array, n: int, b: int):
    """Balanced (block-sparse JL) count-sketch: m = ceil(n/b) rows of b
    columns; row k is rotated by a uniform r_k, so element i = (k, c) hashes
    to slot (c + r_k) mod b.  Within a row no two elements collide; across
    rows any pair collides with probability exactly 1/b."""
    m = -(-n // b)
    rkey, skey = jax.random.split(key)
    r = jax.random.randint(rkey, (m,), 0, b)
    s = jax.random.rademacher(skey, (n,), dtype=jnp.float32)
    return r, s


# jitted so that eager callers trace and compile the row loop once per shape
@partial(jax.jit, static_argnums=3)
def _balanced_sk_core(v: jax.Array, r: jax.Array, s: jax.Array, b: int) -> jax.Array:
    """sk given derived (r, s): out[j] = sum_k x[k, (j - r_k) mod b] with
    x = v * s padded to (m, b) -- the sum of the m rows of the signed
    vector, row k cyclically rotated right by r_k.  A row is a contiguous
    slice of v and its rotation two contiguous reads: no element gather,
    no (m, b) index array, and no padded copy of v (only the last, partial
    row is padded).  Rows are added in ascending k whatever the batch, so
    under a vmap over clients each client gets the bits it gets alone.
    Shared by the per-leaf reference and the packed engine (single source
    of truth for the index math)."""
    n, m = v.shape[0], r.shape[0]
    s = s.astype(v.dtype)

    def add_row(k, acc):
        row = (jax.lax.dynamic_slice_in_dim(v, k * b, b)
               * jax.lax.dynamic_slice_in_dim(s, k * b, b))
        return acc + jnp.roll(row, r[k])

    acc = jnp.zeros((b,), v.dtype)
    if m > 1:                       # rows 0 .. m-2 lie whole inside v
        acc = jax.lax.fori_loop(0, m - 1, add_row, acc)
    tail = (m - 1) * b
    last = jnp.pad(v[tail:] * s[tail:], (0, m * b - n))
    return acc + jnp.roll(last, r[m - 1])


def _balanced_desk_core(u: jax.Array, r: jax.Array, s: jax.Array, n: int) -> jax.Array:
    """desk given derived (r, s): element (k, c) reads slot (c + r_k) mod b."""
    b = u.shape[0]
    idx = (jnp.arange(b)[None, :] + r[:, None]) % b
    return u[idx].reshape(-1)[:n] * s.astype(u.dtype)


def _balanced_cs_sk(cfg: SketchConfig, key: jax.Array, v: jax.Array, b: int) -> jax.Array:
    r, s = _balanced_cs_params(key, v.shape[0], b)
    return _balanced_sk_core(v, r, s, b)


def _balanced_cs_desk(cfg: SketchConfig, key: jax.Array, u: jax.Array, n: int) -> jax.Array:
    r, s = _balanced_cs_params(key, n, u.shape[0])
    return _balanced_desk_core(u, r, s, n)


def _countsketch_sk(cfg: SketchConfig, key: jax.Array, v: jax.Array, b: int) -> jax.Array:
    if cfg.cs_hash == "balanced":
        return _balanced_cs_sk(cfg, key, v, b)
    n = v.shape[0]
    h, s = _cs_hashes(key, n, b)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.countsketch(v * s.astype(v.dtype), h, b)
    return jax.ops.segment_sum(v * s.astype(v.dtype), h, num_segments=b)


def _countsketch_desk(cfg: SketchConfig, key: jax.Array, u: jax.Array, n: int) -> jax.Array:
    if cfg.cs_hash == "balanced":
        return _balanced_cs_desk(cfg, key, u, n)
    b = u.shape[0]
    h, s = _cs_hashes(key, n, b)
    return u[h] * s.astype(u.dtype)


def sk_leaf(cfg: SketchConfig, key: jax.Array, v: jax.Array) -> jax.Array:
    """Sketch one flat vector v -> (b,). (paper: bar_m^c = sk(delta))."""
    assert v.ndim == 1
    n = v.shape[0]
    if cfg.kind == "none":
        return v.astype(cfg.transport_dtype)
    b = leaf_sketch_size(n, cfg)
    if b >= n:  # sketch would not compress; transmit raw (still linear/unbiased)
        return v.astype(cfg.transport_dtype)
    fn = {"gaussian": _gaussian_sk, "srht": _srht_sk, "countsketch": _countsketch_sk}[cfg.kind]
    return fn(cfg, key, v, b).astype(cfg.transport_dtype)


def desk_leaf(cfg: SketchConfig, key: jax.Array, s: jax.Array, n: int,
              dtype=jnp.float32) -> jax.Array:
    """Desketch (b,) -> flat (n,). (paper: desk(bar_m))."""
    s = s.astype(dtype)
    if cfg.kind == "none" or s.shape[0] >= n:
        return s[:n]
    fn = {"gaussian": _gaussian_desk, "srht": _srht_desk, "countsketch": _countsketch_desk}[cfg.kind]
    return fn(cfg, key, s, n)


SKETCH_CHUNK_NUMEL = 1 << 24    # leaves above this sketch per layer slice


def sk_leaf_stacked(cfg: SketchConfig, key: jax.Array,
                    rows: jax.Array) -> jax.Array:
    """sk each row of ``rows`` (L, n) with the per-row operator
    ``fold_in(key, j)`` -- the layer-wise chunked path for leaves whose flat
    size would make one hash/sign temporary too large.  ``lax.map`` bounds
    the temporaries to one row's worth and realizes the layer-wise sketching
    the paper's conclusion proposes (shared by the mesh round's per-leaf
    reference path in ``launch.train``)."""
    def sk_one(args):
        j, v = args
        return sk_leaf(cfg, jax.random.fold_in(key, j), v)
    return jax.lax.map(sk_one, (jnp.arange(rows.shape[0]), rows))


def desk_leaf_stacked(cfg: SketchConfig, key: jax.Array, s: jax.Array,
                      n: int) -> jax.Array:
    """Row-wise desk of ``s`` (L, b) back to (L, n): the adjoint of
    ``sk_leaf_stacked`` under the same per-row ``fold_in(key, j)`` chain."""
    def desk_one(args):
        j, sj = args
        return desk_leaf(cfg, jax.random.fold_in(key, j), sj, n)
    return jax.lax.map(desk_one, (jnp.arange(s.shape[0]), s))


# ---------------------------------------------------------------------------
# Pytree-level sketching
# ---------------------------------------------------------------------------

def tree_sketch_sizes(cfg: SketchConfig, tree: Pytree) -> list[int]:
    leaves = jax.tree_util.tree_leaves(tree)
    return [leaf_sketch_size(int(np.prod(l.shape)) if l.shape else 1, cfg) for l in leaves]


def total_sketch_bits(cfg: SketchConfig, tree: Pytree) -> int:
    """Uplink payload in bits per round (the paper's per-round cost O(b)).

    Routed through the packing plan so the count is exactly the transmitted
    ``(b_total,)`` payload (matches the per-leaf sum in per_tensor mode and
    the single concatenated sketch in concat mode)."""
    from repro.core.packed import make_packing_plan
    itemsize = jnp.dtype(cfg.transport_dtype).itemsize
    return make_packing_plan(cfg, tree).b_total * itemsize * 8


def sketch_tree(cfg: SketchConfig, key: jax.Array, tree: Pytree) -> Pytree:
    """sk over every leaf (per_tensor) or over the concatenation (concat)."""
    if cfg.mode == "concat":
        leaves, _ = jax.tree_util.tree_flatten(tree)
        flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
        return sk_leaf(cfg, key, flat)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [sk_leaf(cfg, _keys(key, i), l.reshape(-1).astype(jnp.float32))
           for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def desketch_tree(cfg: SketchConfig, key: jax.Array, sketches: Pytree,
                  like: Pytree) -> Pytree:
    """desk back to the shapes/dtypes of ``like``."""
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    if cfg.mode == "concat":
        sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in like_leaves]
        flat = desk_leaf(cfg, key, sketches, sum(sizes))
        parts = []
        off = 0
        for l, n in zip(like_leaves, sizes):
            parts.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, parts)
    sk_leaves = jax.tree_util.tree_leaves(sketches)
    out = []
    for i, (l, s) in enumerate(zip(like_leaves, sk_leaves)):
        n = int(np.prod(l.shape)) if l.shape else 1
        v = desk_leaf(cfg, _keys(key, i), s, n).reshape(l.shape).astype(l.dtype)
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out)


def roundtrip_tree(cfg: SketchConfig, key: jax.Array, tree: Pytree) -> Pytree:
    """desk(sk(tree)) -- the lossy replicate the server optimizer consumes."""
    return desketch_tree(cfg, key, sketch_tree(cfg, key, tree), tree)
