"""Packed sketch engine: one fused dispatch per round instead of a per-leaf loop.

The per-leaf path in ``repro.core.sketch`` (kept as the reference
implementation) runs a Python loop over every parameter leaf and re-derives
the CountSketch hashes/signs (or SRHT params, or Gaussian chunk keys) from
scratch for sk *and again* for desk.  FetchSGD (Rothchild et al. 2020) and
FedSKETCH (Haddadpour et al. 2020) instead sketch the *concatenated*
gradient into one contiguous buffer, making compression a single fused
memory-bound pass.  This module adopts that design (DESIGN.md §4):

* ``PackingPlan``        -- static layout, computed ONCE from the param
                            pytree + ``SketchConfig``: every leaf's flat
                            vector gets a slice of one contiguous
                            ``(d_total,)`` buffer and every leaf's sketch a
                            slice of one contiguous ``(b_total,)`` payload.
* ``derive_round_params``-- per-round hashes/signs/SRHT params/Gaussian
                            keys derived ONCE per (round, leaf) and shared
                            by sk and desk.  Leaves with identical (n, b)
                            are derived with a single vmapped PRNG call
                            (bit-identical to the per-leaf calls: threefry
                            streams depend only on the folded key).
* ``sk_packed``/``desk_packed`` -- fused single-jitted-pass sk/desk for all
                            three sketch families.  The default balanced
                            count-sketch family scatters nothing: its sk
                            adds each leaf's rows rotated by r_k
                            (contiguous slices, no gather); its desk is
                            still an element gather.
                            The "independent" family collapses the whole
                            tree to ONE segment-sum over a global hash
                            (leaf-local slot + payload offset); with
                            ``use_pallas`` its multi-client sk is ONE
                            Pallas launch over a (client, b-block, tile)
                            grid instead of O(G x num_leaves) kernel calls.

Per-leaf key derivation matches ``sketch_tree`` exactly (fold_in on the
leaf index), so packed and per-leaf paths produce identical sketches --
parity is enforced by tests/test_packed.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketch import (SketchConfig, _balanced_cs_params,
                               _balanced_desk_core, _balanced_sk_core,
                               _cs_hashes, _gaussian_desk, _gaussian_sk,
                               _keys, _srht_params, fwht, leaf_sketch_size,
                               next_pow2)
from repro.obs import spans

Pytree = Any


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Static layout of one pytree leaf inside the packed (d_total,) buffer."""
    shape: tuple[int, ...]
    dtype: Any
    n: int
    in_off: int


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One sketch unit: a leaf (per_tensor mode) or the whole packed vector
    (concat mode).  ``raw`` units are transmitted uncompressed (b == n)."""
    index: int                 # position in op/payload order
    in_off: int                # offset into the packed input buffer
    n: int                     # input length
    b: int                     # payload slots (== n when raw)
    pay_off: int               # offset into the packed payload
    raw: bool
    tag: Optional[int]         # fold_in tag (leaf index); None -> round key
    n2: int                    # next_pow2(n), used by srht


@dataclasses.dataclass(frozen=True)
class PackingPlan:
    """Static packing of a param pytree under one SketchConfig.

    Computed once (shapes only -- safe to build inside a jit trace); shared
    by every round.  ``b_total`` is the uplink payload length in slots.
    """
    cfg: SketchConfig
    treedef: Any
    leaves: tuple[LeafSpec, ...]
    ops: tuple[OpSpec, ...]
    d_total: int
    b_total: int

    @property
    def all_raw(self) -> bool:
        return all(op.raw for op in self.ops)


def shard_local_abstract(tree: Pytree, pspecs: Pytree,
                         axis_sizes) -> Pytree:
    """Per-device local shard shapes of ``tree`` under ``pspecs``.

    ``axis_sizes`` maps mesh axis name -> size (``dict(mesh.shape)``).
    Returns ``ShapeDtypeStruct`` leaves whose dim i is the global dim divided
    by the product of the mesh axes sharding it -- the leaf shapes a
    ``shard_map`` body sees.  Every sharded dim must divide evenly (the same
    precondition shard_map itself enforces)."""
    def local(leaf, spec):
        dims = []
        # a spec may be shorter than the leaf rank (trailing dims implicitly
        # replicated): pad with None so no dim is silently dropped
        spec = tuple(spec) + (None,) * (len(leaf.shape) - len(tuple(spec)))
        for d, e in zip(leaf.shape, spec):
            axes = () if e is None else (e if isinstance(e, tuple) else (e,))
            sz = 1
            for a in axes:
                sz *= axis_sizes[a]
            if d % sz:
                raise ValueError(
                    f"dim {d} of {leaf.shape} not divisible by mesh axes "
                    f"{axes} (size {sz})")
            dims.append(d // sz)
        return jax.ShapeDtypeStruct(tuple(dims), leaf.dtype)
    return jax.tree.map(local, tree, pspecs)


def make_sharded_packing_plan(cfg: SketchConfig, tree: Pytree, pspecs: Pytree,
                              axis_sizes) -> PackingPlan:
    """PackingPlan over the SHARD-LOCAL slices of ``tree`` (DESIGN §8).

    The mesh round sketches each leaf's local shard inside ``shard_map``
    (shard-local along the model/FSDP axes -- no all-gather of the d-dim
    delta), so the packed layout must be computed from the *local* shapes,
    not the global ones.  Built once outside any trace; per-leaf fold_in
    tags match the per-leaf reference path in
    ``launch.train.sharded_sketch_avg_desk`` exactly."""
    return make_packing_plan(cfg, shard_local_abstract(tree, pspecs,
                                                       axis_sizes))


def make_packing_plan(cfg: SketchConfig, tree: Pytree) -> PackingPlan:
    """Lay out every leaf of ``tree`` into the packed input/payload buffers."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    leaves, in_off = [], 0
    for l in flat:
        n = int(np.prod(l.shape)) if l.shape else 1
        leaves.append(LeafSpec(tuple(l.shape), l.dtype, n, in_off))
        in_off += n
    d_total = in_off

    ops, pay_off = [], 0
    if cfg.mode == "concat":
        b = d_total if cfg.kind == "none" else leaf_sketch_size(d_total, cfg)
        ops.append(OpSpec(0, 0, d_total, b, 0, b >= d_total, None,
                          next_pow2(d_total)))
        pay_off = b
    else:
        for i, spec in enumerate(leaves):
            n = spec.n
            b = n if cfg.kind == "none" else leaf_sketch_size(n, cfg)
            ops.append(OpSpec(i, spec.in_off, n, b, pay_off, b >= n, i,
                              next_pow2(n)))
            pay_off += b
    return PackingPlan(cfg, treedef, tuple(leaves), tuple(ops),
                       d_total, pay_off)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def pack_tree(plan: PackingPlan, tree: Pytree) -> jax.Array:
    """Flatten ``tree`` into the contiguous f32 (d_total,) buffer."""
    flat = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32) for l in flat])


def unpack_tree(plan: PackingPlan, flat: jax.Array, cast: bool = True) -> Pytree:
    """Slice the (d_total,) buffer back into leaf shapes (plan dtypes)."""
    out = []
    for spec in plan.leaves:
        v = flat[spec.in_off:spec.in_off + spec.n].reshape(spec.shape)
        out.append(v.astype(spec.dtype) if cast else v)
    return jax.tree_util.tree_unflatten(plan.treedef, out)


# ---------------------------------------------------------------------------
# per-round operator parameters (derived once, shared by sk and desk)
# ---------------------------------------------------------------------------

def _group_derive(key: jax.Array, group: list[OpSpec], fn):
    """Derive ``fn(key_op, n, b)`` for every op of an (n, b) group with ONE
    vmapped PRNG call -- bit-identical to the per-leaf fold_in chain
    (threefry streams depend only on the folded key).  Returns results with
    a leading group axis."""
    n, b = group[0].n, group[0].b
    if len(group) == 1 and group[0].tag is None:  # concat mode: round key
        return jax.tree.map(lambda x: x[None], fn(key, n, b))
    tags = jnp.asarray([op.tag for op in group], jnp.int32)
    ks = jax.vmap(lambda t: _keys(key, t))(tags)
    return jax.vmap(lambda k: fn(k, n, b))(ks)


def _grouped(ops) -> dict[tuple[int, int], list[OpSpec]]:
    groups: dict[tuple[int, int], list[OpSpec]] = {}
    for op in ops:
        if not op.raw:
            groups.setdefault((op.n, op.b), []).append(op)
    return groups


def derive_generation_params(plan: PackingPlan, base_key: jax.Array,
                             g: jax.Array) -> dict:
    """Re-derive generation round ``g``'s sketch operator from the run's
    base key: ``derive_round_params(plan, fold_in(base_key, g))``.

    This is the contract the async staleness buffers depend on (DESIGN §7):
    a delayed payload sketched in round g can only be desketched with round
    g's OWN operator (Property 1 linearity holds within one operator), and
    because every round key is ``fold_in(base_key, t)``, the operator is
    recomputable at pop time from ``(base_key, g)`` alone -- nothing but the
    payload needs storing.  Single source of the fold, shared by
    ``fed.async_buffer.make_async_round`` and the mesh ring buffer
    (``launch/train.py``)."""
    return derive_round_params(plan, jax.random.fold_in(base_key, g))


@jax.named_scope(spans.DERIVE)
def derive_round_params(plan: PackingPlan, key: jax.Array) -> dict:
    """Derive the round's sketch operator ONCE.

    The returned dict is consumed by both ``sk_packed`` and ``desk_packed``,
    so hashes/signs/SRHT params exist exactly once per (round, leaf) -- the
    per-leaf path re-derives them on each side of the round trip.
    """
    cfg = plan.cfg
    if cfg.kind == "none" or plan.all_raw:
        return {}

    if cfg.kind == "countsketch":
        if cfg.cs_hash == "balanced":
            params: list = [None] * len(plan.ops)
            for group in _grouped(plan.ops).values():
                rs, ss = _group_derive(key, group, _balanced_cs_params)
                for r, op in enumerate(group):
                    params[op.index] = (rs[r], ss[r])
            return {"bal": tuple(params)}
        h_parts: list = [None] * len(plan.ops)
        s_parts: list = [None] * len(plan.ops)
        for group in _grouped(plan.ops).values():
            hs, ss = _group_derive(key, group, _cs_hashes)
            for r, op in enumerate(group):
                h_parts[op.index] = hs[r] + op.pay_off
                s_parts[op.index] = ss[r]
        for op in plan.ops:
            if op.raw:
                h_parts[op.index] = op.pay_off + jnp.arange(op.n, dtype=jnp.int32)
                s_parts[op.index] = jnp.ones((op.n,), jnp.float32)
        return {"h": jnp.concatenate(h_parts), "s": jnp.concatenate(s_parts)}

    if cfg.kind == "srht":
        params: list = [None] * len(plan.ops)
        for group in _grouped(plan.ops).values():
            signs, idx = _group_derive(key, group,
                                       lambda k, n, b: _srht_params(k, n, b)[1:])
            for r, op in enumerate(group):
                params[op.index] = (signs[r], idx[r])
        return {"srht": tuple(params)}

    if cfg.kind == "gaussian":
        keys: list = [None] * len(plan.ops)
        for op in plan.ops:
            if not op.raw:
                keys[op.index] = key if op.tag is None else _keys(key, op.tag)
        return {"keys": tuple(keys)}

    raise ValueError(f"unknown sketch kind: {cfg.kind}")


# ---------------------------------------------------------------------------
# fused sk / desk over the packed buffers
# ---------------------------------------------------------------------------

def _srht_groups(plan: PackingPlan) -> dict[int, list[OpSpec]]:
    """Non-raw ops grouped by padded FWHT length (batched transform rows)."""
    groups: dict[int, list[OpSpec]] = {}
    for op in plan.ops:
        if not op.raw:
            groups.setdefault(op.n2, []).append(op)
    return groups


def _batched_fwht(cfg: SketchConfig, rows: jax.Array) -> jax.Array:
    """FWHT along the last axis of (..., L, n2) rows; Pallas when routed."""
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        lead = rows.shape[:-1]
        out = kops.fwht_rows(rows.reshape(-1, rows.shape[-1]))
        return out.reshape(lead + (rows.shape[-1],))
    return fwht(rows)


def sk_flat(plan: PackingPlan, rp: dict, flat: jax.Array) -> jax.Array:
    """Fused sk of the packed (d_total,) buffer -> (b_total,) payload."""
    cfg = plan.cfg
    if cfg.kind == "none" or plan.all_raw:
        return flat.astype(cfg.transport_dtype)

    if cfg.kind == "countsketch":
        if cfg.cs_hash == "balanced":
            parts: list = [None] * len(plan.ops)
            for op in plan.ops:
                v = flat[op.in_off:op.in_off + op.n]
                if op.raw:
                    parts[op.index] = v
                    continue
                r, s = rp["bal"][op.index]
                parts[op.index] = _balanced_sk_core(v, r, s, op.b)
            return jnp.concatenate(parts).astype(cfg.transport_dtype)
        x = flat * rp["s"]
        if cfg.use_pallas:
            from repro.kernels import ops as kops
            out = kops.countsketch(x, rp["h"], plan.b_total)
        else:
            out = jax.ops.segment_sum(x, rp["h"], num_segments=plan.b_total)
        return out.astype(cfg.transport_dtype)

    if cfg.kind == "srht":
        parts: list = [None] * len(plan.ops)
        for n2, group in _srht_groups(plan).items():
            rows = jnp.stack([
                jnp.pad(flat[op.in_off:op.in_off + op.n], (0, n2 - op.n))
                * rp["srht"][op.index][0] for op in group])
            u = _batched_fwht(cfg, rows) / jnp.sqrt(jnp.asarray(n2, jnp.float32))
            for r, op in enumerate(group):
                scale = jnp.sqrt(jnp.asarray(n2 / op.b, jnp.float32))
                parts[op.index] = u[r][rp["srht"][op.index][1]] * scale
        for op in plan.ops:
            if op.raw:
                parts[op.index] = flat[op.in_off:op.in_off + op.n]
        return jnp.concatenate(parts).astype(cfg.transport_dtype)

    if cfg.kind == "gaussian":
        parts = [None] * len(plan.ops)
        for op in plan.ops:
            v = flat[op.in_off:op.in_off + op.n]
            parts[op.index] = v if op.raw else _gaussian_sk(
                cfg, rp["keys"][op.index], v, op.b)
        return jnp.concatenate(parts).astype(cfg.transport_dtype)

    raise ValueError(f"unknown sketch kind: {cfg.kind}")


def desk_flat(plan: PackingPlan, rp: dict, payload: jax.Array) -> jax.Array:
    """Fused desk of the (b_total,) payload -> packed (d_total,) buffer."""
    cfg = plan.cfg
    s = payload.astype(jnp.float32)
    if cfg.kind == "none" or plan.all_raw:
        return s

    if cfg.kind == "countsketch":
        if cfg.cs_hash == "balanced":
            parts: list = [None] * len(plan.ops)
            for op in plan.ops:
                u = s[op.pay_off:op.pay_off + op.b]
                if op.raw:
                    parts[op.index] = u
                    continue
                r, sg = rp["bal"][op.index]
                parts[op.index] = _balanced_desk_core(u, r, sg, op.n)
            return jnp.concatenate(parts)
        return s[rp["h"]] * rp["s"]

    if cfg.kind == "srht":
        parts: list = [None] * len(plan.ops)
        for n2, group in _srht_groups(plan).items():
            rows = []
            for op in group:
                signs, idx = rp["srht"][op.index]
                scale = jnp.sqrt(jnp.asarray(n2 / op.b, jnp.float32))
                rows.append(jnp.zeros((n2,), jnp.float32).at[idx].add(
                    s[op.pay_off:op.pay_off + op.b] * scale))
            w = _batched_fwht(cfg, jnp.stack(rows)) \
                / jnp.sqrt(jnp.asarray(n2, jnp.float32))
            for r, op in enumerate(group):
                signs = rp["srht"][op.index][0]
                parts[op.index] = (w[r] * signs)[:op.n]
        for op in plan.ops:
            if op.raw:
                parts[op.index] = s[op.pay_off:op.pay_off + op.b]
        return jnp.concatenate(parts)

    if cfg.kind == "gaussian":
        parts = [None] * len(plan.ops)
        for op in plan.ops:
            u = s[op.pay_off:op.pay_off + op.b]
            parts[op.index] = u if op.raw else _gaussian_desk(
                cfg, rp["keys"][op.index], u, op.n)
        return jnp.concatenate(parts)

    raise ValueError(f"unknown sketch kind: {cfg.kind}")


# ---------------------------------------------------------------------------
# pytree-level entry points
# ---------------------------------------------------------------------------

def sk_packed(plan: PackingPlan, rp: dict, tree: Pytree) -> jax.Array:
    """Sketch a whole pytree in one fused pass -> (b_total,) payload."""
    return sk_flat(plan, rp, pack_tree(plan, tree))


@jax.named_scope(spans.DESK)
def desk_packed(plan: PackingPlan, rp: dict, payload: jax.Array) -> Pytree:
    """Desketch the (b_total,) payload back to the plan's pytree."""
    return unpack_tree(plan, desk_flat(plan, rp, payload))


@jax.named_scope(spans.SKETCH)
def sk_packed_clients(plan: PackingPlan, rp: dict, stacked: Pytree) -> jax.Array:
    """Sketch G stacked client trees (leaves (G, ...)) -> (G, b_total).

    For the independent-hash CountSketch family with ``use_pallas`` this is
    ONE batched Pallas launch over a (client, b-block, tile) grid; all
    other families run as a vmap of the fused pass (still one jitted
    dispatch for the whole tree, not per leaf).  For the default balanced
    family that pass is a loop over each leaf's rows, adding row k rotated
    by r_k into a (G, b) accumulator: contiguous slices with the client
    axis leading, no element gather.
    """
    cfg = plan.cfg
    flat2 = jax.vmap(lambda t: pack_tree(plan, t))(stacked)
    if (cfg.kind == "countsketch" and cfg.cs_hash == "independent"
            and cfg.use_pallas and not plan.all_raw):
        from repro.kernels import ops as kops
        out = kops.countsketch_clients(flat2 * rp["s"][None, :], rp["h"],
                                       plan.b_total)
        return out.astype(cfg.transport_dtype)
    return jax.vmap(lambda f: sk_flat(plan, rp, f))(flat2)


def sk_packed_clients_wsum(plan: PackingPlan, rp: dict, stacked: Pytree,
                           w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused sketch of a client chunk, reduced to its weighted payload sum.

    The streaming unit of work of the microbatch fold (DESIGN.md §12):
    sketch the ``mb`` stacked client trees (leaves ``(mb, ...)``) with the
    shared round operator and immediately reduce them to the ``(b_total,)``
    weighted payload sum plus the scalar weight sum, so no ``(G, b_total)``
    payload ever materializes outside one chunk.  Linearity (Property 1)
    makes the chunk-summed sketch exactly the sketch of the weighted delta
    sum, so folding these partial sums over chunks -- and then psumming
    across mesh client shards -- reproduces the cohort mean aggregation.
    """
    s = sk_packed_clients(plan, rp, stacked).astype(jnp.float32)
    with jax.named_scope(spans.MEAN):
        return jnp.sum(s * w[:, None].astype(s.dtype), axis=0), jnp.sum(w)


def roundtrip_packed(plan: PackingPlan, key: jax.Array, tree: Pytree) -> Pytree:
    """desk(sk(tree)) with round params derived exactly once."""
    rp = derive_round_params(plan, key)
    return desk_packed(plan, rp, sk_packed(plan, rp, tree))
