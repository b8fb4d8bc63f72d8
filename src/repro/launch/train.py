"""Distributed SAFL training / serving steps for the production mesh.

The FL topology maps onto the mesh (DESIGN §3): one client group per
(pod, data) index; the *sketched* uplink is a psum of b-dim vectors executed
inside a shard_map (so sketching is shard-local along the model axis -- no
all-gather of the d-dim delta ever happens).  The FedOpt baseline step
transmits raw deltas (an O(d) all-reduce) for roofline comparison.

Two drivers share one round core (DESIGN §8): the per-round jitted step
(``make_safl_train_step``; one host dispatch per round) and the scanned
multi-round driver (``make_safl_scan_fn`` / ``run_mesh_scan``; R rounds as
one ``lax.scan`` OUTSIDE the shard_map with donated
``(params, opt_state, data_state, key)`` carries, device-side sharded batch
sampling via ``mesh_sampler``, and chunked on-device loss history).  Both
are bit-identical per round (tests/test_mesh_scan.py).

Run as a module for a real (CPU-scale) training run:
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.adaptive import AdaConfig, apply_update, init_opt_state
from repro.core.packed import (PackingPlan, derive_generation_params,
                               derive_round_params, desk_flat,
                               make_sharded_packing_plan, pack_tree, sk_flat,
                               sk_packed_clients_wsum, unpack_tree)
from repro.core.safl import (SAFLConfig, chunk_clients, client_delta,
                             mask_weights, masked_mean, masked_mean_tree,
                             masked_psum_mean, resolve_microbatch)
from repro.core.sketch import (SKETCH_CHUNK_NUMEL, SketchConfig, desk_leaf,
                               desk_leaf_stacked, sk_leaf, sk_leaf_stacked)
from repro.fed.faults import corrupt_payload, take_rows
from repro.fed.faults import n_dropped as fault_n_dropped
from repro.fed.participation import check_policy_clients, is_weighted_mask
from repro.fed.robust import (carry_if_empty, divergence_flag,
                              sentinel_validity)
from repro.launch.driver import round_hook_kwargs
from repro.models.config import ModelConfig
from repro.models.model import decode_step, forward, loss_fn, param_shapes
from repro.models.sharding import param_pspecs

Pytree = Any


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def client_axes_of(mesh, topology: str) -> tuple[str, ...]:
    """Mesh axes that enumerate FL clients.

    cross_device: every (pod, data) index is a client (weights replicated
    over data, tensor-parallel over model).  cross_device_dp: same clients,
    but the client's OWN batch is data-parallel over the model axis with
    fully replicated weights (beyond-paper §Perf: trades per-layer TP
    activation collectives for one grad all-reduce -- the right regime for
    <=3B models).  cross_silo: each pod is one client (weights FSDP-sharded
    within the pod) -- the mapping for 100B+ configs."""
    if topology == "cross_silo":
        return tuple(a for a in ("pod",) if a in mesh.axis_names)
    return data_axes_of(mesh)


def num_clients_of(mesh, topology: str) -> int:
    axes = client_axes_of(mesh, topology)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# shard-local sketch -> b-dim psum -> desk  (the compressed uplink)
# ---------------------------------------------------------------------------

_SKETCH_CHUNK_NUMEL = SKETCH_CHUNK_NUMEL   # back-compat alias


def _collect(s, client_axes, w_loc, den):
    """The compressed uplink collective: ``pmean`` over the client axes when
    the round has no cohort mask, else the masked cohort mean fused into the
    SAME single collective (``core.safl.masked_psum_mean``: weighted local
    sum, one psum, divide by the global cohort weight / static HT
    denominator).  ``s`` keeps its leading local-client axis either way
    (size 1 after masking -- every shard holds the identical cohort mean),
    so the downstream desk/mean lowering is shared."""
    if w_loc is None:
        return jax.lax.pmean(s, client_axes) if client_axes else s
    return masked_psum_mean(s, w_loc, den, client_axes)


def _sketch_avg_desk_local(skcfg: SketchConfig, client_axes, deltas, key,
                           w_loc=None, den=None):
    """Per-leaf REFERENCE path, PER DEVICE inside shard_map.  deltas leaves:
    (G_loc, *local_shard).  Every cross-client collective in SAFL is the
    collect below -- b floats per tensor, not d.

    Leaves whose local shard exceeds SKETCH_CHUNK_NUMEL are sketched per
    slice of their leading (layer-stack) axis via lax.map: this bounds the
    hash/sign temporaries to one layer's worth and realizes the layer-wise
    sketching the paper's conclusion proposes.

    This is the ``plan=None`` fallback; the production route is the packed
    plan path below (same per-leaf fold_in chain, no per-round Python tree
    traversal), pinned bitwise equal by tests/test_mesh_scan.py.  Under a
    cohort mask (``w_loc``) the per-leaf route needs exactly one client row
    per shard (it folds the local client axis into the flattened leaf);
    multi-client shards take the packed route."""
    if w_loc is not None:
        g_loc = jax.tree_util.tree_leaves(deltas)[0].shape[0]
        if g_loc != 1:
            raise NotImplementedError(
                f"masked per-leaf sketch path needs one client row per "
                f"shard, got G_loc={g_loc}; use the packed plan route")
    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    out = []
    for i, leaf in enumerate(leaves):
        lk = jax.random.fold_in(key, i)
        lshape = leaf.shape[1:]                     # drop local client dim
        numel = 1
        for d in lshape:
            numel *= d
        n0 = lshape[0] if lshape else 1
        if numel > SKETCH_CHUNK_NUMEL and len(lshape) >= 2 and n0 > 1:
            vs = leaf.reshape(n0, numel // n0).astype(jnp.float32)
            s = sk_leaf_stacked(skcfg, lk, vs)                # (n0, b_sub)
            if w_loc is not None:   # masked uplink (one client row: s[None])
                s = masked_psum_mean(s[None], w_loc, den, client_axes)[0]
            elif client_axes:
                s = jax.lax.pmean(s, client_axes)  # <-- compressed uplink
            u = desk_leaf_stacked(skcfg, lk, s, numel // n0)
            out.append(u.reshape(leaf.shape))
            continue
        v = leaf.reshape(-1).astype(jnp.float32)
        s = sk_leaf(skcfg, lk, v)
        if w_loc is not None:
            s = masked_psum_mean(s[None], w_loc, den, client_axes)[0]
        elif client_axes:
            s = jax.lax.pmean(s, client_axes)      # <-- compressed uplink
        u = desk_leaf(skcfg, lk, s, v.shape[0])
        out.append(u.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def _sketch_avg_desk_local_packed(plan: PackingPlan, client_axes, deltas,
                                  key, w_loc=None, den=None, mb=None,
                                  codec=None, ax_sizes=()):
    """Plan-routed shard-local sketch, PER DEVICE inside shard_map.

    The static layout (``plan``, built once OUTSIDE the trace from the
    shard-local leaf shapes) replaces the per-leaf Python loop: the round's
    operator is derived ONCE (shared by sk and desk, per-leaf fold_in tags
    identical to the reference path), each local client row is packed into
    one contiguous buffer and compressed in one fused pass, and the collect
    moves ONE (G_loc, b_total) payload.  A cohort mask (``w_loc``) fuses
    into that same collective (masked weighted sum before the psum) and
    shrinks the payload rows to the single cohort mean.  Being trace-free
    state -- only the round key is traced -- this is what lets the
    multi-round scan carry the sketch path with zero per-round host work
    (DESIGN §8).

    ``mb`` (optional) streams the shard-local sketch stage over chunks of
    ``mb`` client rows (DESIGN §12): a ``lax.scan`` folds the fused
    pack->sketch of each chunk into a running weighted sketch-sum, so the
    (G_loc, b_total) payload is never materialized -- peak sketch memory is
    O(mb * b_total).  The fold then needs exactly ONE psum of the
    (b_total,) partial sum + its scalar weight over the client axes
    (sketch linearity / mergeability, Property 1) before the single desk.
    A non-dividing tail chunk is zero-padded with zero weight, which is
    exact under the weighted sum.

    ``codec`` (static ``fed.codec.CodecConfig``, DESIGN.md §13) quantizes
    the shard-local weighted sketch-sum -- ONE (b_total,) row per client
    shard -- immediately before the single psum, so what the collective
    moves is the encoded payload.  Quantize-before-reduce is a deliberate
    bias trade (documented in §13): the per-shard quantizers are
    conditionally unbiased, but the server mean of quantized partial sums
    is not the quantization of the mean; the codec path is its own program
    family either way.  The rounding uniforms key off the FLAT SHARD INDEX
    (``ax_sizes`` aligns with ``client_axes``), so each shard draws an
    independent, reproducible stream."""
    rp = derive_round_params(plan, key)
    if codec is not None:
        from repro.fed.codec import encode_decode
        cid = jnp.int32(0)
        for ax, n in zip(client_axes, ax_sizes):
            cid = cid * n + jax.lax.axis_index(ax)

        def _enc(S):
            return encode_decode(codec, key, S[None],
                                 client_ids=cid[None])[0][0]
    if mb is not None:
        g_loc = jax.tree_util.tree_leaves(deltas)[0].shape[0]
        w = jnp.ones((g_loc,), jnp.float32) if w_loc is None else \
            w_loc.astype(jnp.float32)
        n_mb = -(-g_loc // mb)
        pad = n_mb * mb - g_loc
        dc = chunk_clients(deltas, mb, pad)          # (n_mb, mb, ...)
        wc = jnp.pad(w, (0, pad)).reshape(n_mb, mb)  # pad rows weigh 0

        def fold(carry, xc):
            S, W = carry
            dS, dW = sk_packed_clients_wsum(plan, rp, xc["d"], xc["w"])
            return (S + dS, W + dW), None

        S0 = jnp.zeros((plan.b_total,), jnp.float32)
        (S, W), _ = jax.lax.scan(fold, (S0, jnp.float32(0.0)),
                                 {"d": dc, "w": wc})
        if codec is not None:   # encode what the collective moves (§13)
            S = _enc(S)
        if client_axes:
            S = jax.lax.psum(S, client_axes)
            W = jax.lax.psum(W, client_axes)
        denom = jnp.float32(den) if den is not None else \
            jnp.maximum(W, jnp.float32(1.0))
        mbar = S / denom
        u = desk_flat(plan, rp, mbar)
        out = unpack_tree(plan, u, cast=False)
        return jax.tree.map(lambda x: x[None], out)  # (1, ...): cohort mean
    flat = jax.vmap(lambda t: pack_tree(plan, t))(deltas)   # (G_loc, d_loc)
    s = jax.vmap(lambda f: sk_flat(plan, rp, f))(flat)      # (G_loc, b_tot)
    if codec is not None:
        # codec family: weighted-local-sum -> quantize -> the ONE psum;
        # same restructure the mb fold uses, so both branches encode the
        # identical (b_total,) partial sum per shard
        g_loc = s.shape[0]
        w = (jnp.ones((g_loc,), jnp.float32) if w_loc is None
             else w_loc.astype(jnp.float32))
        S = jnp.sum(s.astype(jnp.float32) * w[:, None], axis=0)
        W = jnp.sum(w)
        S = _enc(S)
        if client_axes:
            S = jax.lax.psum(S, client_axes)
            W = jax.lax.psum(W, client_axes)
        denom = jnp.float32(den) if den is not None else \
            jnp.maximum(W, jnp.float32(1.0))
        u = desk_flat(plan, rp, S / denom)
        out = unpack_tree(plan, u, cast=False)
        return jax.tree.map(lambda x: x[None], out)  # (1, ...): cohort mean
    s = _collect(s, client_axes, w_loc, den)   # <-- compressed uplink
    u = jax.vmap(lambda p: desk_flat(plan, rp, p))(s)
    return jax.vmap(lambda f: unpack_tree(plan, f, cast=False))(u)


def sharded_sketch_avg_desk(mesh, skcfg: SketchConfig, pspecs, deltas, key,
                            topology: str = "cross_device", plan=None,
                            part_mask=None, microbatch=None, codec=None):
    """Sketch each client delta (shard-local), cohort-mean over client axes,
    desketch.

    deltas leaves: (G, *param_shape), G sharded over the client axes; param
    dims sharded per ``pspecs``.  Returns the update tree with param
    sharding.  ``plan`` (optional) is the shard-local ``PackingPlan`` from
    ``core.packed.make_sharded_packing_plan``: when given, leaf sketching
    runs through the fused packed engine (one dispatch, operator derived
    once); ``plan=None`` keeps the per-leaf reference loop.  Both produce
    identical values for shards below the layer-chunk threshold
    (tests/test_mesh_scan.py pins this bitwise).

    ``part_mask`` (optional) is a repro.fed participation mask over the G
    clients -- a (G,) 0/1 array, or the weighted dict form of
    ``ImportanceParticipation``.  The mask is evaluated OUTSIDE the
    shard_map (scan body); here its weight vector enters sharded over the
    client axes and the aggregation becomes the masked cohort mean, fused
    into the same single collective the unmasked path uses
    (``core.safl.masked_psum_mean``).  An all-ones mask is pinned bitwise
    to ``part_mask=None``.

    ``microbatch`` (optional) streams the SHARD-LOCAL sketch stage over
    chunks of that many client rows (DESIGN §12): instead of materializing
    the (G_loc, b_total) payload, each shard folds per-chunk weighted
    sketch-sums and the collective shrinks to one psum of a (b_total,)
    partial sum plus a scalar weight.  Requires the packed ``plan``.
    ``None`` or >= the shard-local cohort keeps the materialized path
    bitwise untouched; the streamed fold is its own program family, equal
    to the materialized one up to float summation order.

    ``codec`` (static ``fed.codec.CodecConfig``) quantizes each shard's
    weighted sketch-sum before the one psum (DESIGN.md §13; requires the
    packed ``plan``; per-client error feedback does not exist at shard
    granularity, so ``codec.error_feedback`` raises -- pass
    ``CodecConfig(..., error_feedback=False)``).  ``codec=None`` routes at
    Python level, keeping the pinned programs byte-identical."""
    client_axes = client_axes_of(mesh, topology)
    lead = client_axes if client_axes else None
    in_specs = jax.tree.map(
        lambda ps: P(*((lead,) + tuple(ps))), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    out_specs = pspecs
    mb = None
    if microbatch is not None:
        g = jax.tree.leaves(deltas)[0].shape[0]
        g_loc = g // max(_axes_size(mesh, client_axes), 1)
        mb = resolve_microbatch(microbatch, g_loc)
        if mb is not None and plan is None:
            raise ValueError(
                "microbatch streaming needs the packed plan route; build "
                "one with make_sharded_packing_plan (per-leaf reference "
                "path folds the client axis leaf-by-leaf and cannot "
                "stream)")
    if codec is not None:
        if plan is None:
            raise ValueError(
                "the mesh payload codec needs the packed plan route; build "
                "one with make_sharded_packing_plan")
        if codec.error_feedback:
            raise ValueError(
                "the mesh uplink quantizes SHARD-LOCAL partial sums; "
                "per-client error feedback does not exist at that "
                "granularity -- use CodecConfig(..., error_feedback=False)")
    if plan is not None:
        fn = functools.partial(_sketch_avg_desk_local_packed, plan,
                               client_axes)
        if mb is not None:
            fn = functools.partial(fn, mb=mb)
        if codec is not None:
            fn = functools.partial(
                fn, codec=codec,
                ax_sizes=tuple(mesh.shape[ax] for ax in client_axes))
    else:
        fn = functools.partial(_sketch_avg_desk_local, skcfg, client_axes)

    if part_mask is None:
        def local(d, k):
            upd = fn(d, k)
            # fold the local client axis (size 1 when G == #client groups;
            # mean over it otherwise)
            return jax.tree.map(lambda u: u.mean(axis=0), upd)

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(in_specs, P()), out_specs=out_specs,
                             check_vma=False)(deltas, key)

    w = mask_weights(part_mask)                              # (G,)
    den = float(part_mask["den"]) if is_weighted_mask(part_mask) else None

    def local_masked(d, k, wl):
        upd = fn(d, k, wl, den)         # leaves (1, ...): the cohort mean
        return jax.tree.map(lambda u: u.mean(axis=0), upd)

    return jax.shard_map(local_masked, mesh=mesh,
                         in_specs=(in_specs, P(), P(lead)),
                         out_specs=out_specs, check_vma=False)(deltas, key, w)


def _sharded_sketch_guarded(mesh, plan: PackingPlan, pspecs, deltas, key,
                            topology: str, part_mask, fault_spec, sentinel):
    """The compressed uplink with the DESIGN.md §10 fusion chain applied
    inside the sketch shard_map: faults -> sentinels -> participation mask
    -> ONE payload psum.

    The fault spec and the mask enter REPLICATED (tiny (G,) vectors); each
    shard corrupts/vets its own client rows (``rows`` as in the staleness
    buffer) and the sentinel's cross-shard agreement costs one extra psum of
    two (G,) stats arrays over ALL mesh axes (``fed.robust
    .sentinel_validity`` -- a client is only valid if every model shard of
    its payload row is, or shards would divide by different cohort weights
    and desynchronize).  The payload itself still moves through exactly one
    psum over the client axes, with the fused effective weights.

    Returns ``(update_tree, eff_w (G,), n_rejected)`` -- the effective
    weight vector is what the caller's loss metric and empty-cohort
    fallback key off."""
    client_axes = client_axes_of(mesh, topology)
    all_axes = tuple(mesh.axis_names)
    G = num_clients_of(mesh, topology)
    lead = client_axes if client_axes else None
    in_specs = jax.tree.map(
        lambda ps: P(*((lead,) + tuple(ps))), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    w0 = (jnp.ones((G,), jnp.float32) if part_mask is None
          else mask_weights(part_mask))
    den = float(part_mask["den"]) if is_weighted_mask(part_mask) else None

    def local(*a):
        d_tree, k, w_full = a[:3]
        spec = a[3] if fault_spec is not None else None
        rp = derive_round_params(plan, k)
        flat = jax.vmap(lambda tr: pack_tree(plan, tr))(d_tree)
        s = jax.vmap(lambda f: sk_flat(plan, rp, f))(flat)   # (G_loc, b_loc)
        g_loc = s.shape[0]
        cid = 0
        for ax in client_axes:
            cid = cid * mesh.shape[ax] + jax.lax.axis_index(ax)
        rows = cid * g_loc + jnp.arange(g_loc)
        w_arr = w_full
        if spec is not None:
            s = corrupt_payload(take_rows(spec, rows), s)
            w_arr = w_full * spec["arrive"]
        if sentinel is not None:
            valid, s, n_rej = sentinel_validity(
                sentinel, s, rows, w_arr, G, all_axes)
            w_eff = w_arr * valid.astype(jnp.float32)
        else:
            n_rej = jnp.float32(0.0)
            w_eff = w_arr
        wl = w_eff[rows]
        sw = jnp.sum(s * wl[:, None], axis=0, keepdims=True)
        if client_axes:
            sw = jax.lax.psum(sw, client_axes)   # <-- the ONE payload psum
        if den is not None:     # static Horvitz-Thompson denominator
            mean = sw / jnp.asarray(den, sw.dtype)
        else:                   # w_eff is replicated: no weight psum needed
            mean = sw / jnp.maximum(jnp.sum(w_eff), 1.0).astype(sw.dtype)
        u = desk_flat(plan, rp, mean[0])
        return unpack_tree(plan, u, cast=False), w_eff, n_rej

    args = [deltas, key, w0]
    specs = [in_specs, P(), P()]
    if fault_spec is not None:
        args.append(fault_spec)
        specs.append({k: P() for k in fault_spec})
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=(pspecs, P(), P()),
                         check_vma=False)(*args)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def client_deltas_sharded(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
                          topology: str, params, batch, eta):
    """Per-client local training, manual over the client axes and AUTO/GSPMD
    over the model (+FSDP) axes: each client group runs K local SGD steps on
    its own replica with zero cross-client communication.  Returns
    (deltas (G, *param), losses (G,))."""
    from repro.models.sharding import manual_axes
    loss = lambda p, b: loss_fn(model_cfg, p, b)
    caxes = client_axes_of(mesh, topology)

    # in dp mode all model-axis hints are disabled so GSPMD freely
    # propagates the batch-over-model sharding
    haxes = caxes + (("model",) if topology == "cross_device_dp" else ())

    def body(p, b_local):
        with manual_axes(haxes):
            mb = jax.tree.map(lambda x: x[0], b_local)      # drop local G=1
            if topology == "cross_device_dp":
                mb = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, P(None, "model") if x.ndim >= 2 else P()), mb)
            delta, l = client_delta(safl_cfg, loss, p, mb, eta)
        delta = jax.tree.map(lambda d: d[None], delta)
        return delta, l[None]

    if not caxes:                                            # 1 client total
        return body(params, batch)

    if topology == "cross_silo":
        # XLA's SPMD partitioner cannot handle partial-manual shard_map over
        # the pod axis of a 3-axis mesh (hard CHECK failure); the vmap
        # formulation partitions cleanly here because the client count (2
        # pods) matches the pod axis exactly and weights carry no pod axis.
        # The vmapped G dim already sits on the client axes, so the model's
        # batch hints must not claim them too: with them, a (pod, data=1,
        # model) mesh aborts XLA's SPMD partitioner (device-group CHECK).
        with manual_axes(caxes):
            def one(mb):
                return client_delta(safl_cfg, loss, params, mb, eta)
            deltas, losses = jax.vmap(one)(batch)
        return deltas, losses

    lead = P(caxes)
    b_specs = jax.tree.map(lambda x: lead, batch)
    d_specs = jax.tree.map(lambda x: lead, params)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), b_specs),
                         out_specs=(d_specs, lead),
                         axis_names=set(caxes), check_vma=False)(params, batch)


def _mesh_pspecs(model_cfg: ModelConfig, topology: str):
    abstract = jax.eval_shape(
        lambda: jax.tree.map(lambda s: jnp.zeros(s, model_cfg.dtype),
                             param_shapes(model_cfg),
                             is_leaf=lambda x: isinstance(x, tuple)))
    if topology == "cross_device_dp":
        pspecs = jax.tree.map(lambda p: P(*((None,) * len(p))),
                              param_pspecs(abstract),
                              is_leaf=lambda x: isinstance(x, P))
    else:
        pspecs = param_pspecs(abstract, fsdp=(topology == "cross_silo"))
    return abstract, pspecs


def _mesh_plan(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
               topology: str):
    """(abstract, pspecs, plan) for one mesh round family.

    The shard-local ``PackingPlan`` is built HERE, once, outside any trace
    (``core.packed.make_sharded_packing_plan``), so only the round operator
    (``derive_round_params``) depends on the round key -- the sketch path is
    trace-free state a multi-round ``lax.scan`` can thread through its
    carry.  Models with a local shard above ``SKETCH_CHUNK_NUMEL`` keep the
    per-leaf reference path instead (``plan=None``): its layer-chunked
    lax.map bounds the operator temporaries to one layer slice, which the
    whole-leaf packed route would not."""
    from repro.core.packed import shard_local_abstract
    abstract, pspecs = _mesh_pspecs(model_cfg, topology)
    plan = None
    if safl_cfg.sketch.kind != "none":
        local_abs = shard_local_abstract(abstract, pspecs, dict(mesh.shape))
        if all(int(np.prod(l.shape)) <= SKETCH_CHUNK_NUMEL
               for l in jax.tree.leaves(local_abs)):
            plan = make_sharded_packing_plan(safl_cfg.sketch, abstract,
                                             pspecs, dict(mesh.shape))
    return abstract, pspecs, plan


def _buffer_specs(mesh, topology: str):
    """Partition specs of the mesh staleness ring buffer.

    ``buf`` is the global client-major payload ring -- generation dim
    unsharded, client dim over the client axes, the packed payload dim over
    every remaining mesh axis (each model/FSDP shard owns its slice of the
    shard-local sketch, mirroring how the payload exists inside the sketch
    shard_map).  ``bufw`` drops the payload dim."""
    caxes = client_axes_of(mesh, topology)
    other = tuple(a for a in mesh.axis_names if a not in caxes)
    return (P(None, caxes, other if other else None), P(None, caxes)), caxes


def init_mesh_async_state(model_cfg: ModelConfig, safl_cfg: SAFLConfig,
                          acfg, mesh, params,
                          topology: str = "cross_device") -> dict:
    """Server opt state + the mesh staleness ring buffer (scan-carry
    resident), for ``run_mesh_scan(..., buffer=acfg)`` /
    ``make_safl_train_step(..., buffer=acfg)``.

    The ring holds the last ``D = max_delay + 1`` generation rounds'
    per-client ``(G, b_total)`` sketch payloads (sharded: clients over the
    client axes, payload over the model/FSDP axes -- see
    ``_buffer_specs``) plus the matching 0/1 cohort weights."""
    _, _, plan = _mesh_plan(model_cfg, safl_cfg, mesh, topology)
    if plan is None:
        raise ValueError(
            "the mesh staleness buffer stores packed (G, b_total) sketch "
            "payloads: it needs the packed plan route (sketch.kind != "
            "'none' and every local shard <= SKETCH_CHUNK_NUMEL)")
    (buf_spec, bufw_spec), caxes = _buffer_specs(mesh, topology)
    if not caxes:
        raise ValueError("the mesh staleness buffer needs client mesh axes")
    G = num_clients_of(mesh, topology)
    n_other = 1
    for a in mesh.axis_names:
        if a not in caxes:
            n_other *= mesh.shape[a]
    D = acfg.buffer_rounds
    buf = jax.device_put(
        jnp.zeros((D, G, plan.b_total * n_other), jnp.float32),
        NamedSharding(mesh, buf_spec))
    bufw = jax.device_put(jnp.zeros((D, G), jnp.float32),
                          NamedSharding(mesh, bufw_spec))
    return {"opt": init_opt_state(safl_cfg.server, params),
            "buf": buf, "bufw": bufw}


def sharded_sketch_buffered(mesh, acfg, plan: PackingPlan, pspecs, deltas,
                            buf, bufw, round_key, base_key, t,
                            topology: str = "cross_device", part_mask=None,
                            fault_spec=None, sentinel=None):
    """FedBuff-style staleness-buffered uplink on the mesh (DESIGN §9).

    One shard_map over the whole mesh: sketch the local client rows with
    round t's operator, push the ``(G_loc, b_total)`` payload (and the
    round's cohort weights) into the ring slot ``t % D``, recompute every
    generation's arrivals from the deterministic delay policy
    (``fed.async_buffer.arrival_weight`` -- pure in (g, c, seed), nothing
    stored but payloads), reduce each arriving generation in ITS OWN sketch
    space, run ONE fused psum over the client axes for all generations'
    partial sums, and desketch each generation with its own operator
    re-derived from ``fold_in(base_key, g)`` INSIDE the shard_map
    (``core.packed.derive_generation_params``).  Returns
    ``(update_tree, buf, bufw)``.

    With ``delay="zero"`` the d > 0 arrival groups are statically empty and
    the round lowers to the synchronous masked path -- the bitwise parity
    pin of tests/test_mesh_scan.py.

    ``fault_spec``/``sentinel`` (DESIGN.md §10) corrupt and then vet the
    payload BEFORE the push -- the ring must never store a poisoned row, or
    it would re-emit it at every later pop of that generation; dropped and
    rejected clients store weight 0, exactly like non-participation.  The
    guarded call additionally returns ``(W, n_rejected)``:
    ``(update_tree, buf, bufw, W, n_rejected)``."""
    from repro.fed.async_buffer import arrival_weight
    if is_weighted_mask(part_mask):
        raise TypeError(
            "the mesh staleness buffer stores 0/1 cohort masks per "
            "generation; weighted (importance-sampling) masks are not "
            "supported -- use a 0/1 participation policy")
    client_axes = client_axes_of(mesh, topology)
    (buf_spec, bufw_spec), _ = _buffer_specs(mesh, topology)
    if not client_axes:
        raise ValueError("the mesh staleness buffer needs client mesh axes")
    G = num_clients_of(mesh, topology)
    D = acfg.buffer_rounds
    lead = client_axes
    in_specs = jax.tree.map(
        lambda ps: P(*((lead,) + tuple(ps))), pspecs,
        is_leaf=lambda x: isinstance(x, P))

    guarded = fault_spec is not None or sentinel is not None
    all_axes = tuple(mesh.axis_names)

    def local(*a):
        d_tree, buf, bufw, rk, base, t, wv = a[:7]
        spec = a[7] if fault_spec is not None else None
        rp_t = derive_round_params(plan, rk)
        flat = jax.vmap(lambda tr: pack_tree(plan, tr))(d_tree)
        sks = jax.vmap(lambda f: sk_flat(plan, rp_t, f))(flat) \
            .astype(jnp.float32)                        # (G_loc, b_loc)
        g_loc = sks.shape[0]
        # global client ids of this shard's rows (row-major over the client
        # axes, matching how shard_map splits the leading G dim)
        cid = 0
        for a_ in client_axes:
            cid = cid * mesh.shape[a_] + jax.lax.axis_index(a_)
        rows = cid * g_loc + jnp.arange(g_loc)
        if not guarded:
            w_loc, n_rej = wv, None      # wv entered sharded over clients
        else:
            # wv entered REPLICATED: faults/sentinels fuse into the full
            # (G,) weight vector BEFORE the push (§10 order), so the ring
            # only ever stores vetted payloads and their fused weights
            w_full = wv
            if spec is not None:
                sks = corrupt_payload(take_rows(spec, rows), sks)
                w_full = w_full * spec["arrive"]
            if sentinel is not None:
                valid, sks, n_rej = sentinel_validity(
                    sentinel, sks, rows, w_full, G, all_axes)
                w_full = w_full * valid.astype(jnp.float32)
            else:
                n_rej = jnp.float32(0.0)
            w_loc = w_full[rows]
        # -- push: generation t claims slot t % D (its previous tenant,
        # generation t - D, fully drained by round t - 1) --
        slot_t = jnp.mod(t, D)
        buf = buf.at[slot_t].set(sks)
        bufw = bufw.at[slot_t].set(w_loc)
        # -- pop: per-generation shard-local partial sums; the d = 0 group
        # reads the just-pushed sks/w_loc directly (CSE; with the "zero"
        # delay policy the d > 0 groups are statically empty, so the round
        # constant-folds to the synchronous masked program) --
        weighted = []                   # (W_loc, S_loc, rp_g) per delay
        for d in range(D):              # static: D is a config constant
            g = t - d
            if acfg.delay == "zero" and d > 0:
                continue
            if d == 0:
                payload, w_in = sks, w_loc
            else:
                payload = buf[jnp.mod(g, D)]
                w_in = bufw[jnp.mod(g, D)]
            w = w_in * arrival_weight(acfg, g, d, G)[rows]
            S_loc = jnp.sum(w[:, None] * payload, axis=0)   # (b_loc,)
            rp_g = rp_t if d == 0 else derive_generation_params(plan, base, g)
            weighted.append((jnp.sum(w), S_loc, rp_g))
        # ONE fused collective for every generation's partial sums: D
        # payloads of b_total floats -- still sketch-dimensional uplink
        S_stack = jnp.stack([s for _, s, _ in weighted])
        W_stack = jnp.stack([wd for wd, _, _ in weighted])
        S_stack, W_stack = jax.lax.psum((S_stack, W_stack), client_axes)
        W = jnp.sum(W_stack)
        W_safe = jnp.where(W > 0, W, 1.0)   # no arrivals -> zero update
        upd_flat = sum(desk_flat(plan, rp_g, S_stack[i] / W_safe)
                       for i, (_, _, rp_g) in enumerate(weighted))
        update = unpack_tree(plan, upd_flat, cast=False)
        if guarded:
            return update, buf, bufw, W, n_rej
        return update, buf, bufw

    w = part_mask if part_mask is not None \
        else jnp.ones((G,), jnp.float32)
    args = [deltas, buf, bufw, round_key, base_key, t, w]
    specs = [in_specs, buf_spec, bufw_spec, P(), P(), P(),
             P() if guarded else P(lead)]
    out_specs = (pspecs, buf_spec, bufw_spec)
    if guarded:
        out_specs = out_specs + (P(), P())
    if fault_spec is not None:
        args.append(fault_spec)
        specs.append({k: P() for k in fault_spec})
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=out_specs, check_vma=False)(*args)


def _make_round_core(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
                     topology: str = "cross_device", *, participation=None,
                     buffer=None, faults=None, sentinel=None,
                     telemetry=None, microbatch=None, codec=None):
    """The typed-key SAFL mesh round:
    ``core(params, state, batch, round_key, **hook_kwargs) ->
    (params, state, loss_or_metrics)``.

    The static sketch layout comes from ``_mesh_plan`` (built once, outside
    any trace); ``make_safl_train_step`` wraps this with the key_data
    calling convention and ``make_safl_scan_fn`` scans it.  The repro.fed
    hooks ride the same core for both drivers: ``participation`` masks the
    server aggregation over the round's sampled cohort (mask evaluated by
    the CALLER in the scan body, handed in as ``part_mask``), ``buffer``
    (an ``fed.async_buffer.AsyncConfig``) swaps the synchronous uplink for
    the mesh staleness ring buffer, with ``state`` the dict from
    ``init_mesh_async_state`` and ``t``/``base_key`` threaded in by the
    caller (``launch.driver.round_hook_kwargs``), and ``faults``/
    ``sentinel`` (``fed.faults`` / ``fed.robust``, DESIGN.md §10) inject
    and contain payload faults inside the sketch shard_map (the caller
    threads the traced per-round ``fault_spec``).  Hookless and
    participation/buffer-only cores return a loss SCALAR (the PR-4/PR-5
    contract, bitwise-pinned); fault/sentinel cores return a metrics dict
    (``loss`` + ``n_dropped``/``n_rejected``/``diverged`` counters).

    ``telemetry`` (static ``repro.obs.Telemetry``) switches any core to the
    metrics-dict return and adds the probe scalars (DESIGN.md §11).  The
    Δ̄-based probes are computed OUTSIDE the sketch shard_map from the
    sharded global delta tree, so GSPMD inserts the O(d) reductions they
    need -- an explicitly opt-in cost the compressed uplink never pays.
    ``telemetry=None`` (the default) leaves every program byte-identical to
    the pinned trajectories.

    ``microbatch`` (static, optional) streams the shard-local sketch stage
    over client-row chunks (DESIGN §12) -- plain (hookless /
    participation-only) sketched cores only: the staleness buffer and the
    fault/sentinel guard need the materialized per-client payload rows, and
    telemetry probes read the materialized delta tree, so combining them
    raises.  ``None`` / >= the shard-local cohort is the materialized path,
    bitwise-pinned.

    ``codec`` (static ``fed.codec.CodecConfig``, DESIGN.md §13) quantizes
    each shard's sketch partial-sum before the one psum -- plain sketched
    cores only (buffer/faults/sentinel operate on per-client payload rows
    that the shard-sum codec never sees; telemetry probes are computed
    from unquantized deltas; fedopt has no sketch payload), and only
    without per-client error feedback (shard granularity).  A codec core
    returns a metrics dict whose ``uplink_bits`` is the MEASURED encoded
    size: one payload row per client shard crossing the collective."""
    abstract, pspecs, plan = _mesh_plan(model_cfg, safl_cfg, mesh, topology)
    G = num_clients_of(mesh, topology)
    guarded = faults is not None or sentinel is not None
    if codec is not None:
        if buffer is not None or guarded:
            raise NotImplementedError(
                "the mesh payload codec quantizes shard-local partial "
                "sums; the staleness buffer and the fault/sentinel guard "
                "operate on materialized per-client payload rows -- run "
                "those hooks without codec=")
        if telemetry is not None:
            raise ValueError(
                "telemetry probes read the unquantized delta tree; drop "
                "telemetry= or codec=")
        if safl_cfg.sketch.kind == "none":
            raise ValueError(
                "the payload codec quantizes the packed sketch uplink; "
                "fedopt (sketch.kind='none') has no sketch payload")
        if plan is None:
            raise ValueError(
                "the mesh payload codec needs the packed plan route "
                "(every local shard <= SKETCH_CHUNK_NUMEL)")
        if codec.error_feedback:
            raise ValueError(
                "the mesh uplink quantizes SHARD-LOCAL partial sums; "
                "per-client error feedback does not exist at that "
                "granularity -- use CodecConfig(..., error_feedback=False)")
    if microbatch is not None:
        resolve_microbatch(microbatch, G)   # reject mb <= 0 at build time
        if buffer is not None or guarded:
            raise NotImplementedError(
                "mesh microbatch streaming folds the payload before any "
                "per-client row exists; the staleness buffer and the "
                "fault/sentinel guard operate on materialized payload "
                "rows -- run those hooks without microbatch=")
        if telemetry is not None:
            raise ValueError(
                "telemetry probes read the materialized cohort delta "
                "tree; drop telemetry= or microbatch=")
        if safl_cfg.sketch.kind == "none":
            raise ValueError(
                "mesh microbatch streaming folds in sketch space; "
                "fedopt (sketch.kind='none') has no sketch payload")
        if plan is None:
            raise ValueError(
                "mesh microbatch streaming needs the packed plan route "
                "(every local shard <= SKETCH_CHUNK_NUMEL)")
    if participation is not None:
        check_policy_clients(participation, G, "mesh driver")
    if guarded:
        if safl_cfg.sketch.kind == "none":
            raise ValueError(
                "fault injection / payload sentinels act on the packed "
                "sketch uplink; fedopt (sketch.kind='none') has no sketch "
                "payload")
        if plan is None:
            raise ValueError(
                "the mesh fault/sentinel hooks need the packed plan route "
                "(every local shard <= SKETCH_CHUNK_NUMEL)")
        if faults is not None and faults.num_clients != G:
            raise ValueError(
                f"fault policy covers {faults.num_clients} clients, the "
                f"mesh topology has {G}")
    if buffer is not None:
        if safl_cfg.sketch.kind == "none":
            raise ValueError("the staleness buffer aggregates in sketch "
                             "space; fedopt (sketch.kind='none') cannot "
                             "ride it")
        if plan is None:
            raise ValueError(
                "the mesh staleness buffer needs the packed plan route "
                "(every local shard <= SKETCH_CHUNK_NUMEL)")

    def core(params, state, batch, key, *, t=None, base_key=None,
             part_mask=None, fault_spec=None):
        eta = jnp.asarray(safl_cfg.client_lr, jnp.float32)
        deltas, losses = client_deltas_sharded(
            model_cfg, safl_cfg, mesh, topology, params, batch, eta)

        def _tel(m, *, update, st, mask):
            # telemetry=None is the identity on the return value, so the
            # disabled-path programs stay byte-identical (static gate)
            if telemetry is None:
                return m
            from repro.obs.telemetry import telemetry_probes
            m = dict(m) if isinstance(m, dict) else {"loss": m}
            m.update(telemetry_probes(telemetry, deltas=deltas,
                                      update=update, part_mask=mask,
                                      state=st))
            return m

        if buffer is not None:
            if not guarded:
                update, buf, bufw = sharded_sketch_buffered(
                    mesh, buffer, plan, pspecs, deltas, state["buf"],
                    state["bufw"], key, base_key, t, topology,
                    part_mask=part_mask)
                params, opt = apply_update(
                    safl_cfg.server, state["opt"], params, update)
                new_state = {"opt": opt, "buf": buf, "bufw": bufw}
                return (params, new_state,
                        _tel(masked_mean(losses, part_mask), update=update,
                             st=new_state, mask=part_mask))
            update, buf, bufw, W, n_rej = sharded_sketch_buffered(
                mesh, buffer, plan, pspecs, deltas, state["buf"],
                state["bufw"], key, base_key, t, topology,
                part_mask=part_mask, fault_spec=fault_spec,
                sentinel=sentinel)
            new_params, opt = apply_update(
                safl_cfg.server, state["opt"], params, update)
            loss = masked_mean(losses, part_mask)
            metrics = {"loss": loss, "arrival_weight": W,
                       "n_rejected": n_rej}
            if fault_spec is not None:
                metrics["n_dropped"] = fault_n_dropped(fault_spec, part_mask)
            if sentinel is not None:
                # no-arrival round: carry the server through unchanged
                new_params, opt = jax.tree.map(
                    lambda nw, o: jnp.where(W > 0, nw, o),
                    (new_params, opt), (params, state["opt"]))
                metrics["diverged"] = divergence_flag(sentinel, loss)
            new_state = {"opt": opt, "buf": buf, "bufw": bufw}
            return (new_params, new_state,
                    _tel(metrics, update=update, st=new_state,
                         mask=part_mask))
        if guarded:
            update, eff_w, n_rej = _sharded_sketch_guarded(
                mesh, plan, pspecs, deltas, key, topology, part_mask,
                fault_spec, sentinel)
            eff_mask = ({**part_mask, "w": eff_w}
                        if is_weighted_mask(part_mask) else eff_w)
            new_params, new_state = apply_update(
                safl_cfg.server, state, params, update)
            loss = masked_mean(losses, eff_mask)
            metrics = {"loss": loss, "n_rejected": n_rej}
            if fault_spec is not None:
                metrics["n_dropped"] = fault_n_dropped(fault_spec, part_mask)
            if sentinel is not None:
                new_params, new_state = carry_if_empty(
                    eff_mask, (new_params, new_state), (params, state))
                metrics["diverged"] = divergence_flag(sentinel, loss)
            return new_params, new_state, _tel(metrics, update=update,
                                               st=new_state, mask=eff_mask)
        if safl_cfg.sketch.kind == "none":
            # FedOpt baseline: raw-delta mean = O(d) all-reduce over clients
            if part_mask is None:
                update = jax.tree.map(lambda d: jnp.mean(d, axis=0), deltas)
            else:
                update = masked_mean_tree(deltas, part_mask)
        else:
            update = sharded_sketch_avg_desk(
                mesh, safl_cfg.sketch, pspecs, deltas, key, topology,
                plan=plan, part_mask=part_mask, microbatch=microbatch,
                codec=codec)
        params, state = apply_update(safl_cfg.server, state, params, update)
        loss = (jnp.mean(losses) if part_mask is None
                else masked_mean(losses, part_mask))
        if codec is not None:
            # measured wire size: one encoded (b_total,) partial-sum row
            # per client shard crosses the collective (a static count --
            # masked-out clients still contribute their zeroed rows to the
            # shard sum, so every shard transmits)
            n_shards = 1
            for ax in client_axes_of(mesh, topology):
                n_shards *= mesh.shape[ax]
            m = {"loss": loss, "uplink_bits": jnp.float32(
                codec.payload_bits(plan.b_total) * n_shards)}
            return params, state, m
        return params, state, _tel(loss, update=update, st=state,
                                   mask=part_mask)

    return core, pspecs


def make_safl_train_step(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
                         topology: str = "cross_device", *,
                         participation=None, buffer=None, faults=None,
                         sentinel=None, telemetry=None, microbatch=None,
                         codec=None):
    """SAFL round on the mesh.  batch leaves: (G, K, mb, ...) with G = number
    of FL clients (data-parallel groups or pods, per ``topology``).

    Without hooks the step keeps the PR-4 signature
    ``step(params, opt_state, batch, key_data)`` where ``key_data`` is the
    ROUND key's data.  With any repro.fed hook
    (``participation=``/``buffer=``/``faults=``/``sentinel=``) the step
    needs the absolute round index and the run's base key --
    ``step(params, state, batch, base_key_data, t)`` -- and derives the
    round key as ``fold_in(base, t)`` itself, the exact chain the scanned
    driver uses; ``state`` is the ``init_mesh_async_state`` dict when
    buffered.  Fault/sentinel steps return a metrics DICT in place of the
    loss scalar (see ``_make_round_core``)."""
    core, pspecs = _make_round_core(model_cfg, safl_cfg, mesh, topology,
                                    participation=participation,
                                    buffer=buffer, faults=faults,
                                    sentinel=sentinel, telemetry=telemetry,
                                    microbatch=microbatch, codec=codec)
    hooked = (participation is not None or buffer is not None
              or faults is not None or sentinel is not None)
    if not hooked:
        def step(params, opt_state, batch, key_data):
            return core(params, opt_state, batch,
                        jax.random.wrap_key_data(key_data))
    else:
        def step(params, state, batch, key_data, t):
            base = jax.random.wrap_key_data(key_data)
            kw, _ = round_hook_kwargs(t, base, None, participation,
                                      buffer is not None, faults)
            return core(params, state, batch, jax.random.fold_in(base, t),
                        **kw)

    return step, pspecs


def _fedopt_cfg(safl_cfg: SAFLConfig) -> SAFLConfig:
    return SAFLConfig(sketch=SketchConfig(kind="none"),
                      server=safl_cfg.server,
                      client_lr=safl_cfg.client_lr,
                      local_steps=safl_cfg.local_steps,
                      remat_local=safl_cfg.remat_local)


def make_fedopt_train_step(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
                           topology: str = "cross_device", *,
                           participation=None, buffer=None, faults=None,
                           sentinel=None, telemetry=None, microbatch=None,
                           codec=None):
    """Uncompressed FedOPT baseline: raw-delta mean = O(d) all-reduce."""
    return make_safl_train_step(model_cfg, _fedopt_cfg(safl_cfg), mesh,
                                topology, participation=participation,
                                buffer=buffer, faults=faults,
                                sentinel=sentinel, telemetry=telemetry,
                                microbatch=microbatch, codec=codec)


# ---------------------------------------------------------------------------
# multi-pod scanned mesh driver: scan OUTSIDE the shard_map round (DESIGN §8)
# ---------------------------------------------------------------------------

def mesh_sampler(mesh, sampler, topology: str = "cross_device"):
    """Wrap a device sampler (``init_state()/sample(state, t)``) so its
    ``(G, K, mb, ...)`` batches land sharded on the mesh per
    ``batch_pspecs`` -- G over the client axes, mb over ``data`` in
    cross_silo.  The constraint is pure layout (tokens bitwise unchanged),
    so mesh and single-host trajectories stay comparable."""
    from repro.data.device import ShardedSampler
    st = jax.eval_shape(sampler.init_state)
    babs = jax.eval_shape(sampler.sample, st,
                          jax.ShapeDtypeStruct((), jnp.int32))[1]
    shardings = to_shardings(mesh, batch_pspecs(babs, mesh, topology))
    return ShardedSampler(sampler, shardings)


def make_safl_scan_fn(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
                      topology: str = "cross_device", *, sampler,
                      num_rounds: int, donate: bool = True,
                      participation=None, buffer=None, faults=None,
                      sentinel=None, telemetry=None, microbatch=None,
                      codec=None):
    """Jit ``num_rounds`` SAFL mesh rounds as ONE ``lax.scan`` dispatch.

    The scan sits OUTSIDE the shard_map round: each scanned step draws its
    batch on device (``sampler.sample(data_state, t)``, sharded via
    ``mesh_sampler``), derives the round key as ``fold_in(key, t)`` inside
    the scan body, and runs the same round core the per-round jitted step
    uses -- so scanned and per-round mesh trajectories are bit-identical
    (tests/test_mesh_scan.py).  The ``(params, opt_state, data_state, key)``
    carry is DONATED: large models update in place across chunks, and the
    host pays one dispatch + one metric fetch per chunk instead of per
    round.

    ``participation``/``buffer`` are the repro.fed hooks
    (``launch.driver.round_hook_kwargs``, DESIGN §9): the cohort mask is
    evaluated IN THE SCAN BODY as a pure function of the absolute round
    index and consumed inside the round's sketch shard_map; a buffered run
    carries the staleness ring (``init_mesh_async_state``) in place of the
    bare opt state, donated like every other carry leaf.  An all-ones mask
    and a delay=0 buffer are pinned bitwise to the hookless scan.
    ``faults``/``sentinel`` (DESIGN.md §10) inject and contain payload
    faults; their chunk history grows the per-round ``n_dropped``/
    ``n_rejected``/``diverged`` counters next to the loss (disabled hooks
    leave the scan program -- and the pinned trajectories -- untouched).

    Signature of the returned fn:
        ``(params, opt_state, data_state, key_data, t0) ->
           (params, opt_state, data_state, key_data, hist)``
    ``t0`` is a traced scalar so successive chunks of one length share one
    executable; ``hist["loss"]`` is the chunk's on-device loss history.
    Returns ``(chunk_fn, pspecs)``.
    """
    core, pspecs = _make_round_core(model_cfg, safl_cfg, mesh, topology,
                                    participation=participation,
                                    buffer=buffer, faults=faults,
                                    sentinel=sentinel, telemetry=telemetry,
                                    microbatch=microbatch, codec=codec)

    def chunk(params, opt_state, data_state, key_data, t0):
        def body(carry, t):
            params, opt_state, dstate, kd = carry
            dstate, batch = sampler.sample(dstate, t)
            base = jax.random.wrap_key_data(kd)
            kw, _ = round_hook_kwargs(t, base, None, participation,
                                      buffer is not None, faults)
            rk = jax.random.fold_in(base, t)
            params, opt_state, m = core(params, opt_state, batch, rk, **kw)
            # fault/sentinel cores return the full metrics dict; everything
            # else keeps the bare-loss history (static distinction)
            return ((params, opt_state, dstate, kd),
                    m if isinstance(m, dict) else {"loss": m})

        (params, opt_state, data_state, key_data), hist = jax.lax.scan(
            body, (params, opt_state, data_state, key_data),
            t0 + jnp.arange(num_rounds, dtype=jnp.int32))
        return params, opt_state, data_state, key_data, hist

    return (jax.jit(chunk, donate_argnums=(0, 1, 2, 3) if donate else ()),
            pspecs)


def make_fedopt_scan_fn(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh,
                        topology: str = "cross_device", *, sampler,
                        num_rounds: int, donate: bool = True,
                        participation=None, buffer=None, faults=None,
                        sentinel=None, telemetry=None, microbatch=None,
                        codec=None):
    """Scanned uncompressed FedOPT mesh rounds (``sketch.kind == "none"``:
    the raw-delta O(d) all-reduce inside the same scan layout)."""
    return make_safl_scan_fn(model_cfg, _fedopt_cfg(safl_cfg), mesh,
                             topology, sampler=sampler,
                             num_rounds=num_rounds, donate=donate,
                             participation=participation, buffer=buffer,
                             faults=faults, sentinel=sentinel,
                             telemetry=telemetry, microbatch=microbatch,
                             codec=codec)


def run_mesh_scan(model_cfg: ModelConfig, safl_cfg: SAFLConfig, mesh, sampler,
                  params, opt_state, *, rounds: int, key,
                  topology: str = "cross_device", chunk_size: int = 0,
                  start_round: int = 0, donate: bool = True, on_chunk=None,
                  participation=None, buffer=None, faults=None,
                  sentinel=None, telemetry=None, stream=None,
                  microbatch=None, codec=None):
    """Run ``rounds`` mesh rounds in scanned chunks (the multi-pod analogue
    of ``launch.driver.run_scan``).

    ``chunk_size`` bounds rounds per dispatch (0 = all in one); metrics
    cross to the host once per chunk and ``on_chunk(t_done, params,
    opt_state, chunk_hist)`` runs between chunks.  ``start_round`` resumes a
    ``(t, key)`` checkpoint cursor mid-trajectory (every per-round stream --
    data, cohorts, delays, sketch operators -- is a pure function of the
    absolute round index under ``key``).

    **Hook contract** (the full set, with each hook's pin class -- see
    DESIGN.md appendix "Pinning methodology" for the taxonomy):

    * ``participation=`` (sampling policy, DESIGN §9): the per-round cohort
      mask is evaluated in the scan body and consumed inside the round's
      sketch shard_map.  ``None`` is bitwise-neutral; an all-ones 0/1 mask
      reproduces the hookless trajectory bitwise
      (tests/test_mesh_scan.py).
    * ``buffer=`` (an ``fed.async_buffer.AsyncConfig``): ``opt_state`` must
      then be the ``init_mesh_async_state`` dict (the staleness ring rides
      the donated scan carry).  A delay=0 buffer is bitwise the hookless
      scan; nonzero delays are their own program family.
    * ``faults=`` / ``sentinel=`` (DESIGN.md §10): fault-injection /
      payload-sentinel hooks; their history carries ``n_dropped``/
      ``n_rejected``/``diverged`` counters next to the loss, which is what
      the rollback supervisor (``launch.supervisor``) watches.  Disabled
      (``None``) they are bitwise-neutral; enabled they form their own
      family (extra scan outputs shift XLA fusion).
    * ``telemetry=`` (static ``repro.obs.Telemetry``, DESIGN §11): adds the
      in-graph probe keys to the history; its own family when enabled,
      bitwise-neutral when ``None``.
    * ``stream=`` (a ``repro.obs.shards.ShardWriter``): switches to
      streamed per-chunk JSONL shards + wall-time span events and skips the
      in-memory accumulation, exactly as in ``launch.driver.run_scan`` (the
      returned ``history`` is then ``{}``).  Host-side only -- never
      changes the compiled round program.
    * ``microbatch=`` (static int, DESIGN §12): streams each shard's sketch
      stage over chunks of that many client rows (plain sketched cores
      only -- combining with buffer/faults/sentinel/telemetry raises).
      ``None`` or >= the shard-local cohort keeps the materialized program
      bitwise-pinned; a streaming value is its own family, allclose to the
      materialized path.
    * ``codec=`` (static ``fed.codec.CodecConfig``, DESIGN.md §13):
      quantizes each shard's sketch partial-sum before the one psum (plain
      sketched cores only; requires ``error_feedback=False`` -- per-client
      EF does not exist at shard granularity).  ``None`` is
      bitwise-neutral; an enabled codec is its own family and its history
      reports the MEASURED ``uplink_bits``.

    Returns ``(params, opt_state, history)`` with host-side
    ``(rounds - start_round,)`` arrays (key set:
    ``launch.driver.HISTORY_KEYS``)."""
    chunk_size = int(chunk_size) or int(rounds)
    data_state = sampler.init_state()
    # host copy of the (invariant) base key: the donated key carry comes
    # back as a pass-through output of its own donated buffer, so each chunk
    # gets a fresh device copy instead of rethreading a deleted array
    kd_host = np.asarray(jax.random.key_data(key))
    compiled: dict[int, Callable] = {}
    hists = []
    t = int(start_round)
    while t < rounds:
        n = min(chunk_size, rounds - t)
        fresh = n not in compiled
        if fresh:               # tail chunk of a different length re-jits
            compiled[n], _ = make_safl_scan_fn(
                model_cfg, safl_cfg, mesh, topology, sampler=sampler,
                num_rounds=n, donate=donate, participation=participation,
                buffer=buffer, faults=faults, sentinel=sentinel,
                telemetry=telemetry, microbatch=microbatch, codec=codec)
        t_wall = time.perf_counter()
        params, opt_state, data_state, _, hist = compiled[n](
            params, opt_state, data_state, jnp.asarray(kd_host),
            jnp.asarray(t, jnp.int32))
        if stream is not None:
            from repro.obs.shards import host_fetch
            hist = host_fetch(hist)            # async copy, ONE fetch
            dt = time.perf_counter() - t_wall
            stream.write_chunk(t, hist)
            stream.write_span(t, t + n, dt, compile=fresh)
        else:
            hist = jax.tree.map(np.asarray, hist)  # ONE fetch per chunk
            hists.append(hist)
        t += n
        if on_chunk is not None:
            on_chunk(t, params, opt_state, hist)
    if not hists:   # streamed, or resumed at start_round == rounds
        return params, opt_state, {}
    history = jax.tree.map(lambda *xs: np.concatenate(xs), *hists)
    return params, opt_state, history


def run_mesh_host_loop(step, sampler, params, opt_state, *, rounds: int, key,
                       start_round: int = 0, donate: bool = True,
                       participation=None, buffer=None, faults=None,
                       sentinel=None):
    """One-jitted-dispatch-per-round mesh reference with the scanned
    driver's EXACT key/batch sequence: round t consumes
    ``key_data(fold_in(key, t))`` and ``sampler.sample(state, t)``.
    ``step`` is the per-round fn from ``make_safl_train_step`` /
    ``make_fedopt_train_step``.  benchmarks/run.py times this against
    ``run_mesh_scan`` (mesh/<algo> vs mesh/<algo>_scan); the trajectories
    agree bitwise.

    With the repro.fed hooks, build ``step`` with the SAME
    ``participation=``/``buffer=``/``faults=``/``sentinel=`` and pass them
    here too: the hooked step takes ``(params, state, batch, base_key_data,
    t)`` and re-derives the round key / cohort mask / fault spec itself, so
    this loop feeds it the base key and the absolute round index instead of
    the folded round key.  Fault/sentinel steps emit a metrics dict per
    round; the history stacks every key."""
    data_state = sampler.init_state()
    sample = jax.jit(sampler.sample)
    jstep = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    hooked = (participation is not None or buffer is not None
              or faults is not None or sentinel is not None)
    kd_base = np.asarray(jax.random.key_data(key))
    hists = []
    for t in range(int(start_round), rounds):
        data_state, batch = sample(data_state, jnp.asarray(t, jnp.int32))
        if hooked:
            params, opt_state, m = jstep(
                params, opt_state, batch, jnp.asarray(kd_base),
                jnp.asarray(t, jnp.int32))
        else:
            kd = jax.random.key_data(jax.random.fold_in(key, t))
            params, opt_state, m = jstep(params, opt_state, batch, kd)
        if not isinstance(m, dict):
            m = {"loss": m}
        hists.append(jax.tree.map(np.asarray, m))  # blocks every round
    return params, opt_state, jax.tree.map(lambda *xs: np.stack(xs), *hists)


def make_prefill_step(model_cfg: ModelConfig):
    def step(params, batch):
        h, _ = forward(model_cfg, params, batch, remat=False)
        head = (params["embed"].T if model_cfg.tie_embeddings
                else params["lm_head"])
        return h[:, -1] @ head                      # (B, V) last-token logits
    return step


def make_serve_step(model_cfg: ModelConfig):
    def step(params, cache, tokens, pos):
        return decode_step(model_cfg, params, cache, tokens, pos)
    return step


# ---------------------------------------------------------------------------
# sharding spec helpers for jit in_shardings
# ---------------------------------------------------------------------------

def batch_pspecs(batch_tree, mesh, topology: str = "cross_device") -> Pytree:
    """Train-batch specs: (G, K, mb, ...).  cross_device shards G over
    (pod, data); cross_silo shards G over pod and mb over data."""
    caxes = client_axes_of(mesh, topology)
    lead = caxes if caxes else None
    if topology == "cross_silo":
        inner = "data" if "data" in mesh.axis_names else None
        return jax.tree.map(
            lambda x: P(*((lead, None, inner) + (None,) * (x.ndim - 3))),
            batch_tree)
    if topology == "cross_device_dp":
        return jax.tree.map(
            lambda x: P(*((lead, None, "model") + (None,) * (x.ndim - 3))),
            batch_tree)
    return jax.tree.map(
        lambda x: P(*((lead,) + (None,) * (x.ndim - 1))), batch_tree)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def infer_batch_pspecs(batch_tree, data_axes, mesh=None) -> Pytree:
    """Inference batch: leading batch dim over (pod, data); left replicated
    when the batch does not divide the axes (e.g. long_500k with B=1)."""
    def spec(x):
        axes = data_axes
        if mesh is not None and x.shape[0] % _axes_size(mesh, data_axes):
            axes = None
        return P(*((axes,) + (None,) * (x.ndim - 1)))
    return jax.tree.map(spec, batch_tree)


def cache_pspecs(cache_tree, data_axes, mesh=None) -> Pytree:
    """KV caches are sequence-sharded over the model axis (flash-decoding
    style partial softmax via GSPMD); SSM state shards d_inner.  The batch
    dim falls back to replicated when it does not divide the data axes."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    specs = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        nd = leaf.ndim
        baxes = data_axes
        if mesh is not None and leaf.shape[1] % _axes_size(mesh, data_axes):
            baxes = None
        if name in ("k", "v", "xk", "xv"):       # (nb, B, S, Hk, hd)
            sp = (None, baxes, "model", None, None)
        elif name in ("ckv", "kpe"):             # (nb, B, S, r)
            sp = (None, baxes, "model", None)
        elif name == "h":                        # (nb, B, di, ds)
            sp = (None, baxes, "model", None)
        elif name == "conv":                     # (nb, B, kw-1, di)
            sp = (None, baxes, None, "model")
        else:
            sp = (None,) * nd
        if mesh is not None:
            # drop any axis a dim cannot divide (e.g. whisper's 1500-frame
            # cross cache on a 16-way model axis)
            fixed = []
            for dim, e in zip(leaf.shape, sp[:nd]):
                if e is None:
                    fixed.append(None)
                    continue
                axes = e if isinstance(e, tuple) else (e,)
                fixed.append(e if dim % _axes_size(mesh, axes) == 0 else None)
            sp = tuple(fixed)
        specs.append(P(*sp[:nd]))
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_pspecs(server: AdaConfig, pspecs) -> dict:
    out = {"step": P()}
    for k in ("m", "v", "vhat"):
        if (server.name in ("amsgrad", "adam", "sgdm") and k == "m") or \
           (server.name in ("amsgrad", "adam", "adagrad") and k == "v") or \
           (server.name == "amsgrad" and k == "vhat"):
            out[k] = pspecs
    return out


def to_shardings(mesh, pspec_tree):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# runnable single-host trainer (examples / integration tests use this)
# ---------------------------------------------------------------------------

def train_loop(model_cfg: ModelConfig, safl_cfg: SAFLConfig, data,
               rounds: int, *, batch_per_client: int = 8, log_every: int = 10,
               seed: int = 0, scan: bool = True, chunk_size: int = 0):
    """CPU-scale SAFL training on real (synthetic-dataset) batches.

    When ``data`` supports device-side sampling (``device_sampler``) the
    whole run executes as scanned on-device chunks with donated carries
    (launch/driver.py, DESIGN.md §6); metrics come back once per chunk.
    Other datasets fall back to the host-driven loop (still with donated
    params/opt buffers, so no per-round copy)."""
    from repro.core.packed import make_packing_plan
    from repro.core.safl import init_safl, safl_round
    key = jax.random.key(seed)
    from repro.models.model import init_params
    params = init_params(model_cfg, key)
    opt = init_safl(safl_cfg, params)
    loss = lambda p, b: loss_fn(model_cfg, p, b)
    # static sketch layout built ONCE, outside any trace
    plan = make_packing_plan(safl_cfg.sketch, params)
    round_fn = functools.partial(safl_round, safl_cfg, loss, plan=plan)

    if scan and hasattr(data, "device_sampler"):
        from repro.launch.driver import run_scan
        sampler = data.device_sampler(batch_per_client, safl_cfg.local_steps)

        def on_chunk(t_done, _params, _opt, hist):
            if log_every:
                print(f"round {t_done - 1:4d}  loss {hist['loss'][-1]:.4f}")

        params, opt, hist = run_scan(
            round_fn, sampler, params, opt, rounds=rounds, key=key,
            chunk_size=chunk_size or (log_every or rounds),
            on_chunk=on_chunk)
        return params, opt, [float(x) for x in hist["loss"]]

    round_jit = jax.jit(round_fn, donate_argnums=(0, 1))
    history = []
    for t in range(rounds):
        batch = data.round_batch(batch_per_client, safl_cfg.local_steps, t)
        params, opt, m = round_jit(params, opt, batch, jax.random.fold_in(key, t))
        history.append(float(m["loss"]))
        if log_every and (t % log_every == 0 or t == rounds - 1):
            print(f"round {t:4d}  loss {history[-1]:.4f}")
    return params, opt, history


def _main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--sketch", default="countsketch")
    ap.add_argument("--ratio", type=float, default=0.1)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.data import BigramLMData, LMDataConfig
    cfg = get_config(args.arch, smoke=args.smoke)
    safl = SAFLConfig(
        sketch=SketchConfig(kind=args.sketch, ratio=args.ratio),
        server=AdaConfig(name="amsgrad", lr=0.003),
        client_lr=0.05, local_steps=args.local_steps)
    data = BigramLMData(LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=32, num_clients=args.clients))
    train_loop(cfg, safl, data, args.rounds)


if __name__ == "__main__":
    _main()


def flat_tp_pspecs(pspecs, params_abs=None) -> Pytree:
    """Beyond-paper serving layout: fold the data axis into the model axis
    (256-way pure TP), sharding every weight's CONTRACTING (input) dim.

    v2 after a refuted iteration (EXPERIMENTS §Perf H3): sharding output/head
    dims conflicts with the sequence-sharded KV cache and makes GSPMD
    all-gather the cache (1.8 TB/step observed).  Contracting-dim sharding
    keeps weights fully resident AND the cache sequence-sharded; every
    matmul just all-reduces its (tiny, batch x features) decode activation.
    MoE experts stay expert-sharded (resident) with token all-to-all."""
    _W = {"wq", "wk", "wv", "wo", "wi", "wg", "w_dq", "w_uq", "w_dkv",
          "w_kr", "w_uk", "w_uv", "lm_head", "mtp_head", "router",
          "x_proj", "dt_proj", "out_proj", "wx", "wz"}

    def conv(path, p):
        name = str(getattr(path[-1], "key", path[-1]))
        parent = str(getattr(path[-2], "key", path[-2])) if len(path) > 1 \
            else ""
        nd = len(p)
        tp = ("data", "model")
        if name in ("wi", "wg", "wo") and parent in ("moe",) and nd >= 3:
            # stacked experts (nb, E, in, out): shard E (resident experts)
            lead = nd - 3
            return P(*((None,) * lead + (tp, None, None)))
        if name == "embed":
            return P(tp, None)
        if name in _W and nd >= 2:
            # shard the contracting dim (second-to-last) -> output psum
            return P(*((None,) * (nd - 2) + (tp, None)))
        return P(*((None,) * nd))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree_util.tree_unflatten(
        treedef, [conv(path, p) for path, p in flat])


def flat_tp_cache_pspecs(cache_tree, mesh=None) -> Pytree:
    """Cache layout for flat-TP serving: sequence dim over (data, model),
    batch replicated."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    specs = []
    tp = ("data", "model")
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        nd = leaf.ndim
        if name in ("k", "v", "xk", "xv"):
            sp = (None, None, tp, None, None)
        elif name in ("ckv", "kpe"):
            sp = (None, None, tp, None)
        elif name == "h":
            sp = (None, None, tp, None)
        elif name == "conv":
            sp = (None, None, None, tp)
        else:
            sp = (None,) * nd
        if mesh is not None:
            fixed = []
            for dim, e in zip(leaf.shape, sp[:nd]):
                if e is None:
                    fixed.append(None)
                    continue
                axes = e if isinstance(e, tuple) else (e,)
                fixed.append(e if dim % _axes_size(mesh, axes) == 0 else None)
            sp = tuple(fixed)
        specs.append(P(*sp[:nd]))
    return jax.tree_util.tree_unflatten(treedef, specs)
