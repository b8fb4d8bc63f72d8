"""SAFL: Sketched Adaptive Federated Learning (paper Algorithm 1).

One SAFL round (faithful to Alg. 1):

  1. every client c syncs to the global iterate x_{t,0} and runs K local SGD
     steps with client lr eta:       x_{t,k} = x_{t,k-1} - eta * g_{t,k-1}
  2. client c uplinks the *sketched* local model delta
         m̄_t^c = sk(x_{t,0} - x_{t,K})          (b floats, not d)
  3. the server averages sketches   m̄_t = mean_c m̄_t^c   (linearity => this
     equals the sketch of the averaged delta; no server-side re-compression)
  4. server ADA_OPT (Alg. 2) consumes desk(m̄_t); the b-dim m̄_t is downlinked
     and every client replays the identical, deterministic server update, so
     all replicas stay synchronized.

Mesh mapping (DESIGN.md §3): a "client" is one data-parallel group of the
``(pod, data, model)`` mesh.  The client axis G is carried explicitly in the
batch (leading axis, sharded over (pod, data)); the sketch average over G is
a plain ``mean`` over one packed **(G, b_total)** payload that GSPMD lowers
to a single all-reduce of **b_total floats** -- the compressed uplink the
paper buys, in one collective instead of one per tensor.  Baselines that transmit raw deltas
(FedAvg / FedOpt) all-reduce O(d) instead; the roofline collective term shows
the gap directly.

The same round function serves the paper-scale simulation (G = 5 clients on
one device, exactly the paper's §5 setup) and the multi-pod production mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.adaptive import AdaConfig, apply_update, init_opt_state
from repro.core.packed import (derive_round_params, desk_packed,
                               make_packing_plan, sk_packed_clients)
from repro.core.sketch import SketchConfig
from repro.obs import spans

Pytree = Any
LossFn = Callable[[Pytree, Any], jax.Array]  # (params, batch) -> scalar loss


@dataclasses.dataclass(frozen=True)
class SAFLConfig:
    sketch: SketchConfig = SketchConfig()
    server: AdaConfig = AdaConfig()
    client_lr: float = 0.1          # eta
    local_steps: int = 1            # K
    remat_local: bool = True        # jax.checkpoint around the local grad


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(lambda x, y: (x.astype(jnp.float32)
                                      - y.astype(jnp.float32)), a, b)


def mask_weights(mask) -> jax.Array:
    """The (G,) per-client weight vector of a participation mask.

    Plain (G,) arrays pass through; *weighted* masks -- dicts
    ``{"w": (G,) weights, "den": static denominator, "n": cohort size}``, as
    emitted by ``fed.participation.ImportanceParticipation`` -- contribute
    their weight vector.  A weight of 0 means "not sampled" in both forms.
    """
    return mask["w"] if isinstance(mask, dict) else mask


def masked_mean(x: jax.Array, mask) -> jax.Array:
    """Mean of ``x`` over its leading (client) axis, restricted to ``mask``.

    ``mask`` is a (G,) participation mask (1.0 = sampled).  ``mask=None``
    falls back to ``jnp.mean`` -- and an all-ones mask reproduces that path
    BITWISE: ``1.0 * x`` is exact, the axis-0 reduction lowers identically,
    and the denominator is the same float G (participation policies
    guarantee >=1 sampled client, so the max() guard never rewrites it).

    A weighted mask (dict form, see ``mask_weights``) computes
    ``sum(w * x) / den`` with the STATIC denominator the policy supplies --
    the Horvitz-Thompson form importance sampling needs (dividing by the
    random weight sum would turn the unbiased estimator into a ratio
    estimator).
    """
    if mask is None:
        return jnp.mean(x, axis=0)
    w = mask_weights(mask)
    m = w.reshape(w.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
    if isinstance(mask, dict):
        return jnp.sum(x * m, axis=0) / jnp.asarray(mask["den"], x.dtype)
    den = jnp.maximum(jnp.sum(w), 1.0).astype(x.dtype)
    return jnp.sum(x * m, axis=0) / den


def masked_mean_tree(tree: Pytree, mask) -> Pytree:
    """``masked_mean`` over every leaf (leaves have leading client axis G)."""
    return jax.tree.map(lambda x: masked_mean(x, mask), tree)


def masked_psum_mean(x: jax.Array, w_loc: jax.Array, den,
                     client_axes) -> jax.Array:
    """``masked_mean`` distributed over shard_map client axes.

    ``x`` is a shard-local ``(G_loc, ...)`` block of the global client-major
    payload and ``w_loc`` the matching ``(G_loc,)`` slice of the cohort
    weights.  Computes the global cohort mean with the SAME collective count
    as the unmasked uplink: weighted local sum over the shard's client rows,
    ONE psum over the client axes (plus a scalar weight psum), divide.
    Returns a ``(1, ...)`` row (every shard holds the identical mean).

    ``den=None`` divides by the global weight sum (the 0/1-mask cohort
    mean); a static ``den`` is the Horvitz-Thompson denominator of a
    weighted mask (``core.safl.masked_mean`` semantics).  Bitwise pin: with
    an all-ones mask and one client row per shard this lowers to
    ``psum(x) / n`` -- exactly what ``lax.pmean`` computes -- so the masked
    route reproduces the unmasked trajectory bit for bit
    (tests/test_mesh_scan.py)."""
    w = w_loc.reshape((w_loc.shape[0],) + (1,) * (x.ndim - 1)).astype(x.dtype)
    sw = jnp.sum(x * w, axis=0, keepdims=True)
    if den is None:
        wsum = jnp.sum(w_loc)
        if client_axes:
            sw = jax.lax.psum(sw, client_axes)
            wsum = jax.lax.psum(wsum, client_axes)
        return sw / jnp.maximum(wsum, 1.0).astype(x.dtype)
    if client_axes:
        sw = jax.lax.psum(sw, client_axes)
    return sw / jnp.asarray(den, x.dtype)


def masked_where_tree(mask, new: Pytree, old: Pytree) -> Pytree:
    """Per-client state select: sampled clients take ``new`` leaves, the rest
    keep ``old`` (leaves (G, ...)).  Used for error-feedback memories under
    partial participation; ``mask=None`` (and, bitwise, an all-ones mask)
    returns ``new`` unchanged.  Weighted masks select on ``w > 0``."""
    if mask is None:
        return new
    w = mask_weights(mask)
    def sel(n, o):
        m = w.reshape(w.shape + (1,) * (n.ndim - 1))
        return jnp.where(m > 0, n, o)
    return jax.tree.map(sel, new, old)


@jax.named_scope(spans.CLIENT)
def client_delta(cfg: SAFLConfig, loss_fn: LossFn, params: Pytree,
                 microbatches: Pytree, eta: jax.Array) -> tuple[Pytree, jax.Array]:
    """K local SGD steps for ONE client; returns (x_0 - x_K, mean local loss).

    ``microbatches`` leaves have leading axis K (one slice per local step).
    """
    grad_fn = jax.value_and_grad(loss_fn)
    if cfg.remat_local:
        grad_fn = jax.checkpoint(grad_fn)

    def step(p, mb):
        loss, g = grad_fn(p, mb)
        p = jax.tree.map(
            lambda x, gi: (x.astype(jnp.float32)
                           - eta * gi.astype(jnp.float32)).astype(x.dtype),
            p, g)
        return p, loss

    p_final, losses = jax.lax.scan(step, params, microbatches)
    return tree_sub(params, p_final), jnp.mean(losses)


# ---------------------------------------------------------------------------
# streamed client-microbatch aggregation (DESIGN.md §12)
# ---------------------------------------------------------------------------

def resolve_microbatch(microbatch, num_clients: int):
    """Static routing of the streamed-aggregation knob (DESIGN.md §12).

    ``None`` -- or any chunk size covering the whole cohort -- selects the
    materialized single-chunk path, UNTOUCHED from the pinned program: a
    fold with one chunk is semantically the existing round, so the knob
    routes at Python level and the pinned bitwise trajectories survive by
    construction.  A chunk size below ``num_clients`` returns the validated
    int and selects the streamed fold, which is its own program family
    (pinned within itself, allclose to the materialized path).
    """
    if microbatch is None:
        return None
    mb = int(microbatch)
    if mb <= 0:
        raise ValueError(f"microbatch must be a positive int, got {microbatch}")
    if mb >= num_clients:
        return None
    return mb


def chunk_clients(tree: Pytree, mb: int, pad: int) -> Pytree:
    """Zero-pad the leading client axis by ``pad`` rows and reshape every
    leaf to ``(n_mb, mb, ...)`` microbatch chunks (scan xs layout).  The pad
    rows are masked out by the fold (weight 0 AND statically zeroed payload
    -- see ``streamed_sketch_round``), so any ``mb`` is valid: a non-dividing
    ``G % mb`` costs one masked tail chunk, never a reordered reduction."""
    def f(x):
        if pad:
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((-1, mb) + x.shape[1:])
    return jax.tree.map(f, tree)


def _pad_fault_spec(spec: dict, pad: int) -> dict:
    """Extend a (G,) fault spec with ``pad`` NEUTRAL rows (arrive, honest
    scale, no corruption): pad clients carry weight 0, and a neutral spec
    keeps their zeroed payload finite so 0-weight rows contribute exactly
    +0.0 to the fold."""
    if not pad:
        return spec
    neutral = {"arrive": 1.0, "nan": False, "inf": False, "scale": 1.0}
    return {k: jnp.pad(v, (0, pad), constant_values=neutral[k])
            for k, v in spec.items()}


def streamed_sketch_round(cfg: SAFLConfig, client_fn, params: Pytree,
                          opt_state: dict, batch: Pytree,
                          round_key: jax.Array, mb: int, *,
                          lr_scale: jax.Array | float = 1.0, plan=None,
                          part_mask=None, fault_spec=None, sentinel=None,
                          telemetry=None,
                          codec=None) -> tuple[Pytree, dict, dict]:
    """One sketched round as a fold over client microbatches (DESIGN.md §12).

    Instead of materializing the ``(G, d_total)`` delta stack and the
    ``(G, b_total)`` payload, a ``lax.scan`` processes ``mb`` clients per
    step -- ``client_fn(batch_slice) -> (delta_tree, loss)`` for ONE client,
    vmapped over the chunk -- and carries only the running weighted
    sketch-sum, weight-sum and loss-sum: peak payload memory is
    ``O(mb * b_total)``, independent of G.  Exactness rests on sketch
    linearity (Property 1): the sum of per-chunk sketch sums IS the sketch
    of the weighted delta sum, so the single desketch at the end sees the
    same cohort mean the materialized path computes (equal up to f32
    summation order -- the streamed family is pinned within itself, see
    ``resolve_microbatch``).

    The repro.fed hook contract is preserved per-microbatch against the
    GLOBAL client index: the (G,) participation weights and the (G,) fault
    spec are sliced to rows ``[i*mb, (i+1)*mb)`` of chunk i, which is exact
    because every per-client stream is a pure function of the absolute
    client index (DESIGN.md §7/§10).  The §10 fusion order (faults ->
    sentinels -> mask -> one reduction) runs inside each chunk, except the
    norm-outlier sentinel: its median is a GLOBAL cohort statistic, so
    ``norm_mult > 0`` runs a two-pass fold (pass 1 streams per-client norm/
    finite/loss stats, the median + verdicts are computed between passes,
    pass 2 deterministically recomputes deltas and accumulates the payload
    sum under the final weights) -- 2x client compute is the price of a
    global statistic under O(mb) memory.

    Non-dividing ``G % mb`` pads a masked tail chunk: pad rows carry weight
    0 AND a statically zeroed payload/loss (pad positions are known at
    trace time), so not even a NaN produced by the synthetic zero batch can
    leak into the sums.

    ``codec`` (static ``fed.codec.CodecConfig``, threaded like ``plan``)
    quantize-dequantizes each chunk's payload rows BEFORE the fault/
    sentinel stages (DESIGN.md §13): the rounding uniforms key off the
    GLOBAL client index, so the fold draws exactly the uniforms the
    materialized path would, and the error-feedback memory rides the xs as
    global-offset row slices with the per-chunk residual emitted as scan
    ys (the fold's linearity argument is unchanged -- it sums DECODED
    rows).  With ``codec.error_feedback``, ``opt_state`` is the wrapped
    ``{"opt": ..., "ef": (G, b_total)}`` dict.
    """
    if telemetry is not None:
        raise ValueError(
            "telemetry probes consume the materialized (G, ...) delta "
            "stack; the streamed microbatch fold never builds it -- run "
            "telemetry with microbatch=None")
    if plan is None:
        plan = make_packing_plan(cfg.sketch, params)
    rp = derive_round_params(plan, round_key)

    ef_wrapped = codec is not None and codec.error_feedback
    opt_orig = opt_state
    ef = None
    if ef_wrapped:
        ef, opt_state = opt_orig["ef"], opt_orig["opt"]
    if codec is not None:
        from repro.fed.codec import encode_decode

    G = jax.tree.leaves(batch)[0].shape[0]
    n_mb = -(-G // mb)
    pad = n_mb * mb - G

    w0 = (jnp.ones((G,), jnp.float32) if part_mask is None
          else mask_weights(part_mask).astype(jnp.float32))
    xs = {"batch": chunk_clients(batch, mb, pad),
          "w": jnp.pad(w0, (0, pad)).reshape(n_mb, mb)}
    if pad:
        xs["real"] = jnp.pad(jnp.ones((G,), bool),
                             (0, pad)).reshape(n_mb, mb)
    if fault_spec is not None:
        spec_p = _pad_fault_spec(fault_spec, pad)
        xs["spec"] = {k: v.reshape((n_mb, mb)) for k, v in spec_p.items()}
    if codec is not None:
        # global client ids key the rounding uniforms; pad ids are harmless
        # (their rows are statically zeroed and weight-0)
        xs["cid"] = jnp.pad(jnp.arange(G, dtype=jnp.int32),
                            (0, pad)).reshape(n_mb, mb)
        if ef_wrapped:
            xs["ef"] = jnp.pad(ef, ((0, pad), (0, 0))).reshape(n_mb, mb, -1)

    def chunk_payload(xc):
        """One chunk's (mb, b_total) sketches, (mb,) losses, post-arrival
        weights and EF residual, §10/§13 order (decode before corruption
        before any vetting)."""
        deltas, losses = jax.vmap(client_fn)(xc["batch"])
        sks = sk_packed_clients(plan, rp, deltas).astype(jnp.float32)
        if pad:     # static: hard-zero the tail-pad rows
            sks = jnp.where(xc["real"][:, None], sks, jnp.float32(0.0))
            losses = jnp.where(xc["real"], losses, jnp.float32(0.0))
        ef_c = None
        if codec is not None:
            sks, ef_c = encode_decode(
                codec, round_key, sks,
                ef_rows=xc["ef"] if ef_wrapped else None,
                client_ids=xc["cid"])
        w = xc["w"]
        if fault_spec is not None:
            from repro.fed.faults import corrupt_payload
            sks = corrupt_payload(xc["spec"], sks)
            w = w * xc["spec"]["arrive"]
        return sks, losses, w, ef_c

    counters = {}
    if fault_spec is not None:
        from repro.fed.faults import n_dropped
        counters["n_dropped"] = n_dropped(fault_spec, part_mask)

    S0 = jnp.zeros((plan.b_total,), jnp.float32)
    n_tx = None                  # billed transmitters (codec accounting)
    if sentinel is None or sentinel.norm_mult == 0.0:
        # single pass: the finite-check verdict is row-local, so faults ->
        # sentinel -> mask fuse inside each chunk
        init = (S0, jnp.float32(0.0), jnp.float32(0.0),
                jnp.zeros((), jnp.int32))
        if codec is not None:    # extra carry leaf: codec's program family
            init += (jnp.float32(0.0),)

        def body(carry, xc):
            S, W, L, n_rej = carry[:4]
            sks, losses, w, ef_c = chunk_payload(xc)
            if sentinel is not None:
                ok = jnp.isfinite(sks).all(axis=-1)
                sks = jnp.where(ok[:, None], sks, jnp.float32(0.0))
                n_rej = n_rej + jnp.sum((w > 0) & ~ok)
                w = w * ok.astype(jnp.float32)
            with jax.named_scope(spans.MEAN):
                out = (S + jnp.sum(sks * w[:, None], axis=0), W + jnp.sum(w),
                       L + jnp.sum(w * losses), n_rej)
            if codec is not None:
                out += (carry[4] + jnp.sum((w > 0).astype(jnp.float32)),)
            return out, ef_c

        res, ef_ys = jax.lax.scan(body, init, xs)
        S, W, L, n_rej = res[:4]
        if codec is not None:
            n_tx = res[4]
        if sentinel is not None:
            counters["n_rejected"] = n_rej
    else:
        # two-pass: the norm-outlier median needs the whole cohort's stats
        def stats(carry, xc):
            sks, losses, w, ef_c = chunk_payload(xc)
            ok = jnp.isfinite(sks).all(axis=-1)
            clean = jnp.where(ok[:, None], sks, jnp.float32(0.0))
            return carry, (losses, jnp.sum(jnp.square(clean), axis=-1),
                           ok, w, ef_c)

        _, (losses_c, nrm2_c, ok_c, w_c, ef_ys) = jax.lax.scan(stats, 0, xs)
        losses_p, nrm2_p = losses_c.reshape(-1), nrm2_c.reshape(-1)
        ok_p, w_arr = ok_c.reshape(-1), w_c.reshape(-1)
        from repro.fed.robust import masked_median
        pool = (w_arr > 0) & ok_p
        med2 = masked_median(nrm2_p, pool)
        valid = ok_p & (nrm2_p <= sentinel.norm_mult ** 2 * med2)
        counters["n_rejected"] = jnp.sum((w_arr > 0) & ~valid)
        w_eff = w_arr * valid.astype(jnp.float32)
        if codec is not None:
            n_tx = jnp.sum((w_eff > 0).astype(jnp.float32))

        xs2 = {**xs, "ok": ok_c, "we": w_eff.reshape(n_mb, mb)}

        def accum(S, xc):
            # deltas/sketches/codec draws are pure in (params, batch, rp,
            # round_key): recomputing them is deterministic, so pass 2
            # streams the SAME (decoded) payloads
            sks, _, _, _ = chunk_payload(xc)
            clean = jnp.where(xc["ok"][:, None], sks, jnp.float32(0.0))
            with jax.named_scope(spans.MEAN):
                return S + jnp.sum(clean * xc["we"][:, None], axis=0), None

        S, _ = jax.lax.scan(accum, S0, xs2)
        W = jnp.sum(w_eff)
        L = jnp.sum(w_eff * losses_p)

    with jax.named_scope(spans.MEAN):
        den = (jnp.asarray(part_mask["den"], jnp.float32)
               if isinstance(part_mask, dict) else jnp.maximum(W, 1.0))
        mbar = S / den
        loss = L / den

    update = desk_packed(plan, rp, mbar)
    new_params, new_opt = apply_update(cfg.server, opt_state, params, update,
                                       lr_scale=lr_scale)
    if ef_wrapped:
        # unsampled clients (pre-fault weight 0) freeze their EF memory;
        # the tail-pad ys rows are sliced off before anything reads them
        ef_new = ef_ys.reshape(n_mb * mb, -1)[:G]
        new_opt = {"opt": new_opt,
                   "ef": jnp.where((w0 > 0)[:, None], ef_new, ef)}
    if codec is not None:
        counters["uplink_bits"] = (
            jnp.float32(codec.payload_bits(plan.b_total)) * n_tx)
    if sentinel is not None:
        from repro.fed.robust import carry_if_empty, divergence_flag
        # the scalar surviving weight W plays the eff-mask role: its sum is
        # itself, which is all carry_if_empty consumes.  The wrapped EF
        # memory reverts with the server state on an empty cohort
        # (conservative; DESIGN.md §13)
        new_params, new_opt = carry_if_empty(W, (new_params, new_opt),
                                             (params, opt_orig))
        counters = {**counters, "diverged": divergence_flag(sentinel, loss)}
    return new_params, new_opt, {"loss": loss, **counters}


def safl_round(cfg: SAFLConfig, loss_fn: LossFn, params: Pytree,
               opt_state: dict, batch: Pytree, round_key: jax.Array,
               eta_scale: jax.Array | float = 1.0,
               lr_scale: jax.Array | float = 1.0, *,
               plan=None, part_mask=None, fault_spec=None,
               sentinel=None, telemetry=None,
               microbatch=None, codec=None) -> tuple[Pytree, dict, dict]:
    """One full SAFL round over all clients.

    ``batch`` leaves are shaped (G, K, mb, ...): G clients (sharded over the
    (pod, data) mesh axes in distributed mode), K local steps each.
    ``plan`` is the static packing layout; multi-round callers (the scan
    driver) build it ONCE outside the trace and thread it through via
    ``functools.partial`` -- only the round operator (``derive_round_params``)
    depends on ``round_key``.  ``part_mask`` (optional, (G,)) restricts the
    server aggregation to the round's sampled cohort (repro.fed): the sketch
    mean divides by the SAMPLED cohort size; an all-ones mask is bitwise the
    full-participation path.  ``fault_spec`` (traced, from
    ``fed.faults.*.spec``) injects payload faults and ``sentinel`` (static
    ``fed.robust.SentinelConfig``, threaded like ``plan`` via partial)
    rejects bad payloads before aggregation -- the faults -> sentinels ->
    mask fusion of DESIGN.md §10.  ``telemetry`` (static
    ``repro.obs.Telemetry``, threaded like ``plan`` via partial) adds the
    selected probe scalars to the metrics; it is None by default because any
    extra scan output shifts XLA fusion and hence the pinned f32
    trajectories (DESIGN.md §11).  ``microbatch`` (static) streams the
    aggregation over chunks of that many clients instead of materializing
    the full cohort (DESIGN.md §12) -- ``None`` or any value >= G keeps the
    materialized path below untouched, so the pinned trajectories survive.
    ``codec`` (static ``fed.codec.CodecConfig``, threaded like ``plan``)
    quantize-dequantizes the payload rows between the fused sketch and the
    guard/mean stages, with sketch-space error feedback, and replaces the
    float32 ``uplink_bits`` fiction with the MEASURED encoded size
    (DESIGN.md §13); ``codec=None`` routes at Python level, keeping the
    pinned trajectories byte-identical.  With ``codec.error_feedback``,
    ``opt_state`` is the wrapped ``{"opt": ..., "ef": (G, b_total)}`` dict
    (``fed.codec.init_codec_state``).
    Returns (params, opt_state, metrics).
    """
    if codec is not None and telemetry is not None:
        raise ValueError(
            "telemetry probes read the bare server opt state; under "
            "codec.error_feedback the round state is the wrapped "
            "{'opt', 'ef'} dict -- run telemetry without a codec")
    eta = jnp.asarray(cfg.client_lr * eta_scale, jnp.float32)

    if microbatch is not None:
        mb = resolve_microbatch(microbatch,
                                jax.tree.leaves(batch)[0].shape[0])
        if mb is not None:
            return streamed_sketch_round(
                cfg, lambda b: client_delta(cfg, loss_fn, params, b, eta),
                params, opt_state, batch, round_key, mb, lr_scale=lr_scale,
                plan=plan, part_mask=part_mask, fault_spec=fault_spec,
                sentinel=sentinel, telemetry=telemetry, codec=codec)

    ef_wrapped = codec is not None and codec.error_feedback
    opt_orig = opt_state
    ef = None
    if ef_wrapped:
        ef, opt_state = opt_orig["ef"], opt_orig["opt"]

    # --- client updates (vmapped over the client axis; params broadcast) ---
    deltas, losses = jax.vmap(
        lambda mb: client_delta(cfg, loss_fn, params, mb, eta))(batch)

    # --- uplink: sketch each client's delta with the SHARED round operator
    # (Remark 3.1: same seed across clients within a round).  The packed
    # engine derives the operator ONCE for sk and desk and compresses the
    # whole tree in one fused pass -> (G, b_total) payload. ---
    if plan is None:
        plan = make_packing_plan(cfg.sketch, params)
    rp = derive_round_params(plan, round_key)
    sketches = sk_packed_clients(plan, rp, deltas)

    # --- payload codec (DESIGN.md §13): quantize-dequantize each client's
    # row (plus its EF residual) BEFORE faults/sentinels -- corruption
    # happens in transit to the ENCODED bytes, and the server can only vet
    # what it decodes.  Unsampled clients freeze their EF memory. ---
    if codec is not None:
        from repro.fed.codec import encode_decode
        sketches = sketches.astype(jnp.float32)
        if ef_wrapped:
            sketches, ef_new = encode_decode(codec, round_key, sketches,
                                             ef_rows=ef)
            ef = masked_where_tree(part_mask, ef_new, ef)
        else:
            sketches, _ = encode_decode(codec, round_key, sketches)

    # --- fault injection + sentinel rejection, both in sketch space; the
    # survivors' weights land in the SAME mask the cohort mean already
    # consumes (lazy import: repro.fed imports this module) ---
    counters = {}
    if fault_spec is not None or sentinel is not None:
        from repro.fed.robust import guard_uplink
        sketches, part_mask, counters = guard_uplink(
            sketches, part_mask, fault_spec, sentinel)

    # --- server: average of sketches == sketch of average (Property 1).
    # Under GSPMD this mean over the client axis is the ONLY cross-client
    # collective, and it moves b_total floats, not d.  Under partial
    # participation only the sampled cohort contributes, and the mean
    # divides by the cohort size, not N. ---
    with jax.named_scope(spans.MEAN):
        mbar = masked_mean(sketches, part_mask)

    # --- desk back to R^d and run ADA_OPT (Alg. 2); deterministic, so every
    # replica/client replays the identical server step. ---
    update = desk_packed(plan, rp, mbar)
    new_params, new_opt = apply_update(cfg.server, opt_state, params, update,
                                       lr_scale=lr_scale)
    if ef_wrapped:
        new_opt = {"opt": new_opt, "ef": ef}
    if codec is not None:
        # MEASURED wire size: encoded row bits x the effective post-guard
        # transmitting cohort (guard_uplink rebound part_mask above)
        from repro.fed.codec import measured_uplink_bits
        counters["uplink_bits"] = measured_uplink_bits(
            codec, plan.b_total, eff_mask=part_mask,
            num_clients=losses.shape[0])

    loss = masked_mean(losses, part_mask)
    if sentinel is not None:
        from repro.fed.robust import carry_if_empty, divergence_flag
        # the wrapped EF memory reverts with the server state on an empty
        # cohort (conservative; DESIGN.md §13)
        new_params, new_opt = carry_if_empty(
            part_mask, (new_params, new_opt), (params, opt_orig))
        counters = {**counters, "diverged": divergence_flag(sentinel, loss)}

    metrics = {"loss": loss, **counters}
    if telemetry is not None:
        # part_mask here is the EFFECTIVE mask (guard_uplink rebinds it), so
        # the probes and the aggregation see the same cohort
        from repro.obs.telemetry import telemetry_probes
        metrics.update(telemetry_probes(
            telemetry, deltas=deltas, update=update, part_mask=part_mask,
            state=new_opt))
    return new_params, new_opt, metrics


def fedopt_round(cfg: SAFLConfig, loss_fn: LossFn, params: Pytree,
                 opt_state: dict, batch: Pytree, round_key: jax.Array,
                 eta_scale: jax.Array | float = 1.0,
                 lr_scale: jax.Array | float = 1.0, *,
                 part_mask=None, fault_spec=None,
                 sentinel=None, telemetry=None,
                 microbatch=None, codec=None) -> tuple[Pytree, dict, dict]:
    """Uncompressed FedOPT (Reddi et al. 2020) round: the paper's
    'ambient-dimension' reference line (legend 4e7 / 1e8).  Identical to
    safl_round with the identity compressor -- clients uplink raw deltas,
    i.e. the mean below all-reduces O(d) floats."""
    if fault_spec is not None or sentinel is not None:
        raise ValueError(
            "fault injection / payload sentinels act on the packed sketch "
            "uplink (fed.faults / fed.robust); the uncompressed FedOPT "
            "baseline has no sketch payload -- run them on the SAFL/SACFL "
            "rounds")
    if codec is not None:
        raise ValueError(
            "the payload codec quantizes the packed sketch uplink "
            "(fed.codec, DESIGN.md §13); the uncompressed FedOPT baseline "
            "has no sketch payload -- run the codec on the SAFL/SACFL "
            "rounds")
    eta = jnp.asarray(cfg.client_lr * eta_scale, jnp.float32)

    if microbatch is not None:
        mb = resolve_microbatch(microbatch,
                                jax.tree.leaves(batch)[0].shape[0])
        if mb is not None:
            return _streamed_fedopt_round(
                cfg, loss_fn, params, opt_state, batch, eta, mb,
                lr_scale=lr_scale, part_mask=part_mask, telemetry=telemetry)

    deltas, losses = jax.vmap(
        lambda mb: client_delta(cfg, loss_fn, params, mb, eta))(batch)
    update = masked_mean_tree(deltas, part_mask)
    params, opt_state = apply_update(cfg.server, opt_state, params, update,
                                     lr_scale=lr_scale)
    metrics = {"loss": masked_mean(losses, part_mask)}
    if telemetry is not None:
        # the uncompressed update IS the cohort-mean delta, so the desketch
        # residual probe reads exactly 0 -- the reference line
        from repro.obs.telemetry import telemetry_probes
        metrics.update(telemetry_probes(
            telemetry, deltas=deltas, update=update, part_mask=part_mask,
            state=opt_state))
    return params, opt_state, metrics


def _streamed_fedopt_round(cfg: SAFLConfig, loss_fn: LossFn, params: Pytree,
                           opt_state: dict, batch: Pytree, eta: jax.Array,
                           mb: int, *, lr_scale=1.0, part_mask=None,
                           telemetry=None) -> tuple[Pytree, dict, dict]:
    """Streamed fold of the uncompressed FedOPT round: the raw-delta mean is
    a plain weighted tree sum, so the microbatch carry is one O(d) tree plus
    the weight/loss scalars instead of the (G, d) delta stack.  Same masked
    tail contract as ``streamed_sketch_round``."""
    if telemetry is not None:
        raise ValueError(
            "telemetry probes consume the materialized (G, ...) delta "
            "stack; the streamed microbatch fold never builds it -- run "
            "telemetry with microbatch=None")
    G = jax.tree.leaves(batch)[0].shape[0]
    n_mb = -(-G // mb)
    pad = n_mb * mb - G
    w0 = (jnp.ones((G,), jnp.float32) if part_mask is None
          else mask_weights(part_mask).astype(jnp.float32))
    xs = {"batch": chunk_clients(batch, mb, pad),
          "w": jnp.pad(w0, (0, pad)).reshape(n_mb, mb)}
    if pad:
        xs["real"] = jnp.pad(jnp.ones((G,), bool),
                             (0, pad)).reshape(n_mb, mb)

    S0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)

    def body(carry, xc):
        S, W, L = carry
        deltas, losses = jax.vmap(
            lambda b: client_delta(cfg, loss_fn, params, b, eta))(xc["batch"])
        w = xc["w"]
        if pad:     # static: hard-zero the tail-pad rows
            deltas = jax.tree.map(
                lambda d: jnp.where(
                    xc["real"].reshape((mb,) + (1,) * (d.ndim - 1)), d,
                    jnp.float32(0.0)), deltas)
            losses = jnp.where(xc["real"], losses, jnp.float32(0.0))
        S = jax.tree.map(
            lambda s, d: s + jnp.sum(
                d * w.reshape((mb,) + (1,) * (d.ndim - 1)), axis=0),
            S, deltas)
        return (S, W + jnp.sum(w), L + jnp.sum(w * losses)), None

    (S, W, L), _ = jax.lax.scan(
        body, (S0, jnp.float32(0.0), jnp.float32(0.0)), xs)
    den = (jnp.asarray(part_mask["den"], jnp.float32)
           if isinstance(part_mask, dict) else jnp.maximum(W, 1.0))
    update = jax.tree.map(lambda s: s / den, S)
    params, opt_state = apply_update(cfg.server, opt_state, params, update,
                                     lr_scale=lr_scale)
    return params, opt_state, {"loss": L / den}


def init_safl(cfg: SAFLConfig, params: Pytree) -> dict:
    """Server moment state (m_0 = v_0 = v̂_0 = 0)."""
    return init_opt_state(cfg.server, params)


def split_client_batches(batch: Pytree, num_clients: int, local_steps: int) -> Pytree:
    """Reshape a global batch (B, ...) -> (G, K, B/(G*K), ...)."""
    def reshape(x):
        b = x.shape[0]
        assert b % (num_clients * local_steps) == 0, (
            f"batch {b} not divisible by G*K={num_clients * local_steps}")
        return x.reshape(num_clients, local_steps,
                         b // (num_clients * local_steps), *x.shape[1:])
    return jax.tree.map(reshape, batch)


def uplink_bits_per_round(cfg: SAFLConfig, params: Pytree,
                          cohort_size: int = 1) -> int:
    """Uplink payload in bits per round (paper's communication metric).

    ``cohort_size`` is the number of clients that actually transmit in a
    round: under partial participation (repro.fed) this is the SAMPLED
    cohort size, not N -- pass ``policy.cohort_size`` to get the honest
    per-round total.  The default (1) reports the per-client payload, the
    seed semantics."""
    from repro.core.sketch import total_sketch_bits
    assert cohort_size >= 1, "a round must have at least one uplinking client"
    return total_sketch_bits(cfg.sketch, params) * int(cohort_size)
