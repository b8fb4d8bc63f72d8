"""On-device multi-round driver: R federated rounds per device dispatch.

The seed trainers (``launch/train.py::train_loop``, ``benchmarks/run.py``)
drove every round from the host: sample a batch with numpy, dispatch one
jitted round, synchronously pull the loss back.  At bench scale that
host<->device round trip -- not the compressed-communication math the paper
analyzes -- dominates wall clock.  FetchSGD / FedSKETCH keep the whole
sketch-train loop resident on the accelerator; this driver does the same
(DESIGN.md §6):

* ``run_scan`` runs a chunk of rounds as ONE ``jax.lax.scan``: the scan body
  draws its own batch on device (``repro.data.device``), derives the round's
  sketch operator from the scanned round key (Remark 3.1 semantics
  unchanged -- same fold_in(key, t) chain as the host loop), and steps the
  round function.
* the ``(params, opt/baseline state, data state)`` carry is DONATED
  (``donate_argnums``) so large models update in place across chunks.
* metrics (loss, uplink bits) accumulate on device as stacked scan outputs
  and are fetched once per chunk, not once per round.
* the static sketch layout (``PackingPlan``) is built once OUTSIDE the trace
  by the caller and threaded in via ``functools.partial(round_fn, plan=...)``.

One interface serves ``safl_round``, ``clipped_safl_round`` and every
``baseline_round`` variant: any ``round_fn(params, state, batch, key, **kw)
-> (params, state, metrics)`` is scannable once it is purely functional
(baselines were made so in this PR -- an in-place ``state`` mutation is an
aliasing bug under donation).

``run_host_loop`` is the one-dispatch-per-round reference with the SAME key
and batch sequence; tests/test_driver.py pins scan == host loop
bit-for-bit, and benchmarks/run.py times both (fig1/<algo> vs
fig1/<algo>_scan).

Participation hooks (DESIGN.md §7, ``repro.fed``): ``participation=`` takes
a sampling policy whose ``mask(t)`` is evaluated inside the scan body and
passed to the round as ``part_mask`` (the per-round uplink-bits metric then
reports the SAMPLED cohort: per-client bits x mask sum); ``buffer=True``
additionally threads the traced round index ``t`` and the run's base key
into the round as ``t=``/``base_key=`` kwargs -- what an async staleness
buffer (``repro.fed.async_buffer``) needs to address its ring buffer and
re-derive older rounds' sketch operators at arrival time; ``faults=`` takes
a fault-injection policy (``repro.fed.faults``) whose per-round spec is
evaluated in the scan body and passed to the round as ``fault_spec``
(DESIGN.md §10 -- the sentinel config rides into the round via
``functools.partial``, like ``plan=``).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import spans
from repro.obs.shards import host_fetch
from repro.obs.telemetry import PROBE_KEYS

Pytree = Any
# (params, state, batch, round_key, **kwargs) -> (params, state, metrics)
RoundFn = Callable[..., tuple[Pytree, dict, dict]]

# counter keys the guarded/buffered rounds emit next to the loss (fed/robust
# n_dropped/n_rejected, the sentinel's diverged flag, the async buffer's
# arrival_weight)
COUNTER_KEYS = ("n_dropped", "n_rejected", "diverged", "arrival_weight")

# every key a history dict / metric shard row may carry -- the single source
# of truth shared by this driver, the mesh driver (launch/train.py), the
# bench harness and tools/check_telemetry.py.  Which subset actually appears
# depends on the hooks bound into the round fn (guard counters) and on the
# static Telemetry config (probe keys; repro.obs.telemetry).
HISTORY_KEYS = ("loss", "uplink_bits") + COUNTER_KEYS + PROBE_KEYS


def _with_bits(metrics: dict, bits_per_round: Optional[int],
               mask=None, num_clients: Optional[int] = None) -> dict:
    """Stack the per-round uplink payload next to the loss (f32: 32d bits of
    a 100M-param model overflows int32).  With a participation mask the
    honest per-round figure is per-client bits x the EFFECTIVE post-guard
    cohort: the sampled cohort size (weighted masks carry theirs statically
    as ``"n"``) minus the round's fault drops and sentinel rejections -- a
    dropped payload never reaches the server and a rejected one is
    discarded, so neither is billed (the guarded rounds emit the
    ``n_dropped``/``n_rejected`` counters this reads; an unguarded round
    carries neither, leaving the no-fault program untouched).  Without a
    mask, ``bits_per_round`` is the caller's whole-cohort per-round total
    (seed semantics); when guard counters are present it is scaled by the
    surviving fraction ``(num_clients - lost) / num_clients``
    (``num_clients`` comes from the bound fault policy)."""
    if bits_per_round is None or "uplink_bits" in metrics:
        return metrics
    bits = jnp.asarray(bits_per_round, jnp.float32)
    lost = None
    if "n_dropped" in metrics or "n_rejected" in metrics:
        lost = sum(metrics[k] for k in ("n_dropped", "n_rejected")
                   if k in metrics)
    if mask is not None:
        n = mask["n"] if isinstance(mask, dict) else jnp.sum(mask)
        if lost is not None:
            n = n - lost
        bits = bits * n
    elif lost is not None and num_clients is not None:
        bits = bits * (num_clients - lost) / num_clients
    return {**metrics, "uplink_bits": bits}


def round_hook_kwargs(t, key, kwargs_fn, participation, buffer, faults=None):
    """Per-round traced kwargs for the round fn + the round's cohort mask.

    This is THE contract of the repro.fed hooks, shared by both drivers (the
    single-host scan here and the mesh scan in ``launch/train.py``): the
    cohort mask is evaluated in the scan body as a pure function of the
    absolute round index (``participation.mask(t)``) and handed to the round
    as ``part_mask``; a staleness buffer additionally receives the traced
    round index ``t`` and the run's base key ``base_key`` (ring-buffer
    addressing + per-generation operator re-derivation); a fault policy
    (``repro.fed.faults``) contributes the round's traced fault spec as
    ``fault_spec`` -- drawn against the run key, so the rollback
    supervisor's rekeyed retries redraw transient faults.  The static
    sentinel config is NOT threaded here: like ``plan=``, it binds into the
    round fn via ``functools.partial`` (it is not a pytree, and the host
    loop jits the round with these kwargs as traced arguments)."""
    kw = dict(kwargs_fn(t)) if kwargs_fn is not None else {}
    mask = None
    if participation is not None:
        mask = participation.mask(t)
        kw["part_mask"] = mask
    if buffer:
        kw["t"] = t
        kw["base_key"] = key
    if faults is not None:
        kw["fault_spec"] = faults.spec(t, key)
    return kw, mask


_round_kwargs = round_hook_kwargs         # back-compat alias


def make_chunk_fn(round_fn: RoundFn, sampler, num_rounds: int, *,
                  kwargs_fn=None, bits_per_round: Optional[int] = None,
                  donate: bool = True, participation=None,
                  buffer: bool = False, faults=None, microbatch=None,
                  codec=None):
    """Jit one scanned chunk of ``num_rounds`` rounds.

    Signature of the returned fn:
        (params, state, data_state, key, t0) ->
            (params, state, data_state, stacked_metrics)
    ``t0`` is a traced scalar so successive chunks reuse one executable.
    ``participation``/``buffer``/``faults`` are the repro.fed hooks (module
    docstring).  ``microbatch`` (static) binds the streamed-aggregation
    chunk size into the round fn (DESIGN.md §12); ``codec`` (static
    ``fed.codec.CodecConfig``) binds the payload codec (DESIGN.md §13).
    None leaves the round -- and the pinned programs -- untouched.
    """
    if microbatch is not None:
        round_fn = functools.partial(round_fn, microbatch=microbatch)
    if codec is not None:
        round_fn = functools.partial(round_fn, codec=codec)
    n_fault_clients = getattr(faults, "num_clients", None)

    def chunk(params, state, data_state, key, t0):
        def body(carry, t):
            params, state, dstate = carry
            with jax.named_scope(spans.SAMPLE):
                dstate, batch = sampler.sample(dstate, t)
            kw, mask = round_hook_kwargs(t, key, kwargs_fn, participation,
                                         buffer, faults)
            params, state, m = round_fn(params, state, batch,
                                        jax.random.fold_in(key, t), **kw)
            return (params, state, dstate), _with_bits(m, bits_per_round,
                                                       mask,
                                                       n_fault_clients)

        (params, state, data_state), hist = jax.lax.scan(
            body, (params, state, data_state),
            t0 + jnp.arange(num_rounds, dtype=jnp.int32))
        return params, state, data_state, hist

    return jax.jit(chunk, donate_argnums=(0, 1, 2) if donate else ())


def run_scan(round_fn: RoundFn, sampler, params: Pytree, state: dict, *,
             rounds: int, key: jax.Array, chunk_size: int = 0,
             kwargs_fn=None, bits_per_round: Optional[int] = None,
             donate: bool = True, on_chunk=None, participation=None,
             buffer: bool = False, faults=None, microbatch=None,
             codec=None, start_round: int = 0,
             stream=None) -> tuple[Pytree, dict, dict]:
    """Run ``rounds`` federated rounds on device in scanned chunks.

    * ``sampler`` provides ``init_state()`` and ``sample(state, t)`` (see
      ``repro.data.device.DeviceBigramSampler``).
    * ``kwargs_fn(t)`` (optional) returns extra traced kwargs for the round,
      e.g. ``lambda t: {"lr_scale": sched(t)}`` for a cosine server LR.
    * ``chunk_size`` bounds rounds per dispatch (0 = all in one); metrics are
      fetched to host once per chunk, and ``on_chunk(t_done, params, state,
      chunk_hist)`` runs between chunks (logging / checkpointing).

    **Hook contract** (the full set, with each hook's pin class -- see
    DESIGN.md appendix "Pinning methodology" for the taxonomy):

    * ``participation=`` (policy object, ``repro.fed.participation``): the
      cohort mask is evaluated in the scan body as a pure function of the
      absolute round index and passed to the round as ``part_mask``.
      ``None`` routes at Python level (bitwise-neutral); an all-ones 0/1
      mask is bitwise the unmasked path by construction.
    * ``buffer=True`` (``repro.fed.async_buffer``): threads the traced
      round index ``t`` and the run's base key into the round.  The async
      round with ``delay="zero"`` is bitwise the synchronous program;
      nonzero delays are their own program family.
    * ``faults=`` (policy, ``repro.fed.faults``): per-round traced fault
      spec passed as ``fault_spec``; ``None`` is bitwise-neutral, enabled
      faults are their own family (extra guard counters in the scan ys).
    * ``sentinel=`` / ``telemetry=`` / ``plan=``: static configs, NOT
      threaded here -- bind them into ``round_fn`` via
      ``functools.partial`` before calling.  ``sentinel`` and ``telemetry``
      each start their own program family when enabled (extra scan
      outputs shift XLA fusion); ``None`` is bitwise-neutral.
    * ``microbatch=`` (static int): streams the round's aggregation over
      chunks of that many clients (DESIGN.md §12: peak payload memory
      O(microbatch x b_total) instead of O(G x b_total)); ``None`` (default)
      and any value >= G keep the materialized round program untouched
      (bitwise); a streaming value is its own family, allclose to the
      materialized path.
    * ``codec=`` (static ``fed.codec.CodecConfig``): binds the quantized
      payload codec (DESIGN.md §13) into the round like ``microbatch``;
      ``None`` (default) is bitwise-neutral, an enabled codec is its own
      family (it changes the trajectory by design) and replaces the
      ``uplink_bits`` fiction with the measured encoded size.  With
      ``codec.error_feedback`` the caller wraps ``state`` as
      ``{"opt": ..., "ef": ...}`` (``fed.codec.init_codec_state``).
    * ``stream=`` (below) only changes where metrics land, never the
      compiled round program.

    * ``start_round`` resumes mid-trajectory at an absolute round index --
      the restart path for a ``(t, key)`` checkpoint cursor
      (examples/train_lm.py).  Because every per-round stream (data,
      cohorts, delays, sketch operators) is a pure function of the absolute
      round index under ``key``, a resumed run replays the uninterrupted
      trajectory bit-identically (tests/test_resume.py).
    * ``stream`` (optional) is a ``repro.obs.shards.ShardWriter``: each
      chunk's history is fetched with an async device->host copy and
      appended as one JSONL metrics shard plus a wall-time span event
      (``compile=True`` marks the first dispatch of a chunk length), and the
      in-memory history accumulation is SKIPPED -- the returned ``history``
      is ``{}`` and the shard files are the record.  ``on_chunk`` still
      receives each chunk's host-side history either way.

    **Profile names** (``repro.obs.spans``, DESIGN.md §11).  Each chunk
    opens three host spans on the profiler's clock:
    ``run_scan.dispatch(t0=, rounds=, compile=)`` around the call into the
    chunk (``compile=1`` on the first call of a chunk length, which holds
    the compile), ``run_scan.fetch`` around the history's device->host copy
    and ``run_scan.on_chunk`` around the callback.  With ``stream=``, the
    span event in ``events.jsonl`` covers dispatch and fetch, timed from
    the same two edges.  Inside the chunk, the round's stages carry the
    ``jax.named_scope`` names ``safl.client``, ``safl.derive``,
    ``safl.sketch``, ``safl.mean``, ``safl.desk``, ``safl.server_opt``,
    and the batch draw ``driver.sample``, set where each stage's function
    is defined.  Scopes are op metadata only: no program family, unlike a
    ``Telemetry`` config; the spans cost nothing without a profiler.

    Returns ``(params, state, history)`` with ``history`` a dict of
    host-side ``(rounds - start_round,)`` arrays.  ``loss`` is always
    present; ``uplink_bits`` when ``bits_per_round`` is set; the
    ``COUNTER_KEYS`` subset the bound round emits (``n_dropped`` /
    ``n_rejected`` from the uplink guard, ``diverged`` from the sentinel,
    ``arrival_weight`` from the async buffer); and the ``PROBE_KEYS``
    subset selected by a static ``Telemetry`` config bound into the round
    (``repro.obs.telemetry``).  ``HISTORY_KEYS`` (module level) is the
    single source of truth for the full key set.
    """
    chunk_size = int(chunk_size) or int(rounds)
    data_state = sampler.init_state()
    compiled: dict[int, Callable] = {}
    hists = []
    t = int(start_round)
    while t < rounds:
        n = min(chunk_size, rounds - t)
        fresh = n not in compiled
        if fresh:                   # tail chunk of a different length re-jits
            compiled[n] = make_chunk_fn(
                round_fn, sampler, n, kwargs_fn=kwargs_fn,
                bits_per_round=bits_per_round, donate=donate,
                participation=participation, buffer=buffer, faults=faults,
                microbatch=microbatch, codec=codec)
        t_wall = time.perf_counter()
        with spans.span(spans.DISPATCH, t0=t, rounds=n, compile=int(fresh)):
            params, state, data_state, hist = compiled[n](
                params, state, data_state, key, jnp.asarray(t, jnp.int32))
        with spans.span(spans.FETCH):          # ONE fetch per chunk
            hist = (host_fetch(hist) if stream is not None  # async copy
                    else jax.tree.map(np.asarray, hist))
        if stream is not None:
            # the interval of the two spans above, from their two edges
            dt = time.perf_counter() - t_wall
            stream.write_chunk(t, hist)
            stream.write_span(t, t + n, dt, compile=fresh)
        else:
            hists.append(hist)
        t += n
        if on_chunk is not None:
            with spans.span(spans.ON_CHUNK):
                on_chunk(t, params, state, hist)
    if not hists:   # streamed, or resumed at start_round == rounds
        return params, state, {}
    history = jax.tree.map(lambda *xs: np.concatenate(xs), *hists)
    return params, state, history


def run_host_loop(round_fn: RoundFn, sampler, params: Pytree, state: dict, *,
                  rounds: int, key: jax.Array, kwargs_fn=None,
                  bits_per_round: Optional[int] = None, donate: bool = True,
                  participation=None, buffer: bool = False, faults=None,
                  microbatch=None, codec=None,
                  start_round: int = 0) -> tuple[Pytree, dict, dict]:
    """One-dispatch-per-round reference loop with the scan driver's exact
    key/batch sequence (fold_in(key, t); device-side sampling), including
    the participation/buffer hooks (module docstring).

    Carries are still donated (ISSUE 2 satellite: no params/opt copy even on
    the non-scan path); the remaining cost vs ``run_scan`` is R dispatches
    and R blocking metric fetches -- precisely what fig1/<algo> vs
    fig1/<algo>_scan measures.
    """
    if microbatch is not None:
        round_fn = functools.partial(round_fn, microbatch=microbatch)
    if codec is not None:
        round_fn = functools.partial(round_fn, codec=codec)
    n_fault_clients = getattr(faults, "num_clients", None)
    data_state = sampler.init_state()
    sample = jax.jit(sampler.sample)
    step = jax.jit(round_fn, donate_argnums=(0, 1) if donate else ())
    hists = []
    for t in range(int(start_round), rounds):
        tt = jnp.asarray(t, jnp.int32)
        data_state, batch = sample(data_state, tt)
        kw, mask = round_hook_kwargs(tt, key, kwargs_fn, participation,
                                     buffer, faults)
        params, state, m = step(params, state, batch,
                                jax.random.fold_in(key, tt), **kw)
        hists.append(jax.tree.map(np.asarray,
                                  _with_bits(m, bits_per_round, mask,
                                             n_fault_clients)))
    history = jax.tree.map(lambda *xs: np.stack(xs), *hists)
    return params, state, history
