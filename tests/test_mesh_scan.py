"""Multi-pod scanned mesh driver (launch/train.py, DESIGN §8-§9).

Pins the ISSUE 4 + ISSUE 5 contracts on an 8-forced-CPU-device host mesh:

  * scanned mesh rounds (``run_mesh_scan``: one ``lax.scan`` OUTSIDE the
    shard_map round, donated (params, opt, data_state, key) carries) are
    bit-identical to per-round jitted mesh steps (``run_mesh_host_loop``)
    for safl AND fedopt, on cross_device and cross_silo topologies;
  * chunk-split invariance: chunked dispatch == one-dispatch, bitwise;
  * donation safety: chunk_size=1 rethreads every donated carry across
    dispatches without aliasing crashes;
  * the plan-routed shard-local sketch (``make_sharded_packing_plan`` +
    packed sk/desk inside shard_map) equals the per-leaf reference loop;
  * the repro.fed hooks (DESIGN §9): an all-ones participation mask and a
    delay=0 staleness buffer are pinned BITWISE to the hookless PR-4
    trajectories; masked/buffered scans match the hooked per-round loop
    and are chunk-split invariant; weighted (importance) masks are
    rejected by the mesh buffer path with a clear error.

Device policy (DESIGN §5): the 8-device flag must NOT leak into the main
suite, so when this module is collected on a single-device session it
re-runs itself in a subprocess with
``--xla_force_host_platform_device_count=8`` (the mini-dry-run pattern);
CI additionally runs the direct tests in a dedicated 8-device job step.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.train as train_mod
from repro.core.adaptive import AdaConfig
from repro.core.packed import make_sharded_packing_plan
from repro.core.safl import SAFLConfig, init_safl
from repro.core.sketch import SketchConfig
from repro.data import BigramLMData, LMDataConfig
from repro.fed import (AsyncConfig, CodecConfig, FaultConfig, FaultTable,
                       FixedCohort, FullParticipation,
                       ImportanceParticipation, SentinelConfig,
                       UniformParticipation)
from repro.fed import BYZANTINE as FAULT_BYZ
from repro.fed import DROP as FAULT_DROP
from repro.fed import NAN as FAULT_NAN
from repro.fed import OK as FAULT_OK
from repro.launch.mesh import _mesh
from repro.launch.train import (_mesh_pspecs, init_mesh_async_state,
                                make_fedopt_scan_fn, make_fedopt_train_step,
                                make_safl_train_step, mesh_sampler,
                                num_clients_of, run_mesh_host_loop,
                                run_mesh_scan, sharded_sketch_avg_desk)
from repro.models import ModelConfig, init_params
from repro.models.sharding import use_mesh

ON_8 = jax.device_count() >= 8

needs8 = pytest.mark.skipif(not ON_8, reason="needs 8 forced CPU devices")

TOPOLOGIES = [
    pytest.param("cross_silo", id="cross_silo"),
    pytest.param("cross_device", id="cross_device"),
]

MODEL = ModelConfig(name="meshscan", arch_type="dense", num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                    vocab_size=64)


def _mk(topology, kind="countsketch"):
    """Mesh, config, sharded sampler for one (topology, compressor) case.

    One (2, 2, 2) pod/data/model mesh serves both topologies: cross_device
    clients = the 4 (pod, data) groups, cross_silo clients = the 2 pods
    (mb = 4 is data-sharded 2-way there)."""
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = SAFLConfig(sketch=SketchConfig(kind=kind, ratio=0.1, min_b=8),
                     server=AdaConfig(name="amsgrad", lr=0.01),
                     client_lr=0.5, local_steps=2)
    G = num_clients_of(mesh, topology)
    data = BigramLMData(LMDataConfig(vocab_size=64, seq_len=16,
                                     num_clients=G, alpha=0.05))
    smp = mesh_sampler(mesh, data.device_sampler(8, 2), topology)
    return mesh, cfg, smp


def _fresh(cfg):
    p = init_params(MODEL, jax.random.key(0))
    return p, init_safl(cfg, p)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# scanned == per-round, bitwise
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("kind", ["countsketch", "none"])
def test_scan_matches_per_round_mesh_step_bitwise(topology, kind):
    """N scanned mesh rounds == N per-round jitted mesh steps, bit for bit:
    same fold_in(key, t) chain, same device-sampled sharded batches.
    kind="none" is the FedOPT raw-delta O(d) all-reduce inside the same
    scan layout."""
    mesh, cfg, smp = _mk(topology, kind)
    with use_mesh(mesh):
        step, _ = make_safl_train_step(MODEL, cfg, mesh, topology)
        key = jax.random.key(42)
        p1, o1, h1 = run_mesh_host_loop(step, smp, *_fresh(cfg), rounds=3,
                                        key=key, donate=False)
        # donate=True on the scan side also exercises the donated carries
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology)
    assert h2["loss"].shape == (3,)
    assert np.isfinite(h2["loss"]).all()
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal(p1, p2)
    _assert_trees_equal(o1, o2)


@needs8
def test_fedopt_scan_fn_matches_fedopt_step_bitwise():
    """The make_fedopt_scan_fn entry point (one chunk, donated carries)
    reproduces make_fedopt_train_step rounds exactly."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology, "countsketch")  # fedopt overrides sketch
    with use_mesh(mesh):
        step, _ = make_fedopt_train_step(MODEL, cfg, mesh, topology)
        key = jax.random.key(5)
        p1, o1, h1 = run_mesh_host_loop(step, smp, *_fresh(cfg), rounds=2,
                                        key=key, donate=False)
        chunk, _ = make_fedopt_scan_fn(MODEL, cfg, mesh, topology,
                                       sampler=smp, num_rounds=2)
        # key_data(key) aliases key's buffer and the chunk donates arg 3:
        # pass a fresh device copy so `key` survives
        kd = jnp.asarray(np.asarray(jax.random.key_data(key)))
        p2, o2, _, _, h2 = chunk(*_fresh(cfg), smp.init_state(), kd,
                                 jnp.asarray(0, jnp.int32))
    np.testing.assert_array_equal(h1["loss"], np.asarray(h2["loss"]))
    _assert_trees_equal(p1, p2)
    _assert_trees_equal(o1, o2)


# ---------------------------------------------------------------------------
# chunking + donation
# ---------------------------------------------------------------------------

@needs8
def test_mesh_scan_chunk_split_invariance():
    """Chunked dispatch (2+2) is bit-identical to one 4-round dispatch and
    the stitched on-device loss history matches."""
    mesh, cfg, smp = _mk("cross_silo")
    with use_mesh(mesh):
        key = jax.random.key(7)
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=4, key=key, topology="cross_silo")
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=4, key=key, topology="cross_silo",
                                   chunk_size=2)
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal(p1, p2)
    _assert_trees_equal(o1, o2)


@needs8
def test_mesh_scan_donation_safe():
    """chunk_size=1 rethreads every donated (params, opt, data_state, key)
    buffer through 3 separate dispatches: an aliasing bug (donated buffer
    read after donation) crashes here.  on_chunk must observe progress."""
    mesh, cfg, smp = _mk("cross_silo")
    seen = []
    with use_mesh(mesh):
        p0, _ = _fresh(cfg)
        p, o, h = run_mesh_scan(
            MODEL, cfg, mesh, smp, *_fresh(cfg), rounds=3,
            key=jax.random.key(0), topology="cross_silo", chunk_size=1,
            donate=True,
            on_chunk=lambda t, pp, oo, hh: seen.append((t, hh["loss"].shape)))
    assert seen == [(1, (1,)), (2, (1,)), (3, (1,))]
    assert np.isfinite(h["loss"]).all()
    # params actually moved (the donated carry is not a stale alias)
    moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p)))
    assert moved


# ---------------------------------------------------------------------------
# plan-routed shard-local sketch == per-leaf reference
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("kind", ["countsketch", "srht", "gaussian"])
def test_sharded_sketch_plan_route_matches_per_leaf(kind):
    """The packed-plan route inside shard_map (operator derived once, one
    fused pass, ONE (G_loc, b_total) pmean) produces exactly the per-leaf
    reference loop's values -- same per-leaf fold_in chain."""
    topology = "cross_silo"
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    skcfg = SketchConfig(kind=kind, ratio=0.1, min_b=8)
    with use_mesh(mesh):
        abstract, pspecs = _mesh_pspecs(MODEL, topology)
        plan = make_sharded_packing_plan(skcfg, abstract, pspecs,
                                         dict(mesh.shape))
        params = init_params(MODEL, jax.random.key(0))
        G = num_clients_of(mesh, topology)
        deltas = jax.tree.map(
            lambda p: jax.random.normal(jax.random.key(9),
                                        (G,) + p.shape, jnp.float32), params)
        key = jax.random.key(3)
        ref = jax.jit(lambda d, k: sharded_sketch_avg_desk(
            mesh, skcfg, pspecs, d, k, topology))(deltas, key)
        pkd = jax.jit(lambda d, k: sharded_sketch_avg_desk(
            mesh, skcfg, pspecs, d, k, topology, plan=plan))(deltas, key)
    _assert_trees_equal(ref, pkd)


# ---------------------------------------------------------------------------
# streamed shard-local sketch fold (DESIGN §12, ISSUE 9)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("mb", [1, 2])
def test_mesh_microbatch_streamed_fold_matches(mb):
    """With G_loc = 3 client rows per pod, microbatch=1/2 folds the
    shard-local sketch stage over chunks (mb=2 leaves a masked tail row)
    and reduces ONE (b_total,) partial sum + scalar weight over the client
    axes -- the result matches the materialized (G_loc, b_total) payload
    path up to float summation order, masked and unmasked."""
    topology = "cross_silo"
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    skcfg = SketchConfig(kind="countsketch", ratio=0.1, min_b=8)
    with use_mesh(mesh):
        abstract, pspecs = _mesh_pspecs(MODEL, topology)
        plan = make_sharded_packing_plan(skcfg, abstract, pspecs,
                                         dict(mesh.shape))
        params = init_params(MODEL, jax.random.key(0))
        G = 6                        # 3 rows per pod: the fold is exercised
        deltas = jax.tree.map(
            lambda p: jax.random.normal(jax.random.key(9),
                                        (G,) + p.shape, jnp.float32), params)
        key = jax.random.key(3)
        ref = jax.jit(lambda d, k: sharded_sketch_avg_desk(
            mesh, skcfg, pspecs, d, k, topology, plan=plan))(deltas, key)
        got = jax.jit(lambda d, k: sharded_sketch_avg_desk(
            mesh, skcfg, pspecs, d, k, topology, plan=plan,
            microbatch=mb))(deltas, key)
        mask = jnp.array([1., 0., 1., 1., 0., 1.])
        refm = jax.jit(lambda d, k: sharded_sketch_avg_desk(
            mesh, skcfg, pspecs, d, k, topology, plan=plan,
            part_mask=mask))(deltas, key)
        gotm = jax.jit(lambda d, k: sharded_sketch_avg_desk(
            mesh, skcfg, pspecs, d, k, topology, plan=plan, part_mask=mask,
            microbatch=mb))(deltas, key)
    for a, b in ((ref, got), (refm, gotm)):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=3e-5, atol=3e-6)


@needs8
def test_mesh_microbatch_ge_gloc_is_bitwise_pinned():
    """microbatch >= the shard-local cohort resolves to the materialized
    program: run_mesh_scan trajectories are bit-identical to microbatch
    absent (the mesh analogue of the single-host routing pin)."""
    mesh, cfg, smp = _mk("cross_silo")
    with use_mesh(mesh):
        key = jax.random.key(42)
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology="cross_silo")
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology="cross_silo",
                                   microbatch=64)
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal(p1, p2)
    _assert_trees_equal(o1, o2)


@needs8
def test_mesh_microbatch_hook_combinations_raise():
    """Streaming folds the payload before per-client rows exist: the
    staleness buffer and the fault/sentinel guard (materialized-row
    consumers) refuse to combine with it, as does fedopt (no sketch)."""
    mesh, cfg, smp = _mk("cross_silo")
    with use_mesh(mesh):
        with pytest.raises(NotImplementedError, match="microbatch"):
            train_mod._make_round_core(
                MODEL, cfg, mesh, "cross_silo", buffer=AsyncConfig(),
                microbatch=1)
        with pytest.raises(NotImplementedError, match="microbatch"):
            train_mod._make_round_core(
                MODEL, cfg, mesh, "cross_silo",
                sentinel=SentinelConfig(norm_mult=0.0), microbatch=1)
        with pytest.raises(ValueError, match="sketch"):
            train_mod._make_round_core(
                MODEL, train_mod._fedopt_cfg(cfg), mesh, "cross_silo",
                microbatch=1)


# ---------------------------------------------------------------------------
# quantized payload codec on the mesh driver (DESIGN §13)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_mesh_codec_none_is_bitwise_pinned(topology):
    """``codec=None`` must route at Python level: an explicit None equals
    the hookless mesh scan bit for bit (no traced neutral quantize)."""
    mesh, cfg, smp = _mk(topology)
    key = jax.random.key(42)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology)
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   codec=None)
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal(p1, p2)
    _assert_trees_equal(o1, o2)


@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_mesh_codec_runs_finite_with_static_measured_bits(topology):
    """The shard-sum codec (quantize-before-reduce) trains finite and
    reports the static measured uplink: payload_bits(b_total) per client
    shard, every shard transmitting its partial sum each round."""
    mesh, cfg, smp = _mk(topology)
    codec = CodecConfig(bits=8, error_feedback=False)
    key = jax.random.key(42)
    with use_mesh(mesh):
        p, o, h = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                rounds=3, key=key, topology=topology,
                                codec=codec)
        _, _, plan = train_mod._mesh_plan(MODEL, cfg, mesh, topology)
    n_shards = 1
    for ax in train_mod.client_axes_of(mesh, topology):
        n_shards *= mesh.shape[ax]
    assert np.isfinite(np.asarray(h["loss"])).all()
    np.testing.assert_array_equal(
        np.asarray(h["uplink_bits"]),
        float(codec.payload_bits(plan.b_total) * n_shards))
    # quantized trajectory is its own family: it moved vs the exact one
    _, _, h0 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg), rounds=3,
                             key=key, topology=topology)
    assert not np.array_equal(np.asarray(h["loss"]), np.asarray(h0["loss"]))


@needs8
def test_mesh_codec_hook_combinations_raise():
    """The mesh codec quantizes shard-local partial sums: materialized-row
    consumers (buffer, guard), telemetry, fedopt, and per-client error
    feedback all refuse to combine with it (DESIGN §13 hook matrix)."""
    from repro.obs import Telemetry
    mesh, cfg, smp = _mk("cross_silo")
    codec = CodecConfig(bits=8, error_feedback=False)
    with use_mesh(mesh):
        with pytest.raises(NotImplementedError, match="codec"):
            train_mod._make_round_core(MODEL, cfg, mesh, "cross_silo",
                                       buffer=AsyncConfig(), codec=codec)
        with pytest.raises(NotImplementedError, match="codec"):
            train_mod._make_round_core(
                MODEL, cfg, mesh, "cross_silo",
                sentinel=SentinelConfig(norm_mult=0.0), codec=codec)
        with pytest.raises(ValueError, match="telemetry"):
            train_mod._make_round_core(MODEL, cfg, mesh, "cross_silo",
                                       telemetry=Telemetry(), codec=codec)
        with pytest.raises(ValueError, match="no sketch payload"):
            train_mod._make_round_core(MODEL, train_mod._fedopt_cfg(cfg),
                                       mesh, "cross_silo", codec=codec)
        with pytest.raises(ValueError, match="error feedback"):
            train_mod._make_round_core(MODEL, cfg, mesh, "cross_silo",
                                       codec=CodecConfig(bits=8))


@needs8
def test_mesh_microbatch_codec_matches_materialized_codec():
    """Streaming the shard-local fold and quantizing the same partial sum:
    microbatch >= G_loc with a codec equals the materialized codec round
    bitwise (same quantizer input, same flat-shard-index RNG)."""
    mesh, cfg, smp = _mk("cross_silo")
    codec = CodecConfig(bits=8, error_feedback=False)
    key = jax.random.key(42)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology="cross_silo",
                                   codec=codec)
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology="cross_silo",
                                   codec=codec, microbatch=64)
    np.testing.assert_array_equal(np.asarray(h1["loss"]),
                                  np.asarray(h2["loss"]))
    _assert_trees_equal(p1, p2)
    _assert_trees_equal(o1, o2)


# ---------------------------------------------------------------------------
# repro.fed hooks on the mesh driver (ISSUE 5, DESIGN §9)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_mesh_hooks_allones_mask_and_delay0_buffer_pin_bitwise(topology):
    """The ISSUE 5 acceptance pin: ``run_mesh_scan(participation=...,
    buffer=...)`` with an all-ones mask and a delay=0 buffer reproduces the
    PR-4 hookless mesh trajectories bit for bit -- the masked cohort mean
    lowers to the unmasked pmean and the d > 0 arrival groups constant-fold
    away."""
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    key = jax.random.key(42)
    with use_mesh(mesh):
        p0, o0, h0 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology)
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   participation=FullParticipation(G))
        acfg = AsyncConfig(max_delay=0, delay="zero")
        p, _ = _fresh(cfg)
        st = init_mesh_async_state(MODEL, cfg, acfg, mesh, p, topology)
        p2, s2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, p, st, rounds=3,
                                   key=key, topology=topology, buffer=acfg,
                                   participation=FullParticipation(G))
    np.testing.assert_array_equal(h0["loss"], h1["loss"])
    _assert_trees_equal((p0, o0), (p1, o1))
    np.testing.assert_array_equal(h0["loss"], h2["loss"])
    _assert_trees_equal((p0, o0), (p2, s2["opt"]))


@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_mesh_masked_scan_matches_hooked_host_loop_bitwise(topology):
    """Partial cohorts on the mesh: the scanned driver and the hooked
    per-round step (same policy, base key + round index calling convention)
    agree bitwise, and the cohort actually changes the trajectory vs full
    participation."""
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    pol = UniformParticipation(G, frac=0.5, seed=7)
    key = jax.random.key(11)
    with use_mesh(mesh):
        step, _ = make_safl_train_step(MODEL, cfg, mesh, topology,
                                       participation=pol)
        p1, o1, h1 = run_mesh_host_loop(step, smp, *_fresh(cfg), rounds=3,
                                        key=key, donate=False,
                                        participation=pol)
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   participation=pol)
        _, _, h0 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                 rounds=3, key=key, topology=topology)
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal((p1, o1), (p2, o2))
    assert not np.array_equal(h0["loss"], h2["loss"])


@needs8
def test_mesh_async_buffer_scan_matches_hooked_host_loop_bitwise():
    """Real staleness on the mesh (stagger delays over a 3-deep ring): the
    ring buffer lives in the donated scan carry and per-generation
    desketching inside shard_map reproduces the hooked per-round loop
    bitwise; delayed arrivals change the trajectory."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    acfg = AsyncConfig(max_delay=2, delay="stagger", staleness_alpha=0.5)
    key = jax.random.key(3)

    def fresh_async():
        p, _ = _fresh(cfg)
        return p, init_mesh_async_state(MODEL, cfg, acfg, mesh, p, topology)

    with use_mesh(mesh):
        step, _ = make_safl_train_step(MODEL, cfg, mesh, topology,
                                       buffer=acfg)
        p1, s1, h1 = run_mesh_host_loop(step, smp, *fresh_async(), rounds=4,
                                        key=key, donate=False, buffer=acfg)
        p2, s2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *fresh_async(),
                                   rounds=4, key=key, topology=topology,
                                   buffer=acfg)
        _, _, h0 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                 rounds=4, key=key, topology=topology)
    assert np.isfinite(h2["loss"]).all()
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal((p1, s1), (p2, s2))
    assert not np.array_equal(h0["loss"], h2["loss"])


@needs8
def test_mesh_masked_scan_chunk_split_invariance():
    """Chunked masked+buffered dispatch == one dispatch, bitwise: cohorts
    and delays are pure functions of the absolute round index, and the ring
    buffer rethreads through the donated carry across dispatches."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    pol = UniformParticipation(G, frac=0.5, seed=5)
    acfg = AsyncConfig(max_delay=1, delay="stagger")
    key = jax.random.key(9)

    def run(chunk_size):
        p, _ = _fresh(cfg)
        st = init_mesh_async_state(MODEL, cfg, acfg, mesh, p, topology)
        return run_mesh_scan(MODEL, cfg, mesh, smp, p, st, rounds=4,
                             key=key, topology=topology, participation=pol,
                             buffer=acfg, chunk_size=chunk_size)

    with use_mesh(mesh):
        p1, s1, h1 = run(0)
        p2, s2, h2 = run(2)
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal((p1, s1), (p2, s2))


@needs8
def test_mesh_cohort_of_one():
    """Edge case: a single-client cohort on a (G, K) mesh -- the masked
    denominator is 1, the trajectory stays finite, and FixedCohort selects
    the same client every round (deterministic trajectory across runs)."""
    topology = "cross_device"
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    pol = FixedCohort(G, clients=(1,))
    key = jax.random.key(21)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   participation=pol)
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   participation=pol)
    assert np.isfinite(h1["loss"]).all()
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal((p1, o1), (p2, o2))


@needs8
def test_mesh_importance_uniform_probs_pins_to_uniform_policy():
    """ImportanceParticipation's weighted dict masks ride the mesh masked
    aggregation (static Horvitz-Thompson denominator inside the shard_map);
    with uniform probs the tilt is the identity and the trajectory pins
    bitwise to UniformParticipation."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    key = jax.random.key(13)
    uni = UniformParticipation(G, frac=0.5, seed=3)
    imp = ImportanceParticipation(G, probs=(1.0 / G,) * G, frac=0.5, seed=3)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   participation=uni)
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   participation=imp)
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    _assert_trees_equal((p1, o1), (p2, o2))


@needs8
def test_mesh_buffer_rejects_weighted_masks():
    """The mesh staleness buffer stores 0/1 cohort masks per generation;
    an importance-sampling policy's weighted dict mask must be rejected at
    trace time with a clear error, not silently mis-aggregated."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    imp = ImportanceParticipation(G, probs=(1.0 / G,) * G, frac=0.5, seed=3)
    acfg = AsyncConfig(max_delay=1, delay="stagger")
    with use_mesh(mesh):
        p, _ = _fresh(cfg)
        st = init_mesh_async_state(MODEL, cfg, acfg, mesh, p, topology)
        with pytest.raises(TypeError, match="weighted.*masks"):
            run_mesh_scan(MODEL, cfg, mesh, smp, p, st, rounds=2,
                          key=jax.random.key(0), topology=topology,
                          participation=imp, buffer=acfg)


@needs8
def test_mesh_buffer_guards():
    """Build-time guards: fedopt (sketch.kind='none') cannot ride the
    sketch-space buffer, and a policy built for the wrong client count is
    rejected before any tracing."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    acfg = AsyncConfig(max_delay=1)
    with use_mesh(mesh):
        p, o = _fresh(cfg)
        with pytest.raises(ValueError, match="sketch space"):
            make_fedopt_scan_fn(MODEL, cfg, mesh, topology, sampler=smp,
                                num_rounds=2, buffer=acfg)
        with pytest.raises(ValueError, match="num_clients"):
            run_mesh_scan(MODEL, cfg, mesh, smp, p, o, rounds=2,
                          key=jax.random.key(0), topology=topology,
                          participation=UniformParticipation(16, frac=0.5))


# ---------------------------------------------------------------------------
# faults + sentinels on the mesh (ISSUE 7, DESIGN §10)
# ---------------------------------------------------------------------------

def _fault_row(code, G, client=1):
    return tuple(code if c == client else FAULT_OK for c in range(G))


@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_mesh_neutral_faults_bitwise(topology):
    """A neutral fault policy (all rates 0) on the mesh scan == the
    hookless PR-4 trajectory, bit for bit -- the fault spec multiplies the
    replicated weight vector by all-ones arrivals and the payload by 1.0,
    and the guarded aggregation still pays exactly one payload psum."""
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    key = jax.random.key(7)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology)
        p2, o2, h2 = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=3, key=key, topology=topology,
                                   faults=FaultConfig(num_clients=G))
    _assert_trees_equal((p1, o1), (p2, o2))
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    assert np.asarray(h2["n_dropped"]).sum() == 0


@needs8
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_mesh_nan_equals_drop_bitwise(topology):
    """Sentinel rejection of a NaN-corrupted client == dropping that
    client, bitwise, on the mesh: per-client finite verdicts are made
    globally consistent by one (G,)-stats psum over ALL mesh axes (model
    axes combine chunks of a row, client axes merge disjoint rows), then
    folded into the same replicated weight vector a dropout uses."""
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    key = jax.random.key(7)
    sent = SentinelConfig(norm_mult=10.0)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(
            MODEL, cfg, mesh, smp, *_fresh(cfg), rounds=3, key=key,
            topology=topology, sentinel=sent,
            faults=FaultTable(codes=(_fault_row(FAULT_NAN, G),) * 2))
        p2, o2, h2 = run_mesh_scan(
            MODEL, cfg, mesh, smp, *_fresh(cfg), rounds=3, key=key,
            topology=topology, sentinel=sent,
            faults=FaultTable(codes=(_fault_row(FAULT_DROP, G),) * 2))
    _assert_trees_equal((p1, o1), (p2, o2))
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    assert np.isfinite(h1["loss"]).all()
    for x in jax.tree.leaves(p1):
        assert np.isfinite(np.asarray(x)).all()
    assert np.asarray(h1["n_rejected"]).sum() == 2
    assert np.asarray(h2["n_dropped"]).sum() == 2


@needs8
def test_mesh_byzantine_rejected_by_norm_sentinel():
    """A Byzantine-scaled payload passes the finite check but its sketch
    norm -- summed across model-parallel chunks by the same stats psum --
    trips the median rule; the run matches the drop-masked twin bitwise."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    key = jax.random.key(7)
    sent = SentinelConfig(norm_mult=10.0)
    with use_mesh(mesh):
        p1, o1, h1 = run_mesh_scan(
            MODEL, cfg, mesh, smp, *_fresh(cfg), rounds=3, key=key,
            topology=topology, sentinel=sent,
            faults=FaultTable(codes=(_fault_row(FAULT_BYZ, G),) * 2,
                              byzantine_scale=1e4))
        p2, o2, h2 = run_mesh_scan(
            MODEL, cfg, mesh, smp, *_fresh(cfg), rounds=3, key=key,
            topology=topology, sentinel=sent,
            faults=FaultTable(codes=(_fault_row(FAULT_DROP, G),) * 2))
    _assert_trees_equal((p1, o1), (p2, o2))
    assert np.asarray(h1["n_rejected"]).sum() == 2


@needs8
def test_mesh_buffered_guarded_nan_equals_drop():
    """Through the mesh ring buffer: payloads are vetted BEFORE the push,
    so a NaN generation never re-emits at later pops and the trajectory
    (params/opt/loss) matches the drop twin bitwise.  Ring CONTENTS may
    differ where weights are 0 (zeroed vs honest row), so the ring is
    checked for finiteness, not equality."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    G = num_clients_of(mesh, topology)
    acfg = AsyncConfig(max_delay=2, delay="stagger", staleness_alpha=0.5)
    key = jax.random.key(3)
    sent = SentinelConfig(norm_mult=10.0)

    def fresh_async():
        p, _ = _fresh(cfg)
        return p, init_mesh_async_state(MODEL, cfg, acfg, mesh, p, topology)

    with use_mesh(mesh):
        p1, s1, h1 = run_mesh_scan(
            MODEL, cfg, mesh, smp, *fresh_async(), rounds=4, key=key,
            topology=topology, buffer=acfg, sentinel=sent,
            faults=FaultTable(codes=(_fault_row(FAULT_NAN, G),) * 2))
        p2, s2, h2 = run_mesh_scan(
            MODEL, cfg, mesh, smp, *fresh_async(), rounds=4, key=key,
            topology=topology, buffer=acfg, sentinel=sent,
            faults=FaultTable(codes=(_fault_row(FAULT_DROP, G),) * 2))
    _assert_trees_equal((p1, s1["opt"]), (p2, s2["opt"]))
    np.testing.assert_array_equal(h1["loss"], h2["loss"])
    assert np.isfinite(np.asarray(s1["buf"])).all()
    assert np.asarray(h1["n_rejected"]).sum() == 2


@needs8
def test_mesh_fault_hook_guards():
    """Build-time guards: sketch-space faults/sentinels cannot ride the
    fedopt (sketch.kind='none') route, and a fault policy built for the
    wrong client count is rejected before tracing."""
    topology = "cross_silo"
    mesh, cfg, smp = _mk(topology)
    cfg_none = _mk(topology, "none")[1]
    G = num_clients_of(mesh, topology)
    with use_mesh(mesh):
        p, o = _fresh(cfg)
        with pytest.raises(ValueError, match="sketch"):
            run_mesh_scan(MODEL, cfg_none, mesh, smp, p, o, rounds=2,
                          key=jax.random.key(0), topology=topology,
                          faults=FaultConfig(num_clients=G))
        with pytest.raises(ValueError, match="clients"):
            run_mesh_scan(MODEL, cfg, mesh, smp, p, o, rounds=2,
                          key=jax.random.key(0), topology=topology,
                          faults=FaultConfig(num_clients=16))


# ---------------------------------------------------------------------------
# observability (DESIGN §11): probes + streamed shards on the mesh driver
# ---------------------------------------------------------------------------

@needs8
def test_mesh_telemetry_probes_and_stream(tmp_path):
    """Telemetry on the mesh scan: the Δ̄-based probes (computed OUTSIDE
    the sketch shard_map) land in the history with the full-cohort count,
    and attaching a stream= writer is pure host-side I/O -- params bitwise
    unchanged, shard rows equal to the in-memory history value-for-value."""
    import glob
    import json

    from repro.obs import ShardWriter, Telemetry

    mesh, cfg, smp = _mk("cross_device")
    with use_mesh(mesh):
        pA, oA, hA = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=4, key=jax.random.key(3),
                                   chunk_size=2, telemetry=Telemetry())
        stream = ShardWriter(str(tmp_path / "obs"))
        pB, oB, hB = run_mesh_scan(MODEL, cfg, mesh, smp, *_fresh(cfg),
                                   rounds=4, key=jax.random.key(3),
                                   chunk_size=2, telemetry=Telemetry(),
                                   stream=stream)
    _assert_trees_equal(pA, pB)
    _assert_trees_equal(oA, oB)
    assert hB == {}                   # streamed: the shards are the record
    G = num_clients_of(mesh, "cross_device")
    np.testing.assert_array_equal(hA["cohort"], np.full(4, float(G)))
    assert np.isfinite(hA["residual"]).all() and (hA["residual"] >= 0).all()
    assert (hA["delta_norm"] > 0).all() and (hA["m_norm"] > 0).all()
    rows = []
    for path in sorted(glob.glob(str(tmp_path / "obs" / "metrics-*.jsonl"))):
        with open(path) as f:
            rows += [json.loads(ln) for ln in f]
    assert [r["t"] for r in rows] == list(range(4))
    for i, row in enumerate(rows):
        assert set(row) - {"kind", "t"} == set(hA)
        for k in hA:
            assert row[k] == float(hA[k][i])


# ---------------------------------------------------------------------------
# single-device fallback: re-run this module on 8 forced CPU devices
# ---------------------------------------------------------------------------

@pytest.mark.skipif(ON_8, reason="already running on >= 8 devices")
@pytest.mark.skipif(os.environ.get("MESH_SCAN_NO_SUBPROCESS") == "1",
                    reason="suppressed: a dedicated 8-device step runs the "
                           "suite directly (ci.yml), or we ARE the "
                           "subprocess (re-entry guard)")
def test_mesh_scan_suite_on_8_forced_devices_subprocess():
    """Tier-1 coverage on a single-device session: run this module's direct
    tests in a subprocess with the 8-device host flag (which must never leak
    into the main test session, DESIGN §5)."""
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH",
                                                       "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           # the device-count flag only affects the CPU backend: pin it so a
           # GPU machine cannot land back on < 8 devices and recurse
           "JAX_PLATFORMS": "cpu",
           "MESH_SCAN_NO_SUBPROCESS": "1"}
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    tail = r.stdout[-3000:] + "\n" + r.stderr[-2000:]
    assert r.returncode == 0, tail
    assert " passed" in r.stdout, tail   # not everything skipped
