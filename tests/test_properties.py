"""Hypothesis property-based tests on the system's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional test dep (pip install -e .[test]); suite must still collect")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import sketch as S
from repro.core.adaptive import AdaConfig, apply_update, init_opt_state

KIND = st.sampled_from(["gaussian", "srht", "countsketch"])


@settings(max_examples=25, deadline=None)
@given(kind=KIND, n=st.integers(4, 400), seed=st.integers(0, 2**31 - 1),
       a=st.floats(-5, 5, allow_nan=False), b=st.floats(-5, 5, allow_nan=False))
def test_sketch_linearity_property(kind, n, seed, a, b):
    """Property 1 holds for every size/seed/coefficient combination."""
    cfg = S.SketchConfig(kind=kind, ratio=0.5, min_b=4)
    key = jax.random.key(seed)
    kv = jax.random.key(seed + 1)
    v = jax.random.normal(kv, (n,))
    w = jax.random.normal(jax.random.fold_in(kv, 1), (n,))
    lhs = S.sk_leaf(cfg, key, a * v + b * w)
    rhs = a * S.sk_leaf(cfg, key, v) + b * S.sk_leaf(cfg, key, w)
    scale = float(jnp.abs(lhs).max()) + 1.0
    np.testing.assert_allclose(np.array(lhs), np.array(rhs),
                               atol=5e-4 * scale)


@settings(max_examples=25, deadline=None)
@given(kind=KIND, n=st.integers(8, 300), seed=st.integers(0, 2**31 - 1))
def test_roundtrip_shape_and_finite(kind, n, seed):
    cfg = S.SketchConfig(kind=kind, ratio=0.3, min_b=4)
    key = jax.random.key(seed)
    v = jax.random.normal(jax.random.fold_in(key, 7), (n,))
    rt = S.desk_leaf(cfg, key, S.sk_leaf(cfg, key, v), n)
    assert rt.shape == (n,)
    assert bool(jnp.isfinite(rt).all())


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 2000), ratio=st.floats(0.001, 1.0))
def test_sketch_size_monotone_and_bounded(n, ratio):
    cfg = S.SketchConfig(kind="countsketch", ratio=ratio, min_b=2)
    b = S.leaf_sketch_size(n, cfg)
    assert 1 <= b <= n
    cfg2 = S.SketchConfig(kind="countsketch", ratio=min(1.0, ratio * 2),
                          min_b=2)
    assert S.leaf_sketch_size(n, cfg2) >= b


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       steps=st.integers(1, 8))
def test_amsgrad_vhat_never_decreases(seed, steps):
    """Alg. 2 invariant: v-hat is element-wise non-decreasing."""
    cfg = AdaConfig(name="amsgrad", lr=0.01)
    key = jax.random.key(seed)
    params = {"w": jax.random.normal(key, (16,))}
    state = init_opt_state(cfg, params)
    prev = np.zeros(16)
    for t in range(steps):
        u = {"w": jax.random.normal(jax.random.fold_in(key, t), (16,))}
        params, state = apply_update(cfg, state, params, u)
        vh = np.array(state["vhat"]["w"])
        assert (vh >= prev - 1e-12).all()
        prev = vh


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_update_descends_along_update_direction(seed):
    """ADA_OPT always moves against the (sign of the) update direction
    coordinate-wise (positive preconditioner)."""
    cfg = AdaConfig(name="amsgrad", lr=0.1)
    key = jax.random.key(seed)
    params = {"w": jax.random.normal(key, (8,))}
    u = {"w": jax.random.normal(jax.random.fold_in(key, 1), (8,))}
    p2, _ = apply_update(cfg, init_opt_state(cfg, params), params, u)
    dw = np.array(p2["w"] - params["w"])
    uw = np.array(u["w"])
    nz = np.abs(uw) > 1e-6
    assert (np.sign(dw[nz]) == -np.sign(uw[nz])).all()


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 9))
def test_fwht_energy_preservation(n):
    """Parseval: ||Hx||^2 = n ||x||^2 for the unnormalized transform."""
    size = 1 << n
    x = jax.random.normal(jax.random.key(n), (size,))
    y = S.fwht(x)
    np.testing.assert_allclose(float(y @ y), size * float(x @ x), rtol=1e-3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), g=st.integers(1, 5))
def test_sketch_mergeability(seed, g):
    """Mean of client sketches == sketch of client mean (exact, any G)."""
    cfg = S.SketchConfig(kind="countsketch", ratio=0.5, min_b=4)
    key = jax.random.key(seed)
    vs = [jax.random.normal(jax.random.fold_in(key, i), (64,))
          for i in range(g)]
    sks = [S.sk_leaf(cfg, key, v) for v in vs]
    mean_sk = sum(np.array(s) for s in sks) / g
    sk_mean = np.array(S.sk_leaf(cfg, key, sum(vs) / g))
    np.testing.assert_allclose(mean_sk, sk_mean, atol=1e-4)


# ---------------------------------------------------------------------------
# participation policies (ISSUE 4 satellite): fed/participation.py invariants
# ---------------------------------------------------------------------------

from repro.fed.participation import (ImportanceParticipation,  # noqa: E402
                                     UniformParticipation, round_variates)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 24), frac=st.floats(1e-3, 1.0),
       seed=st.integers(0, 2**31 - 1), t=st.integers(0, 10_000))
def test_participation_cohort_bounds_and_no_replacement(n, frac, seed, t):
    """For ANY frac in (0, 1]: the mask is strictly 0/1 (no client counted
    twice -- sampling without replacement), the cohort size is within
    [1, N], and the mask sums to exactly the declared cohort size."""
    pol = UniformParticipation(n, frac=frac, seed=seed)
    m = np.asarray(pol.mask(jnp.asarray(t, jnp.int32)))
    assert m.shape == (n,)
    assert set(np.unique(m)).issubset({np.float32(0.0), np.float32(1.0)})
    assert 1 <= pol.cohort_size <= n
    assert int(m.sum()) == pol.cohort_size


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 16), extra=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1), t=st.integers(0, 10_000))
def test_participation_pure_in_round_client_seed(n, extra, seed, t):
    """The per-client variate stream is a pure function of
    (round, client, seed): a fresh policy instance reproduces the mask
    bitwise, and client c's variate does not change when clients are added
    (the N-independence the device data sampler also guarantees)."""
    tt = jnp.asarray(t, jnp.int32)
    pol = UniformParticipation(n, frac=0.5, seed=seed)
    m1 = np.asarray(pol.mask(tt))
    m2 = np.asarray(UniformParticipation(n, frac=0.5, seed=seed).mask(tt))
    np.testing.assert_array_equal(m1, m2)
    u_small = np.asarray(round_variates(n, seed, tt))
    u_large = np.asarray(round_variates(n + extra, seed, tt))
    np.testing.assert_array_equal(u_small, u_large[:n])
    if n >= 4:
        # different rounds draw different variates (collision probability
        # across 4+ f32 uniforms is negligible)
        u_next = np.asarray(round_variates(n, seed, tt + 1))
        assert not np.array_equal(u_small, u_next)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 12), seed=st.integers(0, 2**31 - 1),
       t=st.integers(0, 10_000),
       raw=st.lists(st.floats(0.05, 1.0), min_size=2, max_size=12))
def test_importance_mask_invariants(n, seed, t, raw):
    """ImportanceParticipation: exactly m clients sampled (no replacement),
    sampled weights equal 1/(N p_c), static denominator m, and the mask is
    reproducible from a fresh policy instance."""
    raw = (raw * n)[:n]
    probs = tuple(float(p) / sum(raw) for p in raw)
    # renormalize the tail element so the tuple sums to 1 within 1e-6
    probs = probs[:-1] + (1.0 - sum(probs[:-1]),)
    # stay inside the policy's validity regime m * max(p) <= 1
    m = max(1, min(n // 2, int(1.0 / max(probs))))
    pol = ImportanceParticipation(n, probs=probs, frac=m / n, seed=seed)
    assert pol.cohort_size == m
    tt = jnp.asarray(t, jnp.int32)
    mask = pol.mask(tt)
    w = np.asarray(mask["w"])
    sel = w > 0
    assert int(sel.sum()) == pol.cohort_size == mask["n"]
    assert mask["den"] == float(pol.cohort_size)
    np.testing.assert_allclose(
        w[sel], (1.0 / (n * np.asarray(probs, np.float64)))[sel], rtol=1e-5)
    # the same policy, built again
    m2 = ImportanceParticipation(n, probs=probs, frac=m / n,
                                 seed=seed).mask(tt)
    np.testing.assert_array_equal(w, np.asarray(m2["w"]))


# ---------------------------------------------------------------------------
# async staleness buffer (ISSUE 5): the shared arrival-schedule invariants
# ---------------------------------------------------------------------------

from repro.fed.async_buffer import AsyncConfig, arrival_weight  # noqa: E402


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 12), max_delay=st.integers(0, 4),
       delay=st.sampled_from(["zero", "stagger", "uniform"]),
       alpha=st.floats(0.0, 2.0, allow_nan=False),
       seed=st.integers(0, 2**31 - 1), g=st.integers(0, 10_000))
def test_arrival_weight_every_payload_pops_exactly_once(n, max_delay, delay,
                                                        alpha, seed, g):
    """For ANY policy/seed/generation: delays land in [0, D), so summed over
    all pop delays every client's payload arrives EXACTLY once (no payload
    lost before its ring slot is recycled, none double-counted), each
    nonzero weight is exactly the FedBuff discount (1+d)^-alpha, and the
    schedule is reproducible (pure in (g, d, seed)) -- the contract both
    the single-host and the mesh ring buffers pop against."""
    acfg = AsyncConfig(max_delay=max_delay, delay=delay,
                       staleness_alpha=alpha, seed=seed)
    gg = jnp.asarray(g, jnp.int32)
    total = np.zeros((n,))
    for d in range(acfg.buffer_rounds):
        w = np.asarray(arrival_weight(acfg, gg, d, n))
        w2 = np.asarray(arrival_weight(acfg, gg, d, n))
        np.testing.assert_array_equal(w, w2)
        disc = np.float32((1.0 + d) ** -alpha)   # the f32 the buffer applies
        arrived = w > 0
        np.testing.assert_array_equal(w[arrived],
                                      np.full(int(arrived.sum()), disc))
        total += arrived
    np.testing.assert_array_equal(total, np.ones((n,)))
