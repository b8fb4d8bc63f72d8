"""Packed sketch engine: parity vs the per-leaf reference + derive-once.

The per-leaf path (repro.core.sketch) is the reference implementation; the
packed engine (repro.core.packed) must reproduce it exactly -- same round
key, same per-leaf fold_in derivation, same values -- while deriving the
operator params once per (round, leaf) instead of once per (round, leaf,
side-of-the-round-trip).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packed as P
from repro.core import sketch as S

KINDS = ["countsketch", "srht", "gaussian"]
# (kind, extra SketchConfig kwargs): covers both count-sketch hash families
VARIANTS = [("countsketch", {}), ("countsketch", {"cs_hash": "independent"}),
            ("srht", {}), ("gaussian", {})]
V_IDS = ["countsketch_balanced", "countsketch_independent", "srht", "gaussian"]


def _tree():
    return {
        "w": jax.random.normal(jax.random.key(0), (12, 7), jnp.bfloat16),
        "b": jax.random.normal(jax.random.key(1), (5,)),       # raw (b >= n)
        "s": jnp.float32(2.0),                                 # scalar leaf
        "big": jax.random.normal(jax.random.key(2), (40, 25)),
        "big2": jax.random.normal(jax.random.key(3), (40, 25)),  # same-shape group
    }


def _cfg(kind, **kw):
    return S.SketchConfig(kind=kind, ratio=0.3, min_b=8, **kw)


def _ref_payload(cfg, key, tree):
    """Concatenated per-leaf reference sketches, in packed payload order."""
    return jnp.concatenate([
        l.reshape(-1) for l in jax.tree.leaves(S.sketch_tree(cfg, key, tree))])


@pytest.mark.parametrize("kind,kw", VARIANTS + [("none", {})],
                         ids=V_IDS + ["none"])
def test_sk_desk_parity_per_tensor(kind, kw):
    tree, key = _tree(), jax.random.key(9)
    cfg = _cfg(kind, **kw)
    plan = P.make_packing_plan(cfg, tree)
    rp = P.derive_round_params(plan, key)

    pay = P.sk_packed(plan, rp, tree)
    assert pay.shape == (plan.b_total,) and pay.dtype == cfg.transport_dtype
    np.testing.assert_allclose(np.array(pay, np.float32),
                               np.array(_ref_payload(cfg, key, tree),
                                        np.float32), atol=1e-5)

    out = P.desk_packed(plan, rp, pay)
    ref = S.desketch_tree(cfg, key, S.sketch_tree(cfg, key, tree), tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.array(a, np.float32),
                                   np.array(b, np.float32), atol=1e-4)


@pytest.mark.parametrize("kind,kw", VARIANTS, ids=V_IDS)
def test_parity_concat_mode(kind, kw):
    tree, key = _tree(), jax.random.key(11)
    cfg = _cfg(kind, mode="concat", **kw)
    plan = P.make_packing_plan(cfg, tree)
    rp = P.derive_round_params(plan, key)
    pay = P.sk_packed(plan, rp, tree)
    ref = S.sketch_tree(cfg, key, tree)
    np.testing.assert_allclose(np.array(pay, np.float32),
                               np.array(ref, np.float32), atol=1e-5)
    out = P.desk_packed(plan, rp, pay)
    ref_out = S.desketch_tree(cfg, key, ref, tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref_out)):
        np.testing.assert_allclose(np.array(a, np.float32),
                                   np.array(b, np.float32), atol=1e-4)


@pytest.mark.parametrize("kind,kw", VARIANTS, ids=V_IDS)
def test_parity_under_client_vmap(kind, kw):
    """vmap over the client axis == per-client per-leaf reference."""
    tree, key = _tree(), jax.random.key(13)
    cfg = _cfg(kind, **kw)
    plan = P.make_packing_plan(cfg, tree)
    rp = P.derive_round_params(plan, key)
    stacked = jax.tree.map(
        lambda l: jnp.stack([l, 2 * l.astype(jnp.float32).astype(l.dtype),
                             -l]), tree)
    got = P.sk_packed_clients(plan, rp, stacked)
    assert got.shape == (3, plan.b_total)
    want = jax.vmap(lambda t: _ref_payload(cfg, key, t))(stacked)
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), atol=1e-5)


@pytest.mark.parametrize("kind,kw", [("countsketch", {"cs_hash": "independent"}),
                                     ("srht", {})],
                         ids=["countsketch_independent", "srht"])
def test_parity_use_pallas(kind, kw):
    """The Pallas route (interpret=True on CPU) matches the jnp reference."""
    tree, key = _tree(), jax.random.key(17)
    cfg = _cfg(kind, use_pallas=True, **kw)
    cfg_ref = _cfg(kind, **kw)
    plan = P.make_packing_plan(cfg, tree)
    rp = P.derive_round_params(plan, key)
    pay = P.sk_packed(plan, rp, tree)
    np.testing.assert_allclose(np.array(pay),
                               np.array(_ref_payload(cfg_ref, key, tree)),
                               rtol=1e-3, atol=1e-3)
    out = P.desk_packed(plan, rp, pay)
    ref = S.desketch_tree(cfg_ref, key,
                          S.sketch_tree(cfg_ref, key, tree), tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.array(a, np.float32),
                                   np.array(b, np.float32),
                                   rtol=1e-3, atol=1e-3)


def test_parity_use_pallas_clients_batched():
    """Multi-client pallas path: ONE batched launch == vmapped reference."""
    tree, key = _tree(), jax.random.key(19)
    cfg = _cfg("countsketch", use_pallas=True, cs_hash="independent")
    plan = P.make_packing_plan(cfg, tree)
    rp = P.derive_round_params(plan, key)
    stacked = jax.tree.map(lambda l: jnp.stack([l, -l, 2 * l, 0 * l]), tree)
    got = P.sk_packed_clients(plan, rp, stacked)
    want = jax.vmap(
        lambda t: _ref_payload(_cfg("countsketch", cs_hash="independent"),
                               key, t))(stacked)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", KINDS)
def test_roundtrip_packed_jits(kind):
    tree, key = _tree(), jax.random.key(23)
    cfg = _cfg(kind)
    plan = P.make_packing_plan(cfg, tree)
    out = jax.jit(functools.partial(P.roundtrip_packed, plan))(key, tree)
    ref = S.roundtrip_tree(cfg, key, tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.array(a, np.float32),
                                   np.array(b, np.float32), atol=1e-4)


# ---------------------------------------------------------------------------
# derive-once: hashes/signs exist exactly once per (round, leaf)
# ---------------------------------------------------------------------------

def _count_calls(monkeypatch, name):
    counter = {"n": 0}
    orig = getattr(S, name)

    def wrapped(*a, **kw):
        counter["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(S, name, wrapped)
    monkeypatch.setattr(P, name, wrapped)
    return counter


def test_countsketch_hashes_derived_once_per_round(monkeypatch):
    """Packed round trip: one _cs_hashes derivation per (n, b) GROUP (the
    vmapped batch covers every leaf of the group); the per-leaf reference
    re-derives per leaf on BOTH sides of the round trip."""
    tree, key = _tree(), jax.random.key(29)
    cfg = _cfg("countsketch", cs_hash="independent")
    plan = P.make_packing_plan(cfg, tree)
    n_groups = len({(op.n, op.b) for op in plan.ops if not op.raw})
    n_leaves = sum(1 for op in plan.ops if not op.raw)
    assert n_groups < n_leaves  # the tree has same-shape leaves to batch

    counter = _count_calls(monkeypatch, "_cs_hashes")
    rp = P.derive_round_params(plan, key)
    P.desk_packed(plan, rp, P.sk_packed(plan, rp, tree))
    assert counter["n"] == n_groups, counter["n"]

    counter["n"] = 0
    S.desketch_tree(cfg, key, S.sketch_tree(cfg, key, tree), tree)
    assert counter["n"] == 2 * n_leaves, counter["n"]  # sk side + desk side


def test_srht_params_derived_once_per_round(monkeypatch):
    tree, key = _tree(), jax.random.key(31)
    cfg = _cfg("srht")
    plan = P.make_packing_plan(cfg, tree)
    n_groups = len({(op.n, op.b) for op in plan.ops if not op.raw})
    n_leaves = sum(1 for op in plan.ops if not op.raw)

    counter = _count_calls(monkeypatch, "_srht_params")
    rp = P.derive_round_params(plan, key)
    P.desk_packed(plan, rp, P.sk_packed(plan, rp, tree))
    assert counter["n"] == n_groups, counter["n"]

    counter["n"] = 0
    S.desketch_tree(cfg, key, S.sketch_tree(cfg, key, tree), tree)
    assert counter["n"] == 2 * n_leaves, counter["n"]


def test_balanced_params_derived_once_per_round(monkeypatch):
    """The default (balanced) family also derives once per (n, b) group per
    round trip, vs twice per leaf in the per-leaf loop."""
    tree, key = _tree(), jax.random.key(41)
    cfg = _cfg("countsketch")  # balanced is the default family
    plan = P.make_packing_plan(cfg, tree)
    n_groups = len({(op.n, op.b) for op in plan.ops if not op.raw})
    n_leaves = sum(1 for op in plan.ops if not op.raw)

    counter = _count_calls(monkeypatch, "_balanced_cs_params")
    rp = P.derive_round_params(plan, key)
    P.desk_packed(plan, rp, P.sk_packed(plan, rp, tree))
    assert counter["n"] == n_groups, counter["n"]

    counter["n"] = 0
    S.desketch_tree(cfg, key, S.sketch_tree(cfg, key, tree), tree)
    assert counter["n"] == 2 * n_leaves, counter["n"]


def test_sk_and_desk_share_cached_params():
    """sk side and desk side consume the SAME round-param arrays (no
    re-derivation anywhere in the round trip), and re-derivation with the
    same key is deterministic."""
    tree, key = _tree(), jax.random.key(37)
    plan = P.make_packing_plan(_cfg("countsketch", cs_hash="independent"), tree)
    rp1 = P.derive_round_params(plan, key)
    rp2 = P.derive_round_params(plan, key)
    np.testing.assert_array_equal(np.array(rp1["h"]), np.array(rp2["h"]))
    np.testing.assert_array_equal(np.array(rp1["s"]), np.array(rp2["s"]))


# ---------------------------------------------------------------------------
# plan bookkeeping
# ---------------------------------------------------------------------------

def test_plan_payload_matches_per_leaf_sizes():
    tree = _tree()
    for kind in KINDS + ["none"]:
        cfg = _cfg(kind)
        plan = P.make_packing_plan(cfg, tree)
        assert plan.b_total == sum(S.tree_sketch_sizes(cfg, tree))
        assert plan.d_total == sum(
            int(np.prod(l.shape)) if l.shape else 1
            for l in jax.tree.leaves(tree))


def test_total_sketch_bits_through_plan():
    cfg = S.SketchConfig(kind="countsketch", ratio=0.1, min_b=8)
    tree = {"a": jnp.zeros((100,)), "b": jnp.zeros((50, 10))}
    assert S.total_sketch_bits(cfg, tree) == \
        sum(S.tree_sketch_sizes(cfg, tree)) * 32
    # concat mode counts the single concatenated payload
    ccfg = S.SketchConfig(kind="countsketch", ratio=0.1, min_b=8, mode="concat")
    assert S.total_sketch_bits(ccfg, tree) == \
        S.leaf_sketch_size(600, ccfg) * 32


def test_pack_unpack_roundtrip_identity():
    tree = _tree()
    plan = P.make_packing_plan(_cfg("countsketch"), tree)
    flat = P.pack_tree(plan, tree)
    assert flat.shape == (plan.d_total,) and flat.dtype == jnp.float32
    out = P.unpack_tree(plan, flat)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.array(a, np.float32),
                                   np.array(b, np.float32), atol=1e-2)


def test_safl_round_matches_per_leaf_composition():
    """safl_round (packed) == the same round composed from the per-leaf
    reference ops -- the refactor changes the dispatch, not the math."""
    from repro.core.adaptive import AdaConfig, apply_update
    from repro.core.safl import SAFLConfig, client_delta, init_safl, safl_round

    key = jax.random.key(0)
    W = jax.random.normal(jax.random.fold_in(key, 1), (16, 4))
    x = jax.random.normal(jax.random.key(2), (32, 16))
    batch = jax.tree.map(
        lambda t: t.reshape(4, 2, 4, *t.shape[1:]), {"x": x, "y": x @ W})
    loss_fn = lambda p, b: jnp.mean((b["x"] @ p["W"] - b["y"]) ** 2)
    params = {"W": jnp.zeros((16, 4))}

    cfg = SAFLConfig(sketch=S.SketchConfig(kind="countsketch", ratio=0.5,
                                           min_b=4),
                     server=AdaConfig(name="amsgrad", lr=0.05),
                     client_lr=0.05, local_steps=2)
    rk = jax.random.key(77)
    p1, _, _ = safl_round(cfg, loss_fn, params, init_safl(cfg, params),
                          batch, rk)

    # reference composition with the per-leaf ops
    eta = jnp.asarray(cfg.client_lr, jnp.float32)
    deltas, _ = jax.vmap(
        lambda mb: client_delta(cfg, loss_fn, params, mb, eta))(batch)
    sks = jax.vmap(lambda d: S.sketch_tree(cfg.sketch, rk, d))(deltas)
    mbar = jax.tree.map(lambda s: jnp.mean(s, axis=0), sks)
    update = S.desketch_tree(cfg.sketch, rk, mbar, params)
    p2, _ = apply_update(cfg.server, init_safl(cfg, params), params, update)
    np.testing.assert_allclose(np.array(p1["W"]), np.array(p2["W"]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# balanced count-sketch: the row rotation against its two oracles
# ---------------------------------------------------------------------------

def _balanced_sk_gather(v, r, s, b):
    """The element-gather form the row rotation replaced, kept as an oracle:
    out[j] = sum_k x[k, (j - r_k) mod b] through take_along_axis."""
    n, m = v.shape[0], r.shape[0]
    x = jnp.pad(v * s.astype(v.dtype), (0, m * b - n)).reshape(m, b)
    idx = (jnp.arange(b)[None, :] - r[:, None]) % b
    return jnp.take_along_axis(x, idx, axis=1).sum(axis=0)


def _balanced_sk_loop(v, r, s, b):
    """NumPy loop over the rows, in float64: element (k, c) goes to slot
    (c + r_k) mod b.  Returns the sketch and, per slot, the sum of the
    magnitudes added there (for the float32 summation error bound)."""
    v, r, s = (np.asarray(a, np.float64) for a in (v, r, s))
    m = r.shape[0]
    x = np.zeros(m * b)
    x[:v.shape[0]] = v * s
    out, mag = np.zeros(b), np.zeros(b)
    for k in range(m):
        slots = (np.arange(b) + int(r[k])) % b
        np.add.at(out, slots, x[k * b:(k + 1) * b])
        np.add.at(mag, slots, np.abs(x[k * b:(k + 1) * b]))
    return out, mag


# (n, b): n not a multiple of b with b < 128; n a multiple of b = 128; b not
# a multiple of 128 above it; m = 1 exactly and with padding
BAL_SHAPES = [(1000, 21), (4096, 128), (5000, 300), (700, 700), (650, 700)]


@pytest.mark.parametrize("G", [1, 5, 16])
@pytest.mark.parametrize("n,b", BAL_SHAPES)
def test_balanced_sk_rotation_matches_gather_and_loop(n, b, G):
    r, s = S._balanced_cs_params(jax.random.key(n + b), n, b)
    m = r.shape[0]
    V = jax.random.normal(jax.random.key(G), (G, n))
    got = np.asarray(jax.jit(jax.vmap(
        lambda v: S._balanced_sk_core(v, r, s, b)))(V))
    assert got.shape == (G, b)
    gather = np.asarray(jax.vmap(lambda v: _balanced_sk_gather(v, r, s, b))(V))
    eps = np.finfo(np.float32).eps
    for g in range(G):
        want, mag = _balanced_sk_loop(V[g], r, s, b)
        # float32 summation of m terms, each rounded once from float64
        bound = (m + 1) * eps * mag
        assert np.all(np.abs(got[g] - want) <= bound)
        assert np.all(np.abs(gather[g] - want) <= bound)
        # one client alone, unbatched: the same bits as inside the batch
        np.testing.assert_array_equal(
            got[g], np.asarray(S._balanced_sk_core(V[g], r, s, b)))


def test_balanced_sk_bitwise_across_batch_sizes():
    """Each client's sketch is bit-identical whatever the batch it rides
    in, as the streamed fold's microbatch >= G pins need."""
    cfg = S.SketchConfig(kind="countsketch", ratio=0.05, min_b=16)
    tree = {"w": jnp.zeros((37, 29)), "raw": jnp.zeros((7,)),
            "e": jnp.zeros((3000,))}
    plan = P.make_packing_plan(cfg, tree)
    rp = P.derive_round_params(plan, jax.random.key(5))
    big = jax.tree.map(lambda l: jax.random.normal(
        jax.random.key(l.size), (16,) + l.shape), tree)
    sk = jax.jit(lambda t: P.sk_packed_clients(plan, rp, t))
    full = np.asarray(sk(big))
    for G in (1, 5):
        part = np.asarray(sk(jax.tree.map(lambda l: l[:G], big)))
        np.testing.assert_array_equal(part, full[:G])


@pytest.mark.parametrize("G", [1, 5, 16])
def test_packed_balanced_sketch_matches_gather_oracle(G):
    """The packed engine's client sketch (raw leaves included) against the
    gather oracle leaf by leaf, under the vmap over G clients."""
    cfg = S.SketchConfig(kind="countsketch", ratio=0.05, min_b=16)
    tree = {"w": jnp.zeros((37, 29)), "raw": jnp.zeros((7,)),
            "e": jnp.zeros((3000,)), "one": jnp.zeros((16,))}
    plan = P.make_packing_plan(cfg, tree)
    assert {op.raw for op in plan.ops} == {True, False}
    rp = P.derive_round_params(plan, jax.random.key(G))
    stacked = jax.tree.map(lambda l: jax.random.normal(
        jax.random.key(l.size), (G,) + l.shape), tree)
    got = np.asarray(P.sk_packed_clients(plan, rp, stacked))
    flat = np.asarray(jax.vmap(lambda t: P.pack_tree(plan, t))(stacked))
    for op in plan.ops:
        v = flat[:, op.in_off:op.in_off + op.n]
        seg = got[:, op.pay_off:op.pay_off + op.b]
        if op.raw:
            np.testing.assert_array_equal(seg, v)
            continue
        r, s = rp["bal"][op.index]
        want = np.asarray(jax.vmap(
            lambda x: _balanced_sk_gather(x, r, s, op.b))(jnp.asarray(v)))
        np.testing.assert_allclose(seg, want, rtol=0, atol=1e-5)


def test_balanced_sketch_lowers_without_gather():
    """The client sketch compiles to slices and adds, with no gather op;
    the desketch, still an element gather, keeps one."""
    cfg = S.SketchConfig(kind="countsketch", ratio=0.05, min_b=16)
    tree = {"w": jnp.zeros((40, 30)), "e": jnp.zeros((700,))}
    plan = P.make_packing_plan(cfg, tree)
    key = jax.random.key(0)
    stacked = jax.tree.map(lambda l: jnp.zeros((5,) + l.shape), tree)

    def hlo(f, *args):
        return jax.jit(f).lower(*args).compile().as_text()

    gather = re.compile(r"\sgather\(")
    sk = hlo(lambda t, k: P.sk_packed_clients(
        plan, P.derive_round_params(plan, k), t), stacked, key)
    assert not gather.search(sk)
    desk = hlo(lambda p, k: P.desk_packed(
        plan, P.derive_round_params(plan, k), p),
        jnp.zeros((plan.b_total,)), key)
    assert gather.search(desk)
