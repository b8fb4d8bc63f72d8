"""Per-kernel Pallas (interpret=True) vs pure-jnp/numpy oracle sweeps.

The kernel functions take ``interpret`` with no default; these tests pass
``interpret=True`` themselves (the TPU compile of the same kernels is
checked in tests/test_tpu_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.countsketch import (countsketch_clients_pallas,
                                       countsketch_pallas)
from repro.kernels.fwht import fwht_pallas, fwht_rows_pallas
from repro.kernels.gaussian_sketch import (gaussian_desk_pallas,
                                           gaussian_sk_pallas)


@pytest.mark.parametrize("n", [17, 1000, 1024, 5000])
@pytest.mark.parametrize("b", [8, 128, 300])
def test_countsketch_shapes(n, b):
    rng = np.random.RandomState(n + b)
    x = rng.randn(n).astype(np.float32)
    h = rng.randint(0, b, n).astype(np.int32)
    got = countsketch_pallas(jnp.array(x), jnp.array(h), b, interpret=True)
    want = ref.countsketch_ref(jnp.array(x), jnp.array(h), b)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_countsketch_dtypes(dtype):
    rng = np.random.RandomState(0)
    x = rng.randn(333).astype(dtype)
    h = rng.randint(0, 64, 333).astype(np.int32)
    got = countsketch_pallas(jnp.asarray(x, jnp.float32), jnp.array(h), 64,
                             interpret=True)
    want = ref.countsketch_ref(jnp.asarray(x, jnp.float32), jnp.array(h), 64)
    np.testing.assert_allclose(np.array(got), np.array(want), atol=1e-4)


@pytest.mark.parametrize("b", [2049, 4096])
def test_countsketch_large_b_split_by_grid(b):
    """b beyond one VMEM block is split on the b-block grid axis (the old
    wrapper claimed-but-didn't; now the kernel handles any b)."""
    rng = np.random.RandomState(b)
    x = rng.randn(3000).astype(np.float32)
    h = rng.randint(0, b, 3000).astype(np.int32)
    got = ops.countsketch(jnp.array(x), jnp.array(h), b)
    want = ref.countsketch_ref(jnp.array(x), jnp.array(h), b)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("g,n,b", [(1, 100, 16), (5, 2000, 64),
                                   (9, 1500, 3000)])
def test_countsketch_clients_batched(g, n, b):
    """One launch for all G client rows == per-row reference."""
    rng = np.random.RandomState(g + n)
    x = rng.randn(g, n).astype(np.float32)
    h = rng.randint(0, b, n).astype(np.int32)
    got = countsketch_clients_pallas(jnp.array(x), jnp.array(h), b,
                                     interpret=True)
    want = np.stack([np.array(ref.countsketch_ref(jnp.array(x[i]),
                                                  jnp.array(h), b))
                     for i in range(g)])
    np.testing.assert_allclose(np.array(got), want, rtol=1e-4, atol=1e-4)


def test_countsketch_clients_jit_wrapper():
    x = jnp.ones((3, 100))
    h = jnp.zeros((100,), jnp.int32)
    out = ops.countsketch_clients(x, h, 4)
    assert out.shape == (3, 4)
    np.testing.assert_allclose(np.array(out[:, 0]), 100.0)


@pytest.mark.parametrize("shape", [(1, 8), (3, 64), (20, 512), (9, 4096)])
def test_fwht_rows(shape):
    x = np.random.RandomState(1).randn(*shape).astype(np.float32)
    got = fwht_rows_pallas(jnp.array(x), interpret=True)
    want = ref.fwht_ref(x)
    np.testing.assert_allclose(np.array(got), want, rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("n", [64, 4096, 8192, 32768])
def test_fwht_1d_including_kronecker_path(n):
    x = np.random.RandomState(2).randn(n).astype(np.float32)
    got = fwht_pallas(jnp.array(x), interpret=True)
    want = ref.fwht_ref(x)
    np.testing.assert_allclose(np.array(got), want, rtol=1e-3, atol=0.2)


def test_fwht_rows_wrapper_long_rows():
    """ops.fwht_rows falls back to the per-row Kronecker path for C > MAX_C."""
    x = np.random.RandomState(4).randn(2, 8192).astype(np.float32)
    got = ops.fwht_rows(jnp.array(x))
    want = ref.fwht_ref(x)
    np.testing.assert_allclose(np.array(got), want, rtol=1e-3, atol=0.2)


def test_fwht_involution():
    """H (H x) = n x."""
    n = 1024
    x = np.random.RandomState(3).randn(n).astype(np.float32)
    y = fwht_pallas(fwht_pallas(jnp.array(x), interpret=True), interpret=True)
    np.testing.assert_allclose(np.array(y) / n, x, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,b", [(100, 16), (513, 64), (2000, 128)])
def test_gaussian_sk_matches_ref(n, b):
    x = np.random.RandomState(4).randn(n).astype(np.float32)
    seed = jnp.array(11, jnp.uint32)
    got = gaussian_sk_pallas(seed, jnp.array(x), b, interpret=True)
    want = ref.gaussian_sk_ref(11, x, b)
    np.testing.assert_allclose(np.array(got), want, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,b", [(100, 16), (1500, 128)])
def test_gaussian_desk_matches_ref(n, b):
    s = np.random.RandomState(5).randn(b).astype(np.float32)
    seed = jnp.array(11, jnp.uint32)
    got = gaussian_desk_pallas(seed, jnp.array(s), n, interpret=True)
    want = ref.gaussian_desk_ref(11, s, n)
    np.testing.assert_allclose(np.array(got), want, rtol=1e-3, atol=1e-3)


def test_gaussian_adjointness():
    """<sk(v), s> == <v, desk(s)> iff sk/desk regenerate identical R."""
    n, b = 900, 64
    rng = np.random.RandomState(6)
    v = rng.randn(n).astype(np.float32)
    s = rng.randn(b).astype(np.float32)
    seed = jnp.array(42, jnp.uint32)
    sk = gaussian_sk_pallas(seed, jnp.array(v), b, interpret=True)
    desk = gaussian_desk_pallas(seed, jnp.array(s), n, interpret=True)
    lhs = float(np.array(sk) @ s)
    rhs = float(v @ np.array(desk))
    assert abs(lhs - rhs) < 1e-2 * max(1.0, abs(lhs))


def test_gaussian_tile_statistics():
    """In-kernel PRNG produces (approximately) standard normals."""
    t = ref.gaussian_tile_ref(7, 0, 512, 128)
    assert abs(t.mean()) < 0.02
    assert abs(t.std() - 1.0) < 0.02


def test_ops_wrappers_jit():
    x = jnp.arange(256.0)
    h = jnp.zeros((256,), jnp.int32)
    assert float(ops.countsketch(x, h, 8)[0]) == float(x.sum())
    y = ops.fwht(jnp.ones((64,)))
    assert float(y[0]) == 64.0 and float(jnp.abs(y[1:]).max()) == 0.0
